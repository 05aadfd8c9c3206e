"""The four-chip cases of ``test_sharded.py``, run in a process of their
own with four virtual CPU devices (a process fixes its device count when
JAX starts): a tiny ``pd14_full_x4`` cell through ``run.run_cell``, sound
and with the timed path broken underneath, the control, the one-key
reference against the per-chip drive, the mesh check, and the program's
collectives; and a tiny ``pd14_full_stdp_x4`` cell, whose program is a
stand-in (the reference in its place, its state handed back in the
sharded plastic layout), sound and with that state broken, its reference
on the host.  Prints one JSON object.

    python3 chipbench/tests/sharded_cases.py <scratch directory>
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import pathlib
import sys
from types import SimpleNamespace

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import calibrate, compare, layers, reference, run  # noqa: E402
from chipbench.tests.helpers import run_tiny, tiny_root  # noqa: E402
from repro.api import backends  # noqa: E402
from repro.core.distributed import localize_ell  # noqa: E402

CHIPS = 4
SEED = 2 ** 33 + 17
KW = dict(config="pd14_full_x4", traffic="scan20",
          limits_of="pd14_full_x4.scan20", chips=CHIPS)
_run = backends.ShardedBackend.run
_gather = jax.lax.all_gather


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _unchanged(self, state, n_steps, probes, stream=None):
    """A call that hands back the state it was given."""
    _, data = _run(self, state, n_steps, probes, stream)
    return state, data


def _half(self, state, n_steps, probes, stream=None):
    """Half of the neurons left out: they keep their state."""
    new, data = _run(self, state, n_steps, probes, stream)
    h = new.V.shape[0] // 2
    kept = {f: getattr(new, f).at[h:].set(getattr(state, f)[h:])
            for f in ("V", "I_ex", "I_in", "refrac")}
    return new._replace(**kept), data


def _local_only(x, axis_name, *, axis=0, tiled=False, **kw):
    """The exchange left out: each chip sees its own spikes alone."""
    out = jnp.zeros((x.shape[0] * CHIPS,) + x.shape[1:], x.dtype)
    at = jax.lax.axis_index(axis_name) * x.shape[0]
    return jax.lax.dynamic_update_slice_in_dim(out, x, at, 0)


def _flipped(x, axis_name, **kw):
    """One spike of the registry altered each step, where the step hands
    it to every chip."""
    g = _gather(x, axis_name, **kw)
    return g.at[0].set(jnp.logical_not(g[0]))


FAULTS = {
    "unchanged": (backends.ShardedBackend, "run", _unchanged),
    "half": (backends.ShardedBackend, "run", _half),
    "no_exchange": (jax.lax, "all_gather", _local_only),
    "spike_altered": (jax.lax, "all_gather", _flipped),
}


def checks(res: dict) -> dict:
    return {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"],
            **{k: c["value"] for k, c in res["checks"].items()}}


def session(tmp: pathlib.Path):
    root, name = tiny_root(tmp, **KW)
    cell = run.load_cell(name, root)
    net = run.make_network(cell.cfg)
    sim, ovf = run.session(cell.cfg, cell.mix, net, SEED,
                           run.CompileCounter(),
                           devices=jax.devices()[:CHIPS])
    return cell, net, sim, ovf


def one_key(tmp: pathlib.Path) -> dict:
    """The reference as one chip draws it (one key over every neuron: the
    first chip's) against two calls of the sharded program."""
    cell, net, sim, ovf = session(tmp)
    sampler = run.Sampler(2, SEED)
    run.window(sim, cell.mix, 0.3, sampler, run.CompileCounter(), ovf)
    n, k = net.targets.shape
    tb = reference.tables(net, cell.cfg)
    c1 = reference.consts(net, cell.cfg, shards=1)
    readings = []
    for before, after, counts in sampler.kept:
        b = run.export_state(before, n, k)
        ref_after, ref_counts = reference.chunk(
            tb, dict(b, key=b["key"][0]), counts.shape[0], c1)
        readings.append(compare.sample_gaps(
            b, run.export_state(after, n, k), counts,
            jax.tree.map(jax.device_get, ref_after), ref_counts))
    numbers = compare.combine(readings)
    return dict(numbers, correct=compare.verdict(numbers, cell.limits))


def control(tmp: pathlib.Path) -> dict:
    """The reference in bfloat16 in the program's place, and in float32."""
    cell, net, sim, _ = session(tmp)
    steps = int(round(cell.mix["chunk_ms"] / cell.cfg["dt_ms"]))
    tb = reference.tables(net, cell.cfg)
    out = {}
    for dtype in ("bfloat16", "float32"):
        numbers = compare.combine(calibrate.control_readings(
            tb, net, cell.cfg, sim.state, steps, calls=2, dtype=dtype,
            shards=CHIPS))
        out[dtype] = dict(numbers,
                          correct=compare.verdict(numbers, cell.limits))
    return out


def collectives(tmp: pathlib.Path) -> dict:
    """The warmed programs' instructions, by whether their opcode is a
    collective and whether the exchange reader takes them for one."""
    _, _, sim, _ = session(tmp)
    out = {"programs": 0, "collective": [], "read_as_collective": []}
    for cache in sim.backend.caches():
        for key in cache.keys():
            prog = cache.peek(key)
            if not hasattr(prog, "as_text"):
                continue
            out["programs"] += 1
            for line in prog.as_text().splitlines():
                head = line.strip().removeprefix("ROOT ")
                if not head.startswith("%") or " = " not in head:
                    continue
                name = head[1:].split(" = ")[0]
                if any(f" {op}(" in head or f" {op}-start(" in head
                       for op in layers.COLLECTIVES):
                    out["collective"].append(name)
                if layers.is_collective(head):
                    out["read_as_collective"].append(name)
    return out


def mesh_elsewhere(tmp: pathlib.Path) -> str:
    """The harness asked for the sharded backend on chips its mesh does
    not take."""
    root, name = tiny_root(tmp, **dict(KW, chips=2))
    cell = run.load_cell(name, root)
    net = run.make_network(cell.cfg)
    try:
        run.simulator(cell.cfg, run.connectome(net, cell.cfg), SEED,
                      devices=jax.devices()[2:4])
    except RuntimeError as e:
        return str(e)
    return ""


class StandIn:
    """A sound sharded plastic program: the plain reference with the drive
    drawn per chip in the program's place.  It keeps its state as the
    reference's leaves and hands it back as ``run.py``'s layout contract
    says, the weights laid out by the program's own ``localize_ell``;
    ``fault(state, start)`` breaks what it hands back."""

    def __init__(self, net, cfg: dict, conn, seed: int, fault=None):
        m = net.model
        n, k = net.targets.shape
        self.conn, self.fault, self.dt = conn, fault, cfg["dt_ms"]
        self.tb = reference.tables(net, cfg)
        self.c = reference.consts(net, cfg, shards=CHIPS)
        key = run.dynamics_key(seed)
        rng = np.random.default_rng(seed % 2 ** 32)
        nrn = cfg["neuron"]
        self.ref = dict(   # V spread up to threshold: plasticity from the start
            V=rng.uniform(nrn["E_L"], nrn["V_th"], n).astype(np.float32),
            I_ex=np.zeros(n, np.float32), I_in=np.zeros(n, np.float32),
            refrac=np.zeros(n, np.int32),
            ring=np.zeros((m.d_max_bins, 2, n + 1), np.float32),
            t=np.int32(0),
            key=np.stack([np.asarray(jax.random.fold_in(key, d))
                          for d in range(CHIPS)]),
            w=np.concatenate([net.weights, np.zeros((1, k), np.float32)]),
            x_pre=np.zeros(n, np.float32), x_post=np.zeros(n, np.float32))
        self.over = 0
        self.start = self.state = self.lay_out()
        self.backend = SimpleNamespace(name="stand-in", caches=lambda: [],
                                       overflow=lambda state: self.over)
        self.sim_config = SimpleNamespace(
            kernels=SimpleNamespace(describe=lambda: "reference"),
            state_dtype=jnp.float32, spike_budget=self.c.budget)

    def lay_out(self):
        n = self.conn.n_total
        tables, meta = localize_ell(dataclasses.replace(
            self.conn, weights=np.asarray(self.ref["w"])[:n]), CHIPS)
        return calibrate.laid_out(self.ref, meta["n_loc"], tables.weights)

    def warmup(self, t_ms: float) -> None:
        pass

    def run(self, t_ms: float):
        after, counts = reference.chunk(self.tb, self.ref,
                                        int(round(t_ms / self.dt)), self.c)
        self.over += int(after["over"])
        self.ref = {name: after[name] for name in self.ref}
        self.state = self.lay_out()
        if self.fault is not None:
            self.state = self.fault(self.state, self.start)
        return SimpleNamespace(data={"pop_counts": counts},
                               overflow=self.over)


def _weights_unchanged(state, start):
    return state[0], start[1]


def _traces_swapped(state, start):
    sim, plastic = state
    return sim, SimpleNamespace(weights=plastic.weights,
                                x_pre=plastic.x_post, x_post=plastic.x_pre)


def _flat_export(state, n, columns):
    """Sharded weights read as the one-chip export reads its flat ones."""
    out = run.export_sharded(state[0], n)
    k = columns.net.targets.shape[1]
    out.update(w=np.asarray(state[1].weights).reshape(-1)[:(n + 1) * k]
               .reshape(n + 1, k),
               x_pre=np.asarray(state[1].x_pre)[:n],
               x_post=np.asarray(state[1].x_post)[:n])
    return out


#: Each fault: what breaks the state handed back, what breaks its export,
#: and the pair-STDP constants that change from the configuration's.  The
#: configuration's two traces decay alike (``tau_plus`` = ``tau_minus``),
#: so there ``x_pre`` and ``x_post`` are one vector and their swap changes
#: nothing; the swap is planted where they decay ten times apart (a trace
#: is compared against its own change over a call, which the swap of two
#: traces that decay alike within a factor of five stays under).
PLASTIC_FAULTS = {
    "blocks_swapped": (lambda state, start:
                       calibrate.swap_blocks(state, 0, 1), None, {}),
    "weights_unchanged": (_weights_unchanged, None, {}),
    "traces_swapped": (_traces_swapped, None, {"tau_minus": 2.0}),
    "flat_export": (None, _flat_export, {}),
}


def plastic(tmp: pathlib.Path, fault=None, export=None,
            stdp: dict = None) -> dict:
    """The stand-in's cell through ``run.run_cell``, the default device
    the last of the four, its reference on the host (``reference_device``
    ``"host"``), the pair-STDP constants ``stdp`` changed; with the
    devices of the tables the check built."""
    root, name = tiny_root(tmp, config="pd14_full_stdp_x4", traffic="scan5",
                           limits_of="pd14_full_x4.scan20", chips=CHIPS)
    tiny = root / "chipbench" / "configs" / "tiny.json"
    cfg = json.loads(tiny.read_text())
    tiny.write_text(json.dumps(dict(cfg, plasticity=dict(
        cfg["plasticity"], **(stdp or {})))))
    own = root / "chipbench" / "cells" / f"{name}.json"
    own.write_text(json.dumps(dict(json.loads(own.read_text()),
                                   reference_device="host")))
    cell = run.load_cell(name, root)
    nets, built = [], []
    make, tables = run.make_network, reference.tables

    def simulator(cfg, conn, seed, state_dtype=None, devices=None):
        return StandIn(nets[-1], cfg, conn, seed, fault)

    def record(net, cfg, device=None):
        tb = tables(net, cfg, device)
        built.append({k: [[str(d) for d in v.devices()], v.committed]
                      for k, v in tb.items()})
        return tb
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.default_device(jax.devices()[CHIPS - 1]))
        stack.enter_context(patched(
            run, "make_network", lambda cfg: nets.append(make(cfg))
            or nets[-1]))
        stack.enter_context(patched(run, "simulator", simulator))
        stack.enter_context(patched(reference, "tables", record))
        if export is not None:
            stack.enter_context(patched(run, "export_sharded_plastic",
                                        export))
        res = run.run_cell(cell, SEED, 2.0, False,
                           devices=jax.devices()[:CHIPS])
    return dict(checks(res), check_tables=built[-1],
                host=str(jax.devices("cpu")[0]))


def main(scratch: str) -> None:
    base = pathlib.Path(scratch)
    # the sound window is long enough for two calls on a loaded CPU
    out = {"sound": checks(run_tiny(base / "sound", SEED, 2.0, **KW)),
           "one_key": one_key(base / "one_key"),
           "control": control(base / "control"),
           "collectives": collectives(base / "collectives"),
           "mesh_elsewhere": mesh_elsewhere(base / "mesh")}
    for fault, (obj, attr, value) in FAULTS.items():
        with patched(obj, attr, value):
            out[fault] = checks(run_tiny(base / fault, SEED, **KW))
    out["plastic"] = {"sound": plastic(base / "plastic")}
    for fault, (state, export, stdp) in PLASTIC_FAULTS.items():
        out["plastic"][fault] = plastic(base / f"plastic_{fault}", state,
                                        export, stdp)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
