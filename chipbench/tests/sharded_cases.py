"""The four-chip cases of ``test_sharded.py``, run in a process of their
own with four virtual CPU devices (a process fixes its device count when
JAX starts): a tiny ``pd14_full_x4`` cell through ``run.run_cell``, sound
and with the timed path broken underneath, the control, the one-key
reference against the per-chip drive, the mesh check, and the program's
collectives.  Prints one JSON object.

    python3 chipbench/tests/sharded_cases.py <scratch directory>
"""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import calibrate, compare, layers, reference, run  # noqa: E402
from chipbench.tests.helpers import run_tiny, tiny_root  # noqa: E402
from repro.api import backends  # noqa: E402

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
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
