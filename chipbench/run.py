#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 chipbench/run.py --workload pd14_full.scan20 --seed 7 \\
        --seconds 45 --trace 0

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/mixes/<traffic>.json``), with the limits of its correctness
comparison in ``chipbench/cells/<cell>.json``.  Every metric is read by
``chipbench/metrics/<metric>.py``.  A new cell, configuration, mix or
metric is new files and new ``BENCHMARK.json`` entries; nothing here
names one.

One run, in one process:

* set-up (``setup_s``, from process start to the first timed call): the
  network instance is drawn on the device from the configuration's fixed
  ``network_seed`` (:mod:`chipbench.netgen`); the program's ``Simulator``
  is built on the chip's kernels (``kernels`` from the configuration) with
  the dynamics key from ``--seed``, on the backend the configuration names
  (``backend``, over the cell's chips; the default one-chip backend where
  it names none); the mix's one call length is compiled
  (JAX's persistent cache lives in ``chipbench/.cache/jax``); one call
  runs, which includes the configuration's presim;
* the window: ``Simulator.run(chunk_ms)`` again and again until
  ``--seconds`` have passed, each call ending with its per-step population
  spike counts in host memory, each under a zero-compile guard.  A call
  that raises, compiles or drops spikes is ``failed``;
* the check: sampled calls (drawn from the seed) are rerun by the plain
  reference from the state the program started them in, once the
  program's device memory is freed (:mod:`chipbench.compare`).  The
  reference runs on the default device (the first chip), or on the host's
  CPU where the cell's file names ``"reference_device": "host"``: a
  reference that does not fit one chip beside the sampled states.

``--trace 1`` profiles the window and reports the per-layer metrics
instead of the end-to-end ones.  Where the cell's file names
``trace_calls``, the trace stops after that many calls and the window
goes on untraced: writing and reading a trace costs host time in
proportion to its device ops, and a cell with many ops a step on several
chips would otherwise not end in time.  The last line of standard output
is the result as one JSON object; the compared numbers and their limits
are the last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import random  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import List, NamedTuple, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
CACHE = BENCH / ".cache" / "jax"
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class Call(NamedTuple):
    t0: float
    t1: float
    steps: int
    counts: object          # [steps, P] int32, host


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (falls back to module import)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_START


def host_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20


# ---------------------------------------------------------------------------
# What a cell is, found by name
# ---------------------------------------------------------------------------

def load_cell(name: str, root: pathlib.Path = ROOT) -> SimpleNamespace:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by = lambda key, n: next(e for e in spec[key] if e["name"] == n)
    try:
        wl = by("workloads", name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json") from None
    bench = root / "chipbench"
    applies = lambda m: name in m.get("workloads", [name])
    own = json.loads((bench / "cells" / f"{name}.json").read_text())
    return SimpleNamespace(
        name=name, workload=wl, chips=int(wl["chips"]),
        cfg=json.loads((bench / "configs" / f"{wl['config']}.json")
                       .read_text()),
        mix=json.loads((bench / "mixes" / f"{wl['traffic']}.json")
                       .read_text()),
        limits=own["limits"], trace_calls=own.get("trace_calls"),
        reference_device=reference_device(own.get("reference_device")),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
        bench=bench)


def reference_device(where: Optional[str]) -> Optional[str]:
    """A cell file's ``reference_device``: ``None`` (the default device)
    or ``"host"``."""
    if where not in (None, "host"):
        raise SystemExit(f"reference_device {where!r}: only \"host\" (the "
                         "host's CPU) may be named; leave it out for the chip")
    return where


def metric_reader(bench: pathlib.Path, name: str):
    path = bench / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# JAX, the chip, the program
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts XLA compiles and persistent-cache loads, process-wide."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def total(self) -> int:
        return self.compiles + self.cache_hits


def setup_jax(cache: pathlib.Path = CACHE):
    """Persistent compile cache at a fixed path inside the checkout; every
    program is cached, so only a checkout's first run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"this cell needs {n} TPU chip(s); JAX reports "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


def dynamics_key(seed: int):
    """The threefry key of ``--seed`` (any whole number; 64 bits kept)."""
    import jax.numpy as jnp
    s = int(seed) % 2 ** 64
    return jnp.array([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32)


def connectome(net, cfg: dict):
    """The network instance as the program's input type."""
    import numpy as np

    from repro.core.connectivity import Connectome
    m = net.model
    return Connectome(
        n_total=m.n_total, n_exc=m.n_exc, pop_sizes=m.n_pop,
        pop_offsets=m.offsets, targets=net.targets, weights=net.weights,
        dbins=net.dbins, out_degree=net.out_degree,
        n_synapses=int(net.out_degree.sum()), d_max_bins=m.d_max_bins,
        k_ext=m.k_ext[net.pop_of].astype(np.float32),
        i_dc=m.i_dc[net.pop_of].astype(np.float32), w_ext=float(m.w_ext),
        v0_mean=m.v0_mean[net.pop_of].astype(np.float32),
        v0_sd=m.v0_sd[net.pop_of].astype(np.float32), pop_of=net.pop_of,
        k_scaling=float(cfg["k_scaling"]))


def simulator(cfg: dict, conn, seed: int, state_dtype: Optional[str] = None,
              devices=None):
    """The program's session, on the kernels the configuration names, and
    on its ``backend`` over ``devices`` (the cell's chips) where it names
    one."""
    import jax.numpy as jnp

    from repro.api import Simulator
    from repro.configs.microcircuit import MicrocircuitConfig
    from repro.core.params import NeuronParams
    mc = MicrocircuitConfig(
        n_scaling=cfg["n_scaling"], k_scaling=cfg["k_scaling"],
        dt=cfg["dt_ms"], t_presim=cfg["t_presim_ms"],
        strategy=cfg["strategy"], kernels=cfg["kernels"])
    backend = ({} if "backend" not in cfg else
               dict(backend=cfg["backend"], n_devices=len(devices)))
    sim = Simulator(
        mc, connectome=conn, probes=("pop_counts",),
        plasticity=cfg.get("plasticity"),
        neuron=NeuronParams(**cfg["neuron"]), key=dynamics_key(seed),
        state_dtype=getattr(jnp, state_dtype or cfg["state_dtype"]),
        **backend)
    mesh = getattr(sim.backend, "mesh", None)
    if backend and mesh is not None \
            and set(mesh.devices.flat) != set(devices):
        raise RuntimeError(f"the {cfg['backend']} backend's mesh holds "
                           f"{list(mesh.devices.flat)}, not the cell's "
                           f"chips {list(devices)}")
    return sim


def export_state(state, n: int, k: int, columns=None) -> dict:
    """The program's state as the reference's leaves (device arrays; host
    arrays for the sharded backend's state).  A sharded plastic state
    needs ``columns``, the :class:`ShardedColumns` of the network."""
    if hasattr(state, "V"):
        return export_sharded(state, n)
    pair = isinstance(state, tuple) and not hasattr(state, "neuron")
    if pair and hasattr(state[0], "V"):
        return export_sharded_plastic(state, n, columns)
    sim, ps = state if pair else (state, None)
    out = dict(V=sim.neuron.V, I_ex=sim.neuron.I_ex, I_in=sim.neuron.I_in,
               refrac=sim.neuron.refrac, ring=sim.ring, t=sim.t,
               key=sim.key)
    if ps is not None:
        out.update(w=ps.weights[:(n + 1) * k].reshape(n + 1, k),
                   x_pre=ps.x_pre, x_post=ps.x_post)
    return out


def export_sharded(state, n: int) -> dict:
    """The sharded backend's state (``ShardedSimState``: neuron arrays
    padded to ``N_pad`` = chips x ``n_loc``, the ring ``[D, 2, N_pad +
    chips]`` as one block of ``n_loc`` columns and a dump column per chip,
    one key per chip) as the reference's leaves: neurons cut to ``N``,
    each block's ``n_loc`` columns side by side, cut to ``N``, and the
    reference's zero sentinel column after them; the keys ``[chips, 2]``
    as they are."""
    import numpy as np
    n_dev = state.key.shape[0]
    n_loc = state.V.shape[0] // n_dev
    ring = np.asarray(state.ring)
    d = ring.shape[0]
    cols = ring.reshape(d, 2, n_dev, n_loc + 1)[..., :n_loc]
    ring = np.concatenate([cols.reshape(d, 2, n_dev * n_loc)[..., :n],
                           np.zeros((d, 2, 1), ring.dtype)], axis=-1)
    return dict(V=np.asarray(state.V)[:n], I_ex=np.asarray(state.I_ex)[:n],
                I_in=np.asarray(state.I_in)[:n],
                refrac=np.asarray(state.refrac)[:n], ring=ring,
                t=np.asarray(state.t), key=np.asarray(state.key))


# The sharded plastic state.  A sharded program with plasticity hands its
# state back as a pair ``(ShardedSimState, plastic)``:
#
# * ``plastic.weights``: ``[N_pad + 1, chips * k_loc]`` float32, laid out
#   as the sharded delivery's own weight table.  Chip ``d`` (the owner of
#   targets ``[d * n_loc, (d + 1) * n_loc)``, ``n_loc = ceil(N / chips)``)
#   holds columns ``[d * k_loc, (d + 1) * k_loc)``: in row ``s``, source
#   ``s``'s synapses onto ``d``'s neurons in their ELL order, then
#   padding.  The last row is the sentinel source.
# * ``plastic.x_pre``, ``plastic.x_post``: ``[N_pad]`` float32 by global
#   neuron id, the padding neurons after ``N``.
#
# It is the layout the sharded delivery already reads, so a program keeps
# its live weights there and needs no per-step view of another layout.

class ShardedColumns:
    """Where the sharded plastic layout keeps each synapse of the
    network's ELL table ``net.targets``, worked out from the owner rule
    alone (chip ``target // n_loc``): entry ``(s, j)`` onto chip ``d`` is
    the ``r``-th of row ``s``'s synapses onto ``d`` and sits in column
    ``d * k_loc + r``.  The map is built once a run, for the ``k_loc`` of
    the first state read, ``ROWS`` rows and one chip at a time, so that
    host memory stays bounded."""

    ROWS = 4096

    def __init__(self, net, chips: int):
        self.net, self.chips = net, chips
        self.n_loc = -(-net.targets.shape[0] // chips)
        self.k_loc = self.flat = None

    def _owners(self):
        """Each block of rows ``r0:r1`` with the owning chip of each
        entry (``chips`` in the padding)."""
        import numpy as np
        targets = self.net.targets
        n = targets.shape[0]
        for r0 in range(0, n, self.ROWS):
            t = targets[r0:r0 + self.ROWS]
            yield r0, r0 + t.shape[0], np.where(t < n, t // self.n_loc,
                                                self.chips)

    def least_k_loc(self) -> int:
        """The fewest columns a chip's block needs: its most synapses from
        one source."""
        import numpy as np
        return max(1, max(int(np.count_nonzero(owner == d, axis=1).max())
                          for _, _, owner in self._owners()
                          for d in range(self.chips)))

    def index(self, k_loc: int):
        """``[N, K]`` int32: each synapse's flat index in the ``[N_pad +
        1, chips * k_loc]`` sharded weights, -1 in the padding."""
        import numpy as np
        if self.k_loc == k_loc:
            return self.flat
        width = self.chips * k_loc
        if (self.n_loc * self.chips + 1) * width >= 2 ** 31:
            raise ValueError(f"{width} columns overflow an int32 index")
        flat = np.full(self.net.targets.shape, -1, np.int32)
        for r0, r1, owner in self._owners():
            base = np.arange(r0, r1, dtype=np.int32)[:, None] * width
            for d in range(self.chips):
                mine = owner == d
                rank = np.cumsum(mine, axis=1, dtype=np.int32) - 1
                if rank[:, -1].max() >= k_loc:
                    raise ValueError(f"a source has more than {k_loc} "
                                     f"synapses onto chip {d}")
                flat[r0:r1][mine] = (base + d * k_loc + rank)[mine]
        self.k_loc, self.flat = k_loc, flat
        return flat

    def to_reference(self, weights):
        """``[N_pad + 1, chips * k_loc]`` sharded weights as the
        reference's ``[N + 1, K]``: ``net.targets``' column order, the
        network's own values in its padding, the sentinel row 0."""
        import numpy as np
        weights = np.asarray(weights)
        k_loc = weights.shape[1] // self.chips
        if weights.shape != (self.n_loc * self.chips + 1,
                             self.chips * k_loc):
            raise ValueError(f"sharded weights {weights.shape} do not fit "
                             f"{self.chips} chips of {self.n_loc} neurons")
        flat, src = self.index(k_loc), weights.reshape(-1)
        n, k = flat.shape
        w = np.zeros((n + 1, k), np.float32)
        for r0 in range(0, n, self.ROWS):
            at = flat[r0:r0 + self.ROWS]
            w[r0:r0 + at.shape[0]] = np.where(
                at >= 0, src.take(np.maximum(at, 0)),
                self.net.weights[r0:r0 + self.ROWS])
        return w

    def to_sharded(self, w, k_loc: int):
        """The reference's ``[N + 1, K]`` weights in the sharded layout
        (the inverse of :meth:`to_reference`; padding 0)."""
        import numpy as np
        w = np.asarray(w)
        flat = self.index(k_loc)
        out = np.zeros((self.n_loc * self.chips + 1) * self.chips * k_loc,
                       np.float32)
        for r0 in range(0, flat.shape[0], self.ROWS):
            at = flat[r0:r0 + self.ROWS]
            real = at >= 0
            out[at[real]] = w[r0:r0 + at.shape[0]][real]
        return out.reshape(self.n_loc * self.chips + 1, -1)


def export_sharded_plastic(state, n: int, columns: ShardedColumns) -> dict:
    """A sharded plastic state (the pair above) as the reference's leaves:
    :func:`export_sharded`'s, the weights in ``net.targets``' order
    (:meth:`ShardedColumns.to_reference`) and both traces cut to ``N``."""
    import numpy as np
    if columns is None:
        raise ValueError("a sharded plastic state needs the network's "
                         "ShardedColumns to be read")
    sim, plastic = state
    out = export_sharded(sim, n)
    out.update(w=columns.to_reference(plastic.weights),
               x_pre=np.asarray(plastic.x_pre)[:n],
               x_post=np.asarray(plastic.x_post)[:n])
    return out


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

class Sampler:
    """Keeps ``m`` calls drawn uniformly from the window (reservoir,
    seeded): the program's state before and after each, as it handed them
    back (on the device, untouched), and its counts."""

    def __init__(self, m: int, seed: int):
        self.m, self.rng, self.seen, self.kept = m, random.Random(seed), 0, []

    def offer(self, before, after, counts) -> None:
        self.seen += 1
        item = (before, after, counts)
        if len(self.kept) < self.m:
            self.kept.append(item)
        else:
            j = self.rng.randrange(self.seen)
            if j < self.m:
                self.kept[j] = item


def timed_call(sim, chunk_ms: float, counter: CompileCounter, ovf0: int):
    """One call of the session, to its counts in host memory.  ``ovf0`` is
    the spike overflow the call before reported.  Returns ``(t0, t1,
    counts, overflow, ok)``."""
    import jax
    import numpy as np

    from repro.analysis import RecompileGuard
    before = counter.total()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.call"), \
            RecompileGuard(0, caches=sim.backend.caches(),
                           what="a timed call"):
        res = sim.run(chunk_ms)
        counts = np.asarray(res.data["pop_counts"])
    t1 = time.perf_counter()
    ok = counter.total() == before and res.overflow == ovf0
    return t0, t1, counts, res.overflow, ok


def window(sim, mix: dict, seconds: float, sampler: Sampler,
           counter: CompileCounter, ovf: int,
           traced: Optional[int] = None):
    """Calls until ``seconds`` have passed, from ``ovf``, the overflow
    after set-up; the profiler's trace is stopped after ``traced`` calls
    where that is given.  Returns (calls, attempted, failed, (start,
    end)): the window runs from the first call's start to the last call's
    end, and everything between calls counts in it."""
    calls: List[Call] = []
    attempted = failed = 0
    w0 = time.perf_counter()
    while time.perf_counter() - w0 < seconds:
        before = sim.state
        attempted += 1
        try:
            t0, t1, counts, ovf, ok = timed_call(sim, mix["chunk_ms"],
                                                 counter, ovf)
        except Exception:                  # a failed call ends the window
            log(traceback.format_exc())
            failed += 1
            break
        failed += not ok
        calls.append(Call(t0, t1, counts.shape[0], counts))
        sampler.offer(before, sim.state, counts)
        if len(calls) == traced:
            import jax
            jax.profiler.stop_trace()
    span = (calls[0].t0, calls[-1].t1) if calls else (w0, w0)
    return calls, attempted, failed, span


def rerun(tb, c, before, after, counts) -> dict:
    """The readings of one call: the reference from ``before``."""
    import jax
    import numpy as np

    from chipbench import compare, reference
    ref_after, ref_counts = reference.chunk(tb, before, counts.shape[0], c)
    return compare.sample_gaps(
        jax.tree.map(np.asarray, before), jax.tree.map(np.asarray, after),
        counts, jax.tree.map(np.asarray, ref_after), np.asarray(ref_counts))


def host_cpu():
    """The host's CPU device, for a reference that does not fit a chip;
    never a chip in its place."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError("reference_device \"host\": JAX has no CPU "
                           f"backend here ({e}); the reference will not "
                           "run on a chip in its place") from e


def check(samples, net, cfg: dict, limits: dict, tb=None,
          shards: int = 1, where: Optional[str] = None) -> dict:
    """Rerun each sampled call with the plain reference, its drive drawn
    per chip over ``shards`` chips, on the default device or, where
    ``where`` is ``"host"``, on the host's CPU; the compared numbers and
    the verdict."""
    import jax

    from chipbench import compare, reference
    t0 = time.perf_counter()
    device = host_cpu() if where == "host" else None
    place = (contextlib.nullcontext() if device is None
             else jax.default_device(device))
    n, k = net.targets.shape
    columns = ShardedColumns(net, shards)       # built at its first use
    with place:
        tb = reference.tables(net, cfg, device) if tb is None else tb
        c = reference.consts(net, cfg, shards=shards)
        readings = []
        for a, b, counts in samples:
            before = export_state(a, n, k, columns)
            if device is not None:
                before = jax.device_put(before, device)
            readings.append(rerun(tb, c, before,
                                  export_state(b, n, k, columns), counts))
    numbers = compare.combine(readings)
    log(f"check: {len(readings)} call(s) rerun by the reference in "
        f"{time.perf_counter() - t0:.3f} s"
        f"{' on the host' if device is not None else ''}; host_rss_peak_gib="
        f"{host_rss_gib():.3f}; per call: "
        + "; ".join(" ".join(f"{k}={v:.6g}" for k, v in r.items())
                    for r in readings))
    return {"numbers": numbers, "readings": readings,
            "correct": compare.verdict(numbers, limits)}


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def session(cfg: dict, mix: dict, net, seed: int, counter: CompileCounter,
            program_dtype: Optional[str] = None, devices=None):
    """The program's session on ``devices``, compiled for the mix's call
    and past its presim and one untimed call; returns it with its spike
    overflow."""
    t = time.perf_counter()
    sim = simulator(cfg, connectome(net, cfg), seed, program_dtype, devices)
    log(f"setup: simulator backend={sim.backend.name} "
        f"kernels={sim.sim_config.kernels.describe()} "
        f"state_dtype={sim.sim_config.state_dtype.__name__} "
        f"spike_budget={sim.sim_config.spike_budget} "
        f"build_s={time.perf_counter() - t:.3f} "
        f"host_rss_peak_gib={host_rss_gib():.3f}")
    return sim, warm(sim, cfg, mix, counter)


def warm(sim, cfg: dict, mix: dict, counter: CompileCounter) -> int:
    """Compile the mix's call, run the presim and one untimed call;
    returns the spike overflow after it."""
    t = time.perf_counter()
    c0 = (counter.compiles, counter.cache_hits)
    sim.warmup(mix["chunk_ms"])
    first = timed_call(sim, mix["chunk_ms"], counter,   # presim + one call
                       sim.backend.overflow(sim.state))
    log(f"setup: warm-up and presim {cfg['t_presim_ms']} ms: "
        f"{time.perf_counter() - t:.3f} s, compiles="
        f"{counter.compiles - c0[0]} (compile_s={counter.compile_s:.3f}) "
        f"cache_hits={counter.cache_hits - c0[1]} "
        f"first_call_s={first[1] - first[0]:.3f}")
    return first[3]


def make_network(cfg: dict):
    from chipbench import netgen
    t = time.perf_counter()
    net = netgen.network(cfg)
    log(f"setup: network n={net.model.n_total} k={net.targets.shape[1]} "
        f"synapses={int(net.out_degree.sum())} "
        f"gen_s={time.perf_counter() - t:.3f} "
        f"host_rss_peak_gib={host_rss_gib():.3f}")
    return net


def run_cell(cell, seed: int, seconds: float, trace: bool,
             devices=None) -> dict:
    """Set up, measure, check; return the result object.  ``devices``
    skips the look for chips (the tests pass the CPU)."""
    from chipbench import layers, peaks
    from chipbench import trace as T
    if devices is None:
        devs = require_chips(cell.chips)
        jax = setup_jax()
    else:
        import jax
        devs = list(devices)
    counter = CompileCounter()
    cfg, mix = cell.cfg, cell.mix
    net = make_network(cfg)
    sim, ovf = session(cfg, mix, net, seed, counter, devices=devs)

    sampler = Sampler(int(mix["check_chunks"]), seed)
    setup_s = process_age()
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    traced = cell.trace_calls if trace else None
    if trace:
        jax.profiler.start_trace(tmp)
    calls, attempted, failed, span = window(sim, mix, seconds, sampler,
                                            counter, ovf, traced)
    if trace and (traced is None or len(calls) < traced):
        jax.profiler.stop_trace()
    device = device_info(devs)
    log(f"window: {len(calls)} call(s), {failed} failed, "
        f"steps={sum(c.steps for c in calls)} "
        f"spikes={sum(int(c.counts.sum()) for c in calls)} "
        f"memory_peak_bytes={device['memory_peak_bytes']}")
    if traced is not None and len(calls) > traced:
        calls = calls[:traced]           # the part the trace holds
        span = (calls[0].t0, calls[-1].t1)
        log(f"trace: the first {traced} call(s)")

    del sim
    gc.collect()
    verdict = check(sampler.kept, net, cfg, cell.limits, shards=cell.chips,
                    where=cell.reference_device)

    kind = devs[0].device_kind
    run = SimpleNamespace(calls=calls, span=span, net=net, cfg=cfg,
                          mix=mix, setup_s=setup_s, trace=None,
                          chips=len(devs),
                          peaks=(peaks.peaks_for(kind) if kind in peaks.PEAKS
                                 else None))
    wanted = cell.per_layer if trace else cell.end_to_end
    result = {"correct": bool(verdict["correct"]), "attempted": attempted,
              "failed": failed}
    if trace:
        run.trace = T.load(tmp, [d.id for d in devs])
        shutil.rmtree(tmp, ignore_errors=True)
        lo, hi = T.window(run.trace) or (0.0, 0.0)
        device["busy_s"] = T.busy(run.trace, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
    metrics = {}
    for m in wanted:
        value = metric_reader(cell.bench, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        ops = layers.op_self_seconds(run)
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": T.idle_gaps(run.trace, lo, hi)}
    result["checks"] = {k: {"value": v, "limit": cell.limits[k]}
                        for k, v in verdict["numbers"].items()
                        if k in cell.limits}
    result["checks"]["ref_overflow"] = {
        "value": verdict["numbers"]["ref_overflow"], "limit": 0}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"chipbench: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
