"""Probe-based recording for the ``Simulator`` session API.

A probe is a named per-step reducer evaluated inside the simulation loop
(in-scan for the fused backend, per step for the instrumented one).  It
replaces the old ``SimConfig.record: str`` enum: instead of one global
recording mode, a run carries any set of probes and the result maps probe
name -> array with leading axis ``n_steps``.

Built-ins::

    pop_counts()          [T, n_pops] int32 spike counts per population
    spikes()              [T, N] bool raster (memory-heavy at scale)
    total_counts()        [T] int32 network-wide spike count
    voltage(ids=None)     [T, len(ids)] membrane potentials (all N if None)
    mean_plastic_weight() [T] mean plastic weight (requires plasticity=...)
    weight_stats()        streamed mean/std/min/max of the plastic weights
                          (a StreamProbe; requires plasticity=...)
    custom(name, fn)      any reducer ``fn(ctx) -> array``

``ctx`` is a :class:`ProbeContext` with the post-step state, this step's
spike vector, the device-resident network tables, and (when STDP is
composed in) the plastic state.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import (TYPE_CHECKING, Callable, NamedTuple, Optional, Sequence,
                    Union)

import jax
import jax.numpy as jnp

from repro.perf.scopes import scope

if TYPE_CHECKING:
    from repro.core.engine import Network, SimState
    from repro.core.plasticity import PlasticState


class ProbeContext(NamedTuple):
    """What a probe may read each step (all traced values)."""
    state: "SimState"           # post-deliver engine state
    spiked: jnp.ndarray         # [N] bool, this step's spikes
    net: "Network"              # device tables (pop_of, k_ext, ...)
    n_pops: int                 # static population count
    plastic: Optional["PlasticState"] = None   # plasticity-enabled runs only
    plastic_mask: Optional[jnp.ndarray] = None  # [n_syn] bool, plastic synapses


@dataclasses.dataclass(frozen=True)
class Probe:
    """A named per-step reducer. ``fn(ctx) -> jnp.ndarray`` (static shape)."""
    name: str
    fn: Callable[[ProbeContext], jnp.ndarray]

    def __call__(self, ctx: ProbeContext) -> jnp.ndarray:
        with scope("probes"):
            return self.fn(ctx)


def pop_counts() -> Probe:
    """Per-population spike counts — the paper's cheap validation record."""
    def fn(ctx: ProbeContext) -> jnp.ndarray:
        return jax.ops.segment_sum(
            ctx.spiked.astype(jnp.int32), ctx.net.pop_of,
            num_segments=ctx.n_pops, indices_are_sorted=True)
    return Probe("pop_counts", fn)


def spikes() -> Probe:
    """Full boolean spike raster (use for small nets / short horizons)."""
    return Probe("spikes", lambda ctx: ctx.spiked)


def total_counts() -> Probe:
    """Network-wide spike count per step."""
    return Probe(
        "total_counts",
        lambda ctx: jnp.sum(ctx.spiked, dtype=jnp.int32))


def voltage(ids: Optional[Sequence[int]] = None) -> Probe:
    """Membrane-potential traces for ``ids`` (all neurons when None)."""
    idx = None if ids is None else jnp.asarray(ids, jnp.int32)

    def fn(ctx: ProbeContext) -> jnp.ndarray:
        V = ctx.state.neuron.V
        return V if idx is None else V[idx]
    return Probe("voltage", fn)


def mean_plastic_weight() -> Probe:
    """Mean weight over the plastic synapses; needs ``plasticity=``."""
    def fn(ctx: ProbeContext) -> jnp.ndarray:
        if ctx.plastic is None:
            raise ValueError(
                "mean_plastic_weight probe requires a plasticity-enabled "
                "run (pass plasticity=... to Simulator)")
        mask = ctx.plastic_mask
        n_plastic = jnp.maximum(mask.sum(), 1)
        w = ctx.plastic.weights[:mask.shape[0]]
        return jnp.sum(jnp.where(mask, w, 0.0)) / n_plastic
    return Probe("mean_plastic_weight", fn)


def custom(name: str, fn: Callable[[ProbeContext], jnp.ndarray]) -> Probe:
    """Arbitrary reducer; must return a fixed-shape array each step."""
    return Probe(name, fn)


# ---------------------------------------------------------------------------
# Stream probes: stateful accumulators, one value per run instead of per step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class StreamProbe:
    """A stateful per-step accumulator (vs. the per-step-output ``Probe``).

    ``init()`` builds the carry (a pytree of fixed-shape device arrays),
    ``update(carry, spiked)`` absorbs one step's global spike vector.  The
    carry threads through the backend's scan — and, via the Simulator
    session, across ``run``/``run_chunked`` chunk boundaries — so the
    memory cost is the carry size, independent of the horizon.  Each run's
    result carries the current carry snapshot in ``RunResult.streams`` as
    ``{"carry": ..., "meta": ...}``; ``meta`` is static context for the
    finalizer (e.g. sampled ids, bin width).

    Equality is identity (``eq=False``): backend compile caches are keyed
    on probe instances, so reuse one instance across runs of a session.

    ``needs`` declares what ``update`` consumes: ``"spiked"`` (the
    default) receives the global spike vector and runs on every backend
    (the sharded engine feeds it the all-gathered registry); ``"ctx"``
    receives the full :class:`ProbeContext` (plastic state included) and
    is restricted to backends that build one per step (fused).
    """
    name: str
    init: Callable[[], object]
    update: Callable[[object, jnp.ndarray], object]
    meta: dict = dataclasses.field(default_factory=dict)
    needs: str = "spiked"          # "spiked" | "ctx"


def spike_stats(ids, bin_steps: int = 20,
                name: str = "spike_stats") -> StreamProbe:
    """Chunk-streaming spike statistics over the sampled neuron ``ids``.

    Accumulates, on device and inside the simulation scan, the moments
    behind per-population mean rate, CV-ISI and pairwise spike-count
    correlation (see ``repro.validate.stats``); ``repro.validate.
    validate()`` finalizes the carry.  ``bin_steps`` is the correlation
    count-bin width in steps (20 = 2 ms at dt=0.1).

    Use ``repro.validate.sample_ids(c.pop_sizes, per_pop=...)`` to build a
    stratified sample; the O(Ns^2) correlation accumulator is why the
    probe records a sample rather than every neuron.
    """
    import numpy as np

    from repro.validate import stats as VS

    ids = np.asarray(ids, np.int32)
    if ids.ndim != 1 or ids.size == 0:
        raise ValueError(f"ids must be a non-empty 1-D id array, "
                         f"got shape {ids.shape}")
    bin_steps = int(bin_steps)
    if bin_steps < 1:
        raise ValueError(f"bin_steps must be >= 1, got {bin_steps}")
    # intern on content: StreamProbe equality is identity, and backend
    # executable caches key on probe instances — two sessions sampling
    # the same ids must share one probe or every session recompiles
    key = (name, bin_steps, ids.tobytes())
    with _INTERN_LOCK:
        cached = _STREAM_INTERNED.get(key)
        if cached is not None:
            return cached
        dev_ids = jnp.asarray(ids)

        def update(carry, spiked):
            return VS.update_carry(carry, spiked[dev_ids],
                                   bin_steps=bin_steps)

        probe = StreamProbe(name=name,
                            init=lambda: VS.init_carry(ids.size),
                            update=update,
                            meta={"ids": ids, "bin_steps": bin_steps})
        _STREAM_INTERNED[key] = probe
        return probe


def weight_stats(name: str = "weight_stats") -> StreamProbe:
    """Streaming mean/std/min/max of the plastic weights, in-scan.

    The long-horizon learning record: the carry holds the plastic-weight
    distribution statistics of the *last completed step* (plus the step
    count), so a chunked run's per-chunk ``RunResult.streams`` snapshots
    trace the weight trajectory at chunk resolution without ever
    materialising per-step O(n_syn) data.  Requires a plasticity-enabled
    run on a context-passing backend (``Simulator(plasticity=...)``,
    fused); backends that feed stream probes the bare spike vector reject
    it at session construction.
    """
    def init():
        z = jnp.zeros((), jnp.float32)
        return {"steps": jnp.zeros((), jnp.int32),
                "mean": z, "std": z, "min": z, "max": z}

    def update(carry, ctx):
        if not isinstance(ctx, ProbeContext) or ctx.plastic is None:
            raise ValueError(
                "weight_stats probe requires a plasticity-enabled run "
                "(pass plasticity=... to Simulator, fused backend)")
        mask = ctx.plastic_mask
        w = ctx.plastic.weights[:mask.shape[0]].astype(jnp.float32)
        n_p = jnp.maximum(mask.sum(), 1).astype(jnp.float32)
        mean = jnp.sum(jnp.where(mask, w, 0.0)) / n_p
        var = jnp.sum(jnp.where(mask, (w - mean) ** 2, 0.0)) / n_p
        inf = jnp.asarray(jnp.inf, w.dtype)
        return {"steps": carry["steps"] + 1,
                "mean": mean, "std": jnp.sqrt(var),
                "min": jnp.min(jnp.where(mask, w, inf)),
                "max": jnp.max(jnp.where(mask, w, -inf))}

    return StreamProbe(name=name, init=init, update=update,
                       meta={"kind": "weight_stats"}, needs="ctx")


def split_probes(probes: Sequence) -> tuple:
    """(per-step Probes, StreamProbes) partition, order-preserving."""
    step = tuple(p for p in probes if isinstance(p, Probe))
    stream = tuple(p for p in probes if isinstance(p, StreamProbe))
    return step, stream


_BUILTIN = {
    "pop_counts": pop_counts,
    "spikes": spikes,
    "total_counts": total_counts,
    "voltage": voltage,
    "mean_plastic_weight": mean_plastic_weight,
    "weight_stats": weight_stats,
}

ProbeLike = Union[str, Probe, "StreamProbe"]

# name -> interned Probe instance.  Probe equality is identity-based (the
# reducer fn is a fresh closure per factory call), and backend compile
# caches are keyed on Probe instances — resolving the same name twice must
# yield the SAME object or every run would recompile.  Serve worker
# threads resolve probes concurrently, so interning takes _INTERN_LOCK:
# a check-then-insert race would hand two sessions different instances
# of the "same" probe, silently doubling every compile downstream.
_INTERNED: dict = {}

# content-key -> StreamProbe, for parameterised stream-probe factories
# (spike_stats): same sample + bin width -> same instance across sessions
_STREAM_INTERNED: dict = {}

_INTERN_LOCK = threading.Lock()


def resolve(probes: Sequence[ProbeLike]) -> tuple:
    """Normalise a mixed list of names / Probe objects; reject duplicates."""
    out = []
    for p in probes:
        if isinstance(p, str):
            if p not in _BUILTIN:
                raise ValueError(
                    f"unknown probe {p!r}; built-ins: {sorted(_BUILTIN)}")
            with _INTERN_LOCK:
                if p not in _INTERNED:
                    _INTERNED[p] = _BUILTIN[p]()
                p = _INTERNED[p]
        elif not isinstance(p, (Probe, StreamProbe)):
            raise TypeError(f"probe must be a name, Probe or StreamProbe, "
                            f"got {type(p)}")
        out.append(p)
    names = [p.name for p in out]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate probe names: {names}")
    return tuple(out)
