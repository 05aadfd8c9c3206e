"""Engine backends behind the ``Simulator`` session API.

A backend owns the device-resident network tables and exposes a tiny
functional protocol::

    build(connectome, sim_config, neuron)   # host-side table construction
    init(key) -> state                       # fresh dynamical state (pytree)
    run(state, n_steps, probes) -> (state', {probe_name: [n_steps, ...]})

Three engines from the seed repo are adapted:

* ``fused``        — the production ``lax.scan`` path (``engine.
                     update_phase`` + ``deliver_phase`` fused per step),
                     optionally with a plasticity rule composed into the
                     loop (``plasticity=`` on the Simulator),
* ``instrumented`` — each phase a separately jitted call with wall-clock
                     timers (absorbs the old ``engine.PhaseRunner``),
* ``sharded``      — NEST's distribution scheme over a device mesh
                     (``DeliveryStrategy.localize`` shard transform +
                     ``distributed.make_sharded_step``).

Each ``build`` resolves the ``SimConfig`` against the connectome first
(``resolve_sim_config``): the delivery-strategy name is validated against
the registry and an unset ``spike_budget`` becomes the rate-derived auto
value, so the resolved config is what the jitted step closures capture.

``run`` is pure in the state: callers (the Simulator) thread the returned
state, which is what makes warmup-compilation, chunked long runs and
checkpoint/restore uniform across engines.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, ClassVar, Dict, FrozenSet, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.api.probes import Probe, ProbeContext, StreamProbe, split_probes
from repro.core import delivery as dlv
# stdlib-only module; the rest of repro.serve resolves lazily (no cycle)
from repro.serve.compile_cache import ExecutableCache
from repro.core import distributed as DD
from repro.core import stimulus as stim
from repro.core.connectivity import Connectome
from repro.core.engine import (SimConfig, SimState, deliver_phase,
                               fused_drive, fused_update_phase, init_state,
                               prepare_network, resolve_sim_config,
                               update_phase)
from repro.core.neuron import NeuronParams, Propagators
from repro.perf import scopes


def _force_split_step(cfg: SimConfig) -> SimConfig:
    """Per-step-dispatch backends have no one-kernel path: pin the resolved
    policy's step to the phase-split loop (per-op choices untouched)."""
    if cfg.kernels is not None and cfg.kernels.step == "fused":
        cfg = dataclasses.replace(
            cfg, kernels=dataclasses.replace(cfg.kernels, step="split"))
    return cfg


class Backend:
    """Protocol base; concrete backends override build/init/run."""

    name: str = "abstract"

    def build(self, c: Connectome, cfg: SimConfig,
              neuron: Optional[NeuronParams] = None) -> None:
        raise NotImplementedError

    def init(self, key) -> Any:
        raise NotImplementedError

    def run(self, state: Any, n_steps: int, probes: Sequence[Probe],
            stream: Optional[Dict[str, Any]] = None
            ) -> Tuple[Any, Dict[str, jnp.ndarray]]:
        """Advance ``n_steps``; returns (state', data).

        ``data`` maps per-step probe names to ``[n_steps, ...]`` arrays and
        :class:`StreamProbe` names to their carry pytree after the run.
        ``stream`` optionally seeds stream-probe carries (``{name:
        carry}``); missing/None entries start fresh via ``probe.init()`` —
        the Simulator threads carries across chunks this way.
        """
        raise NotImplementedError

    def run_batch(self, states, n_steps: int, probes: Sequence[Probe],
                  stream: Optional[Dict[str, Any]] = None
                  ) -> Tuple[Any, Dict[str, jnp.ndarray], Optional[list]]:
        """Advance ``n_trials`` independent states (leading trial axis).

        ``states`` is a pytree whose leaves carry a leading trial axis
        (``jax.vmap``-style batching of ``init``); ``stream`` carries are
        batched the same way.  Returns ``(states', data, walls)`` with
        every ``data`` array gaining a leading trial axis; ``walls`` is
        the list of measured per-trial wall seconds, or ``None`` when the
        trials ran concurrently (one vmapped program has no per-trial
        latency).

        Default implementation: sequential per-trial ``run`` calls (the
        honest fallback for per-step-dispatch and sharded engines — the
        device mesh is already busy with one trial).  The fused backend
        overrides this with a single vmapped device program.
        """
        n_trials = jax.tree.leaves(states)[0].shape[0]
        probes = tuple(probes)
        _, stream_probes = split_probes(probes)
        out_states, out_data, walls = [], [], []
        for i in range(n_trials):
            st_i = jax.tree.map(lambda x: x[i], states)
            stream_i = None
            if stream is not None:
                stream_i = {
                    name: (None if carry is None
                           else jax.tree.map(lambda x: x[i], carry))
                    for name, carry in stream.items()}
            t0 = time.perf_counter()
            st_i, data_i = self.run(st_i, n_steps, probes, stream=stream_i)
            jax.block_until_ready(st_i)
            walls.append(time.perf_counter() - t0)
            out_states.append(st_i)
            out_data.append(data_i)
        states = jax.tree.map(lambda *xs: jnp.stack(xs), *out_states)
        data = {k: jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[d[k] for d in out_data])
                for k in out_data[0]}
        return states, data, walls

    def warmup_batch(self, states, n_steps: int,
                     probes: Sequence[Probe]) -> None:
        """Compile the batch program; must not mutate ``states``.

        Default: per-trial ``warmup`` on trial 0's state (the sequential
        fallback dispatches per trial, so one compiled trial warms all).
        """
        st0 = jax.tree.map(lambda x: x[0], states)
        self.warmup(st0, n_steps, tuple(probes))

    @staticmethod
    def _stream_carries(stream_probes, stream):
        stream = stream or {}
        return tuple(stream[p.name] if stream.get(p.name) is not None
                     else p.init() for p in stream_probes)

    def caches(self) -> Tuple[ExecutableCache, ...]:
        """Every :class:`ExecutableCache` this backend owns — the scope
        the recompile guard (``repro.analysis.sanitize.RecompileGuard``)
        watches when pinning chunked/resumed runs to zero compiles."""
        return tuple(v for v in vars(self).values()
                     if isinstance(v, ExecutableCache))

    def is_warm_batch(self, n_trials: int, n_steps: int,
                      probes: Sequence[Probe]) -> bool:
        """True when a ``run_batch`` of this shape would hit a compiled
        program — the Simulator arms a zero-budget recompile guard around
        the timed run exactly when this holds (a warmed batch that still
        compiles is a perf bug, not a warmup)."""
        return False

    # optional capabilities -------------------------------------------------
    def supports_probe(self, probe: Probe) -> bool:
        return True

    def _normalize_cfg(self, cfg: SimConfig) -> SimConfig:
        """Backend-specific post-resolution fixup (identity by default);
        per-step-dispatch backends pin the kernel policy's step to
        "split" here so ``built_for`` stays in sync with ``build``."""
        return cfg

    def built_for(self, c: Connectome, cfg: SimConfig) -> bool:
        """True when ``build(c, cfg)`` would reproduce the current build —
        the shared-backend fast path: the serve session manager hands one
        built backend to many ``Simulator`` sessions, and the Simulator
        skips the rebuild (keeping the compiled executables warm) when
        this holds."""
        if getattr(self, "c", None) is not c:
            return False
        try:
            return self.cfg == self._normalize_cfg(resolve_sim_config(cfg, c))
        except Exception:
            return False

    def _invalidate_on_rebuild(self, c: Connectome, cfg: SimConfig,
                               *caches) -> None:
        """Clear compiled-executable caches when ``build`` targets a
        different network/config than the current one (the cached runners
        close over the old tables and would silently compute against
        them)."""
        if getattr(self, "c", None) is None:
            return
        if self.c is not c or self.cfg != cfg:
            for cache in caches:
                cache.clear()

    def warmup(self, state: Any, n_steps: int,
               probes: Sequence[Probe]) -> None:
        """Compile the ``run`` of this length; must not mutate ``state``.

        Default: execute-and-discard (``run`` is pure). Backends with
        per-step dispatch override with a cheaper single-step compile.
        """
        jax.block_until_ready(self.run(state, n_steps, tuple(probes))[0])

    def overflow(self, state: Any) -> int:
        """Cumulative spike-budget overflow counter of ``state``."""
        st = state if hasattr(state, "overflow") else state[0]
        return int(np.asarray(st.overflow).sum())


# ---------------------------------------------------------------------------
# Fused production backend (single scan; optional STDP composition)
# ---------------------------------------------------------------------------

class FusedBackend(Backend):
    """The production path: one jitted ``lax.scan`` over the full chunk.

    ``plasticity`` composes a :class:`repro.core.plasticity.PlasticityRule`
    into the scan: the rule is bound against the connectome at build time,
    the delivery strategy's ``live_tables`` swaps the rule's live weight
    view in each step, and the plastic state rides next to the simulation
    state (checkpointed with it).  Requires a strategy with a live-weight
    path (``event`` / ``ell``).
    """

    name = "fused"

    def __init__(self, plasticity=None, stdp=None):
        if stdp is not None:
            if plasticity is not None:
                raise ValueError("pass plasticity= or the deprecated "
                                 "stdp=, not both")
            plasticity = stdp      # resolve_rule maps STDPConfig / True
        self.plasticity = plasticity
        # instrumented compile caches (repro.serve.compile_cache): `_cache`
        # holds jit wrappers (compiled lazily at first call), `_aot` holds
        # lowered-and-compiled executables (warmup), `_batch_cache` the
        # vmapped wrappers.  A cache miss is a new program; hit counters
        # are what the serve subsystem's compile-sharing tests assert.
        self._cache = ExecutableCache("fused.jit")
        self._aot = ExecutableCache("fused.aot")
        self._batch_cache = ExecutableCache("fused.batch")

    def build(self, c, cfg, neuron=None):
        cfg = resolve_sim_config(cfg, c)    # auto spike budget, name check
        self._invalidate_on_rebuild(c, cfg, self._cache, self._aot,
                                    self._batch_cache)
        self.c, self.cfg = c, cfg
        neuron = neuron or NeuronParams()
        self.prop = Propagators.make(neuron, cfg.dt)
        self.net = prepare_network(c, cfg)
        self.n_pops = len(c.pop_sizes)
        self.drive = stim.compile_drive(cfg.stimulus, c, cfg, neuron)
        self._bound = None
        if self.plasticity is not None:
            from repro.core import plasticity as PL
            rule = PL.resolve_rule(self.plasticity)
            strategy = dlv.get_strategy(cfg.strategy)
            if not strategy.supports_live_weights:
                raise ValueError(
                    f"plasticity needs a delivery strategy with a "
                    f"live-weight path (live_tables); {cfg.strategy!r} "
                    f"has none — use 'event' or 'ell'")
            self._bound = rule.bind(c, cfg)

    def init(self, key):
        sim = init_state(self.c, key, self.cfg.state_dtype)
        if self._bound is not None:
            return (sim, self._bound.state0)
        return sim

    def _args(self, state):
        if self._bound is not None:
            return (state, self.net, self._bound.tables)
        return (state, self.net)

    def warmup(self, state, n_steps, probes):
        # AOT lower+compile: no execution, so warming a long scan is cheap
        key = (n_steps, tuple(probes))

        def build():
            fn = self._compiled(*key)
            _, stream_probes = split_probes(key[1])
            carries = self._stream_carries(stream_probes, None)
            compiled = fn.lower(*self._args(state), carries).compile()
            scopes.record(compiled)
            return compiled
        self._aot.get_or_build(key, build)

    def run(self, state, n_steps, probes, stream=None):
        probes = tuple(probes)
        step_probes, stream_probes = split_probes(probes)
        carries = self._stream_carries(stream_probes, stream)
        fn = self._aot.peek((n_steps, probes)) \
            or self._compiled(n_steps, probes)
        state, carries, outs = fn(*self._args(state), carries)
        data = dict(zip((p.name for p in step_probes), outs))
        data.update(zip((p.name for p in stream_probes), carries))
        return state, data

    def _batch_carries(self, stream_probes, stream, n_trials):
        if stream is not None:
            return tuple(stream[p.name] for p in stream_probes)
        return tuple(
            jax.tree.map(lambda x: jnp.broadcast_to(
                x[None], (n_trials,) + x.shape), p.init())
            for p in stream_probes)

    def _batched(self, n_steps: int, probes):
        def build():
            runner = self._runner(n_steps, probes)
            n_net_args = 2 if self._bound is not None else 1
            in_axes = (0,) + (None,) * n_net_args + (0,)
            return jax.jit(jax.vmap(runner, in_axes=in_axes))
        return self._batch_cache.get_or_build((n_steps, probes), build)

    def warmup_batch(self, states, n_steps, probes):
        # AOT lower+compile, like warmup(): no execution, so warming a
        # long multi-trial program costs compile time only
        probes = tuple(probes)
        n_trials = jax.tree.leaves(states)[0].shape[0]

        def build():
            fn = self._batched(n_steps, probes)
            _, stream_probes = split_probes(probes)
            carries = self._batch_carries(stream_probes, None, n_trials)
            compiled = fn.lower(*self._args(states), carries).compile()
            scopes.record(compiled)
            return compiled
        self._aot.get_or_build((n_trials, n_steps, probes), build)

    def is_warm_batch(self, n_trials, n_steps, probes):
        return (n_trials, n_steps, tuple(probes)) in self._aot \
            or (n_steps, tuple(probes)) in self._batch_cache

    def run_batch(self, states, n_steps, probes, stream=None):
        """Vmapped multi-trial execution: one device program, all trials.

        ``states``/``stream`` leaves carry a leading trial axis; network
        tables stay unbatched (in_axes ``None``), so the compiled program
        shares them across trials.  Returns ``walls=None``: trials run
        concurrently, so no per-trial latency exists.
        """
        probes = tuple(probes)
        step_probes, stream_probes = split_probes(probes)
        n_trials = jax.tree.leaves(states)[0].shape[0]
        carries = self._batch_carries(stream_probes, stream, n_trials)
        fn = self._aot.peek((n_trials, n_steps, probes)) \
            or self._batched(n_steps, probes)
        states, carries, outs = fn(*self._args(states), carries)
        data = dict(zip((p.name for p in step_probes), outs))
        data.update(zip((p.name for p in stream_probes), carries))
        return states, data, None

    def _compiled(self, n_steps: int, probes):
        return self._cache.get_or_build(
            (n_steps, probes),
            lambda: jax.jit(self._runner(n_steps, probes)))

    def _runner(self, n_steps: int, probes):
        """The raw (unjitted) scan runner — ``run`` jits it as-is,
        ``run_batch`` wraps it in ``jax.vmap`` first.

        With a resolved ``KernelPolicy`` whose ``step == "fused"`` the scan
        body is the one-kernel rotated loop (``kernels/lif_deliver``):
        iteration ``i`` delivers step ``i-1``'s spikes and integrates step
        ``i`` in a single Pallas launch, and an epilogue after the scan
        delivers the final step's spikes so the returned state is bitwise
        what the phase-split loop produces.  Mid-scan, ``ctx.state.ring``
        (and the plastic weights seen by weight probes) lag one step; no
        builtin probe reads the ring, and the weight-probe lag is pinned in
        the tests.
        """
        c, cfg, prop, drive = self.c, self.cfg, self.prop, self.drive
        n, n_exc, n_pops = c.n_total, c.n_exc, self.n_pops
        pol = cfg.kernels
        fused = pol is not None and pol.resolved and pol.step == "fused"
        step_probes, stream_probes = split_probes(probes)

        def stream_update(scs, spiked, ctx):
            with scopes.scope("probes"):
                return tuple(
                    p.update(sc, ctx if p.needs == "ctx" else spiked)
                    for p, sc in zip(stream_probes, scs))

        if self._bound is None and fused:
            strategy = dlv.get_strategy(cfg.strategy)

            def runner(state, net, carries):
                def step(carry, _):
                    (sim, spk_prev), scs = carry
                    sim, spiked = fused_update_phase(
                        sim, net, prop, cfg, c.w_ext, n, n_exc, spk_prev,
                        drive)
                    ctx = ProbeContext(sim, spiked, net, n_pops)
                    scs = stream_update(scs, spiked, ctx)
                    return ((sim, spiked), scs), tuple(p(ctx)
                                                       for p in step_probes)
                spk0 = jnp.zeros((n,), jnp.bool_)
                ((state, spk_last), carries), outs = jax.lax.scan(
                    step, ((state, spk0), carries), None, length=n_steps)
                # epilogue: the rotated loop leaves the last step's spikes
                # undelivered — land them at their true phase t-1
                with scopes.scope("deliver"):
                    ring, ovf = strategy.deliver(
                        state.ring, net.tables, spk_last, state.t - 1,
                        n_exc, cfg)
                state = SimState(state.neuron, ring, state.t, state.key,
                                 state.overflow + ovf)
                return state, carries, outs
        elif self._bound is None:
            def runner(state, net, carries):
                def step(carry, _):
                    sim, scs = carry
                    sim, spiked = update_phase(sim, net, prop, cfg,
                                               c.w_ext, n, drive)
                    sim = deliver_phase(sim, net, cfg, spiked, n_exc)
                    ctx = ProbeContext(sim, spiked, net, n_pops)
                    scs = stream_update(scs, spiked, ctx)
                    return (sim, scs), tuple(p(ctx) for p in step_probes)
                (state, carries), outs = jax.lax.scan(
                    step, (state, carries), None, length=n_steps)
                return state, carries, outs
        else:
            from repro.core import plasticity as PL
            from repro.kernels import ops as kops
            bound = self._bound
            strategy = dlv.get_strategy(cfg.strategy)
            mask = bound.plastic_mask
            fused = fused and isinstance(bound, PL._BoundPairSTDP)

        if self._bound is not None and fused:
            k_out = bound.k_out
            dep_coef, _, decay_p, decay_m = PL.stdp_coefficients(bound.cfg)

            def runner(state, net, tables, carries):
                # the kernel reads the mask as int32 tiles shaped like the
                # ELL tables (ELL pad, no reorder)
                rows_ell, k_ell = net.tables.targets.shape
                with scopes.scope("plasticity"):
                    pmask = jnp.pad(tables.plastic_out.astype(jnp.int32),
                                    ((0, rows_ell - (n + 1)),
                                     (0, k_ell - k_out)))

                def step(carry, _):
                    (sim, ps, spk_prev), scs = carry
                    key, ext_ex, i_dc = fused_drive(sim, net, cfg, c.w_ext,
                                                    n, drive)
                    with scopes.scope("plasticity"):
                        live = strategy.live_tables(
                            net.tables, bound.weight_view(ps, tables))
                    with scopes.scope("fused_step"):
                        (neuron, ring, spiked, w_out, xpre_o, xpost_o, ids,
                         ovf) = kops.lif_deliver_plastic(
                            sim.neuron, sim.ring, sim.t, spk_prev, live,
                            live.weights, pmask, ps.x_pre, ps.x_post, prop,
                            ext_ex, i_dc, n_exc=n_exc,
                            spike_budget=cfg.spike_budget,
                            dep_coef=dep_coef, decay_p=decay_p,
                            decay_m=decay_m, interpret=pol.interpret)
                    with scopes.scope("plasticity"):
                        w_flat = jnp.concatenate(
                            [w_out[:n + 1, :k_out].reshape(-1),
                             ps.weights[(n + 1) * k_out:]])
                        w_flat = PL.stdp_pot_clip(w_flat, ps.x_pre, ids,
                                                  tables, bound.cfg,
                                                  bound.clip_mask)
                    ps = PL.PlasticState(w_flat, xpre_o, xpost_o)
                    sim = SimState(neuron, ring, sim.t + 1, key,
                                   sim.overflow + ovf)
                    ctx = ProbeContext(sim, spiked, net, n_pops,
                                       plastic=ps, plastic_mask=mask)
                    scs = stream_update(scs, spiked, ctx)
                    return ((sim, ps, spiked), scs), tuple(
                        p(ctx) for p in step_probes)
                sim0, ps0 = state
                spk0 = jnp.zeros((n,), jnp.bool_)
                ((state, ps, spk_last), carries), outs = jax.lax.scan(
                    step, ((sim0, ps0, spk0), carries), None,
                    length=n_steps)
                # epilogue: deliver + full STDP step for the final spikes
                with scopes.scope("plasticity"):
                    live = strategy.live_tables(
                        net.tables, bound.weight_view(ps, tables))
                with scopes.scope("deliver"):
                    ring, ovf = strategy.deliver(
                        state.ring, live, spk_last, state.t - 1, n_exc, cfg)
                state = SimState(state.neuron, ring, state.t, state.key,
                                 state.overflow + ovf)
                with scopes.scope("plasticity"):
                    ps = bound.step(ps, tables, spk_last)
                return (state, ps), carries, outs
        elif self._bound is not None:
            def runner(state, net, tables, carries):
                def step(carry, _):
                    (sim, ps), scs = carry
                    sim, spiked = update_phase(sim, net, prop, cfg,
                                               c.w_ext, n, drive)
                    with scopes.scope("plasticity"):
                        live = strategy.live_tables(
                            net.tables, bound.weight_view(ps, tables))
                    with scopes.scope("deliver"):
                        ring, ovf = strategy.deliver(
                            sim.ring, live, spiked, sim.t, n_exc, cfg)
                    sim = SimState(sim.neuron, ring, sim.t + 1, sim.key,
                                   sim.overflow + ovf)
                    with scopes.scope("plasticity"):
                        ps = bound.step(ps, tables, spiked)
                    ctx = ProbeContext(sim, spiked, net, n_pops,
                                       plastic=ps, plastic_mask=mask)
                    scs = stream_update(scs, spiked, ctx)
                    return ((sim, ps), scs), tuple(p(ctx)
                                                   for p in step_probes)
                (state, carries), outs = jax.lax.scan(
                    step, (state, carries), None, length=n_steps)
                return state, carries, outs

        return runner


# ---------------------------------------------------------------------------
# Instrumented backend (per-phase jits + wall-clock timers)
# ---------------------------------------------------------------------------

class InstrumentedBackend(Backend):
    """Each phase separately jitted and synchronised, as the paper's timers.

    Slower than ``fused`` (per-step dispatch) but attributes wall clock to
    update / deliver (/ record) — the Fig. 1b phase-breakdown measurement.
    Cumulative per-phase seconds accumulate in ``self.timers``.
    """

    name = "instrumented"

    def __init__(self):
        self.timers: Dict[str, float] = {}
        self._warmed: set = set()
        self._stream_cache = ExecutableCache("instrumented.stream")
        self._record_cache = ExecutableCache("instrumented.record")

    def supports_probe(self, probe):
        # per-step dispatch feeds stream probes the bare spike vector;
        # ctx-consuming ones (weight_stats) need the fused plastic loop
        return not (isinstance(probe, StreamProbe) and probe.needs != "spiked")

    def _normalize_cfg(self, cfg):
        return _force_split_step(cfg)

    def build(self, c, cfg, neuron=None):
        cfg = _force_split_step(resolve_sim_config(cfg, c))
        self._invalidate_on_rebuild(c, cfg, self._stream_cache,
                                    self._record_cache)
        if getattr(self, "c", None) is not None:
            self._warmed.clear()
        self.c, self.cfg = c, cfg
        neuron = neuron or NeuronParams()
        self.prop = Propagators.make(neuron, cfg.dt)
        self.net = prepare_network(c, cfg)
        self.n_pops = len(c.pop_sizes)
        self.drive = stim.compile_drive(cfg.stimulus, c, cfg, neuron)
        self._update = jax.jit(lambda s: update_phase(
            s, self.net, self.prop, cfg, c.w_ext, c.n_total, self.drive))
        self._deliver = jax.jit(lambda s, spk: deliver_phase(
            s, self.net, cfg, spk, c.n_exc))

    def init(self, key):
        return init_state(self.c, key, self.cfg.state_dtype)

    def step_timed(self, state, timers: Dict[str, float]):
        """One update+deliver cycle, phases timed separately.

        Returns (state', spiked). Also used by the ``PhaseRunner`` shim.
        """
        t0 = time.perf_counter()
        state, spiked = self._update(state)
        spiked.block_until_ready()
        t1 = time.perf_counter()
        state = self._deliver(state, spiked)
        jax.block_until_ready(state)
        t2 = time.perf_counter()
        timers["update"] = timers.get("update", 0.0) + (t1 - t0)
        timers["deliver"] = timers.get("deliver", 0.0) + (t2 - t1)
        return state, spiked

    def _record_fn(self, probes):
        def build():
            n_pops, net = self.n_pops, self.net

            def record(state, spiked):
                ctx = ProbeContext(state, spiked, net, n_pops)
                return tuple(p(ctx) for p in probes)
            return jax.jit(record)
        return self._record_cache.get_or_build(probes, build)

    def _stream_fn(self, stream_probes):
        def build():
            def upd(carries, spiked):
                return tuple(p.update(c, spiked)
                             for p, c in zip(stream_probes, carries))
            return jax.jit(upd)
        return self._stream_cache.get_or_build(stream_probes, build)

    def warmup(self, state, n_steps, probes):
        # per-step dispatch: compiling the per-phase jits once is enough
        probes = tuple(probes)
        if probes in self._warmed:
            return
        step_probes, stream_probes = split_probes(probes)
        _s, _spk = self._update(state)
        jax.block_until_ready(self._deliver(_s, _spk))
        if step_probes:
            jax.block_until_ready(self._record_fn(step_probes)(_s, _spk))
        if stream_probes:
            carries = self._stream_carries(stream_probes, None)
            jax.block_until_ready(self._stream_fn(stream_probes)(
                carries, _spk))
        self._warmed.add(probes)

    def run(self, state, n_steps, probes, stream=None):
        probes = tuple(probes)
        step_probes, stream_probes = split_probes(probes)
        record = self._record_fn(step_probes)
        carries = self._stream_carries(stream_probes, stream)
        upd = self._stream_fn(stream_probes) if stream_probes else None
        # warm the compile caches without advancing state (calls are pure)
        self.warmup(state, n_steps, probes)

        outs = [[] for _ in step_probes]
        for _ in range(n_steps):
            state, spiked = self.step_timed(state, self.timers)
            if step_probes or stream_probes:
                t0 = time.perf_counter()
                if stream_probes:
                    carries = upd(carries, spiked)
                vals = record(state, spiked) if step_probes else ()
                jax.block_until_ready((vals, carries))
                self.timers["record"] = (self.timers.get("record", 0.0)
                                         + time.perf_counter() - t0)
                for buf, v in zip(outs, vals):
                    buf.append(np.asarray(v))
        data = {p.name: np.stack(buf)
                for p, buf in zip(step_probes, outs)}
        data.update(zip((p.name for p in stream_probes), carries))
        return state, data


# ---------------------------------------------------------------------------
# Sharded backend (NEST's distribution scheme via shard_map)
# ---------------------------------------------------------------------------

class ShardedBackend(Backend):
    """Wraps the delivery strategy's shard transform + ``make_sharded_step``.

    The connectome is regrouped by target-owning device through
    ``DeliveryStrategy.localize`` (for the ELL-layout strategies this is
    ``distributed.localize_ell``); strategies without a shard transform
    (e.g. ``dense``) are rejected at build time.  Records population counts
    through the same ``pop_counts`` probe surface (the all-gathered spike
    registry is reduced in-scan, replicated across devices). Probe support
    is restricted to reductions computable from the spike registry:
    ``pop_counts`` and ``total_counts``.
    """

    name = "sharded"
    _SUPPORTED: ClassVar[FrozenSet[str]] = frozenset(
        {"pop_counts", "total_counts"})
    # StreamProbes are additionally supported: their update consumes the
    # all-gathered global spike vector (replicated on every device), so the
    # carry stays replicated and rides in the scan next to the state.

    def __init__(self, n_devices: Optional[int] = None):
        self.n_devices = n_devices
        self._cache = ExecutableCache("sharded.jit")
        self._aot = ExecutableCache("sharded.aot")

    def _normalize_cfg(self, cfg):
        return _force_split_step(cfg)

    def build(self, c, cfg, neuron=None):
        cfg = _force_split_step(resolve_sim_config(cfg, c))
        self._invalidate_on_rebuild(c, cfg, self._cache, self._aot)
        strategy = dlv.get_strategy(cfg.strategy)
        if not strategy.supports_sharding:
            raise ValueError(
                f"sharded backend needs a delivery strategy with a shard "
                f"transform (ELL layout); {cfg.strategy!r} provides none — "
                f"use strategy='event' or 'ell'")
        self.c, self.cfg = c, cfg
        neuron = neuron or NeuronParams()
        self.prop = Propagators.make(neuron, cfg.dt)
        self.drive = stim.compile_drive(cfg.stimulus, c, cfg, neuron)
        if not self.drive.separable:
            raise NotImplementedError(
                "the sharded backend supports separable stimuli only "
                "(basis x time-gate form, as all built-ins are); run "
                "general custom stimuli on the fused backend")
        n_dev = self.n_devices or len(jax.devices())
        if n_dev > len(jax.devices()):
            raise ValueError(f"n_devices={n_dev} > available "
                             f"{len(jax.devices())}")
        self.n_dev = n_dev
        from repro.launch.mesh import make_mesh_auto
        self.mesh = make_mesh_auto((n_dev,), ("flat",))
        tables, self.meta = strategy.localize(c, n_dev)
        # each device receives only its own columns, straight from the host
        specs = DD.table_specs(self.mesh.axis_names)
        self.tables = DD.ShardedTables(*(
            jax.device_put(x, NamedSharding(self.mesh, p))
            for x, p in zip(tables, specs)))
        self.n_pops = len(c.pop_sizes)
        spike_b, cur_b = self.drive.padded_bases(self.meta["n_pad"])
        self._drive_bases = (jnp.asarray(spike_b), jnp.asarray(cur_b))
        # global population index padded with a sentinel population so the
        # in-scan segment_sum can drop the padding neurons
        pop_of = np.full(self.meta["n_pad"], self.n_pops, np.int32)
        pop_of[:c.n_total] = c.pop_of
        self.pop_of = jnp.asarray(pop_of)

    def supports_probe(self, probe):
        if isinstance(probe, StreamProbe):
            # the sharded scan feeds stream probes the all-gathered spike
            # vector only; ctx-consuming probes are fused-backend features
            return probe.needs == "spiked"
        return probe.name in self._SUPPORTED

    def warmup(self, state, n_steps, probes):
        _, stream_probes = split_probes(tuple(probes))

        def build():
            fn = self._compiled(n_steps, stream_probes)
            carries = self._stream_carries(stream_probes, None)
            with self.mesh:
                return fn.lower(state, self.tables, carries,
                                self._drive_bases).compile()
        self._aot.get_or_build((n_steps, stream_probes), build)

    def init(self, key):
        c, meta, n_dev = self.c, self.meta, self.n_dev
        st0 = init_state(c, key)            # the sharded engine is f32-only
        n_pad = meta["n_pad"]
        pad = n_pad - c.n_total
        V = jnp.pad(st0.neuron.V, (0, pad),
                    constant_values=self.prop.V_reset)
        if n_dev == 1:
            keys = st0.key[None]           # bit-identical to the fused path
        else:
            keys = jax.vmap(lambda i: jax.random.fold_in(st0.key, i))(
                jnp.arange(n_dev))
        return DD.ShardedSimState(
            V=V,
            I_ex=jnp.zeros(n_pad), I_in=jnp.zeros(n_pad),
            refrac=jnp.zeros(n_pad, jnp.int32),
            ring=jnp.zeros((c.d_max_bins, 2, n_pad + n_dev)),
            t=jnp.zeros((), jnp.int32),
            key=keys,
            overflow=jnp.zeros((n_dev,), jnp.int32))

    def run(self, state, n_steps, probes, stream=None):
        probes = tuple(probes)
        for p in probes:
            if not self.supports_probe(p):
                raise NotImplementedError(
                    f"sharded backend records {sorted(self._SUPPORTED)} "
                    f"and StreamProbes only, got probe {p.name!r}")
        step_probes, stream_probes = split_probes(probes)
        carries = self._stream_carries(stream_probes, stream)
        fn = self._aot.peek((n_steps, stream_probes)) \
            or self._compiled(n_steps, stream_probes)
        with self.mesh:
            state, pop_counts, carries = fn(state, self.tables, carries,
                                            self._drive_bases)
        data = {}
        for p in step_probes:
            if p.name == "pop_counts":
                data[p.name] = pop_counts
            elif p.name == "total_counts":
                data[p.name] = jnp.sum(pop_counts, axis=1)
        data.update(zip((p.name for p in stream_probes), carries))
        return state, data

    def _compiled(self, n_steps: int, stream_probes=()):
        def build():
            c, cfg = self.c, self.cfg
            sim = DD.make_sharded_step(
                self.mesh, self.meta, self.prop, n_exc=c.n_exc,
                w_ext=c.w_ext, drive=self.drive, dt=cfg.dt,
                spike_budget=cfg.spike_budget, n_steps=n_steps,
                pop_of=self.pop_of, n_pops=self.n_pops,
                stream_probes=stream_probes)
            return jax.jit(sim)
        return self._cache.get_or_build((n_steps, stream_probes), build)


REGISTRY = {
    "fused": FusedBackend,
    "instrumented": InstrumentedBackend,
    "sharded": ShardedBackend,
}


def make_backend(spec, *, plasticity=None, stdp=None,
                 n_devices=None) -> Backend:
    """Resolve a backend name / instance; thread backend-specific options."""
    if stdp is not None:
        if plasticity is not None:
            raise ValueError("pass plasticity= or the deprecated stdp=, "
                             "not both")
        plasticity = stdp
    if isinstance(spec, Backend):
        if plasticity is not None \
                and getattr(spec, "plasticity", None) is None:
            raise ValueError("pass plasticity= to the backend constructor "
                             "when supplying a backend instance")
        return spec
    if spec not in REGISTRY:
        raise ValueError(f"unknown backend {spec!r}; "
                         f"available: {sorted(REGISTRY)}")
    if spec == "fused":
        return FusedBackend(plasticity=plasticity)
    if plasticity is not None:
        raise NotImplementedError(f"plasticity (stdp) is only composed "
                                  f"into the fused backend, not {spec!r}")
    if spec == "sharded":
        return ShardedBackend(n_devices=n_devices)
    return REGISTRY[spec]()
