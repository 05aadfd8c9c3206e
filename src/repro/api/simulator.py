"""The unified simulation session API.

One front-end for every engine in the repo — the paper's workloads (and the
long biological-time runs it motivates) are driven as::

    from repro.api import Simulator
    from repro.configs.microcircuit import MicrocircuitConfig

    sim = Simulator(MicrocircuitConfig(n_scaling=0.05, k_scaling=0.05))
    res = sim.run(1000.0)                      # 1 s of model time
    print(res.rtf, res.summary()["rates_hz"])

    # days of biological time, checkpointed:
    res = sim.run_chunked(3_600_000.0, chunk_ms=10_000.0,
                          checkpoint_dir="ckpt", checkpoint_every=10)

The engine behind the session is a pluggable :class:`~repro.api.backends.
Backend` (``fused`` / ``instrumented`` / ``sharded``), recording goes
through probes instead of the old ``record: str`` enum, the presim
transient is handled once per session (the paper's protocol: discard
0.1 s, then time), and checkpoint/restore round-trips through
``repro.checkpoint.checkpointer``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Callable, Optional, Sequence

import jax
import numpy as np

import jax.numpy as jnp

from repro.analysis.sanitize import RecompileGuard
from repro.api import probes as probes_mod
from repro.api import results as results_mod
from repro.api.backends import Backend, make_backend
from repro.api.results import BatchResult, RunResult
from repro.core import stimulus as stimulus_mod
from repro.core.connectivity import Connectome, build_connectome
from repro.core.engine import SimConfig
from repro.core.neuron import NeuronParams

#: A host span on the profiler's clock (about a microsecond when no
#: profiler runs).
_span = jax.profiler.TraceAnnotation


class Simulator:
    """A simulation session: one network, one engine backend, many runs.

    Parameters
    ----------
    config:
        A model config with ``scale / n_scaling / k_scaling / dt /
        strategy / spike_budget / seed / t_presim`` fields (e.g.
        ``repro.configs.microcircuit.MicrocircuitConfig``). ``scale`` sets
        both scalings at once (NEST-style down-scaling with DC
        compensation); ``spike_budget=None`` derives the event/ell budget
        from the expected rates. Optional when a ``connectome`` is
        supplied directly.
    connectome:
        Pre-built :class:`Connectome` (skips instantiation).
    backend:
        ``"fused"`` | ``"instrumented"`` | ``"sharded"`` or a
        :class:`Backend` instance.
    probes:
        Default recording set: probe names or :class:`Probe` objects.
    stimulus:
        Declarative drive timeline: registry kind names, dicts, or
        ``repro.core.stimulus.Stimulus`` instances (mixed freely).  The
        default (``None``) is the paper's 8 Hz ``poisson_background``;
        an explicit timeline *replaces* it, so include the background
        entry when stimulation should ride on top of it.
    plasticity:
        Declarative plasticity rule: a registry kind name
        (``"pair_stdp"``), a spec dict (``{"kind": "pair_stdp", ...}``),
        or a :class:`~repro.core.plasticity.PlasticityRule` instance.
        Composed into the fused engine loop via the delivery strategy's
        live-weight path (``event`` / ``ell``); the plastic state rides
        with the session state through ``run_chunked`` and
        checkpoint/restore bitwise.
    stdp:
        Deprecated alias: ``True`` or an ``STDPConfig`` — use
        ``plasticity=`` instead.
    sim_config:
        Explicit :class:`SimConfig`; otherwise derived from ``config`` and
        ``**overrides`` (e.g. ``kernels="fused"`` or
        ``kernels=KernelPolicy(lif="pallas")``; the resolved
        :class:`~repro.core.kernel_policy.KernelPolicy` is available
        afterwards as ``sim.sim_config.kernels``).
    """

    def __init__(self, config=None, *, connectome: Optional[Connectome] = None,
                 backend="fused", probes: Sequence = ("pop_counts",),
                 stimulus=None, plasticity=None, stdp=None,
                 neuron: Optional[NeuronParams] = None,
                 sim_config: Optional[SimConfig] = None, key=None,
                 n_devices: Optional[int] = None, **overrides):
        if config is None and connectome is None:
            raise ValueError("pass a model config or a pre-built connectome")
        self.config = config
        seed = int(getattr(config, "seed", 0))
        if connectome is None:
            connectome = build_connectome(
                scale=getattr(config, "scale", None),
                n_scaling=config.n_scaling, k_scaling=config.k_scaling,
                seed=seed, dt=config.dt)
        self.connectome = connectome

        if sim_config is None:
            sim_config = SimConfig(
                dt=getattr(config, "dt", 0.1),
                strategy=getattr(config, "strategy", "event"),
                spike_budget=getattr(config, "spike_budget", None),
                strict_delivery=getattr(config, "strict_delivery", False),
                stimulus=getattr(config, "stimulus", None),
                kernels=getattr(config, "kernels", None),
            )
        if overrides:
            sim_config = dataclasses.replace(sim_config, **overrides)
        if stimulus is not None:
            sim_config = dataclasses.replace(
                sim_config,
                stimulus=stimulus_mod.resolve_timeline(stimulus))
        self.sim_config = sim_config
        self.t_presim = float(getattr(config, "t_presim", 0.0))

        if stdp is not None:
            warnings.warn(
                "the stdp= argument is deprecated; pass plasticity= "
                "(e.g. plasticity='pair_stdp', or a PlasticityRule)",
                DeprecationWarning, stacklevel=2)
            if plasticity is not None:
                raise ValueError("pass plasticity= or the deprecated "
                                 "stdp=, not both")
            plasticity = stdp      # resolve_rule maps True / STDPConfig
        if plasticity is not None:
            from repro.core.plasticity import resolve_rule
            plasticity = resolve_rule(plasticity)
        self.plasticity = plasticity
        self.backend: Backend = make_backend(backend, plasticity=plasticity,
                                             n_devices=n_devices)
        if neuron is not None \
                or not self.backend.built_for(connectome, sim_config):
            self.backend.build(connectome, sim_config, neuron)
        # else: shared-backend fast path — the serve session manager hands
        # one built backend to many sessions; its network tables and
        # compiled executables are reused untouched (Backend.run is pure
        # in the state, so sessions never interfere)
        # backends resolve the config (auto spike budget etc.); expose it
        self.sim_config = getattr(self.backend, "cfg", sim_config)

        self.probes = probes_mod.resolve(probes)
        for p in self.probes:
            if not self.backend.supports_probe(p):
                raise NotImplementedError(
                    f"backend {self.backend.name!r} does not support probe "
                    f"{p.name!r}")

        self._key = key if key is not None else jax.random.PRNGKey(seed)
        self.reset()

    # -- session state ------------------------------------------------------

    def reset(self, key=None) -> None:
        """Fresh dynamical state (new presim transient applies)."""
        if key is not None:
            self._key = key
        self._state = self.backend.init(self._key)
        self._presim_done = False
        self._steps_done = 0
        self._t_model_ms = 0.0
        self._overflow_seen = 0
        # StreamProbe carries (name -> pytree), threaded across runs/chunks
        # of the session so streamed statistics cover the whole horizon
        self._stream_state = {}

    @property
    def state(self):
        """The backend's dynamical state pytree (thread-through, functional)."""
        return self._state

    @property
    def suspended(self) -> bool:
        """True while the device state is released (see :meth:`suspend`)."""
        return self._state is None

    def _require_state(self, what: str) -> None:
        if self._state is None:
            raise RuntimeError(
                f"cannot {what}: this session is suspended (its device "
                f"state was released by suspend()); call resume(directory)"
                f" first")

    @property
    def timers(self):
        """Per-phase cumulative seconds (instrumented backend only)."""
        return getattr(self.backend, "timers", {})

    def _steps(self, t_ms: float) -> int:
        return int(round(t_ms / self.sim_config.dt))

    # -- warmup / presim ----------------------------------------------------

    def warmup(self, t_ms: float, probes: Optional[Sequence] = None,
               include_presim: bool = True) -> None:
        """Compile (and discard) a run of ``t_ms`` so a following ``run``
        of the same length measures execution only. Pure: session state is
        untouched."""
        self._require_state("warmup")
        pr = self.probes if probes is None else probes_mod.resolve(probes)
        self.backend.warmup(self._state, self._steps(t_ms), pr)
        if include_presim and self.t_presim > 0 and not self._presim_done:
            self.backend.warmup(self._state, self._steps(self.t_presim), ())

    def _maybe_presim(self, presim_ms: Optional[float]) -> None:
        t = self.t_presim if presim_ms is None else float(presim_ms)
        if self._presim_done or t <= 0:
            return
        with _span("repro.presim"):
            self._state, _ = self.backend.run(self._state, self._steps(t),
                                              ())
            jax.block_until_ready(self._state)
            self._presim_done = True
            self._check_overflow()

    # -- runs ---------------------------------------------------------------

    def run(self, t_ms: float, *, presim_ms: Optional[float] = None,
            probes: Optional[Sequence] = None) -> RunResult:
        """Simulate ``t_ms`` of model time; returns data + RTF accounting.

        The presim transient (``config.t_presim`` unless overridden) runs
        untimed and unrecorded once per session before the first timed
        phase, as in the paper's measurement protocol.

        Under ``jax.profiler`` the call is a ``repro.run`` span holding
        ``repro.presim`` (when the presim runs), ``repro.dispatch``,
        ``repro.sync`` and ``repro.overflow``.
        """
        self._require_state("run")
        with _span("repro.run"):
            pr = (self.probes if probes is None
                  else probes_mod.resolve(probes))
            _, stream_probes = probes_mod.split_probes(pr)
            self._maybe_presim(presim_ms)
            n_steps = self._steps(t_ms)
            timers0 = dict(self.timers)
            stream_in = {p.name: self._stream_state.get(p.name)
                         for p in stream_probes}
            t0 = time.perf_counter()
            with _span("repro.dispatch"):
                self._state, data = self.backend.run(
                    self._state, n_steps, pr, stream=stream_in)
            with _span("repro.sync"):
                jax.block_until_ready((self._state, data))
            wall = time.perf_counter() - t0
            self._steps_done += n_steps
            self._t_model_ms += n_steps * self.sim_config.dt
            timers = {k: v - timers0.get(k, 0.0)
                      for k, v in self.timers.items()}
            streams = {}
            for p in stream_probes:
                carry = data.pop(p.name)
                self._stream_state[p.name] = carry
                # host-offloaded snapshot: chunked runs keep device memory
                # flat
                streams[p.name] = {"carry": jax.tree.map(np.asarray, carry),
                                   "meta": dict(p.meta)}
            overflow = self._check_overflow()
            return RunResult(
                data=dict(data), t_model_ms=n_steps * self.sim_config.dt,
                n_steps=n_steps, dt=self.sim_config.dt, wall_s=wall,
                overflow=overflow, timers=timers, streams=streams,
                _connectome=self.connectome)

    def _check_overflow(self) -> int:
        """Surface dropped spikes: warn on any new overflow since the last
        run, raise under ``SimConfig.strict_delivery``."""
        with _span("repro.overflow"):
            overflow = self.backend.overflow(self._state)
        if overflow > self._overflow_seen:
            msg = (f"spike delivery dropped {overflow - self._overflow_seen}"
                   f" spike(s) this run ({overflow} cumulative): the "
                   f"per-step spike_budget="
                   f"{self.sim_config.spike_budget} of strategy "
                   f"{self.sim_config.strategy!r} was exceeded — raise "
                   f"spike_budget (or leave it None for the rate-derived "
                   f"auto value)")
            self._overflow_seen = overflow
            if self.sim_config.strict_delivery:
                from repro.core.delivery import DeliveryOverflowError
                raise DeliveryOverflowError(msg)
            warnings.warn(msg, stacklevel=3)
        return overflow

    # -- multi-trial batch runs ---------------------------------------------

    def _trial_seeds(self, n_trials: Optional[int], seeds) -> list:
        if seeds is None:
            if n_trials is None:
                raise ValueError("pass n_trials or explicit seeds")
            base = int(getattr(self.config, "seed", 0))
            return [base + i for i in range(int(n_trials))]
        seeds = [int(s) for s in seeds]
        if n_trials is not None and len(seeds) != int(n_trials):
            raise ValueError(f"{len(seeds)} seeds for n_trials={n_trials}")
        return seeds

    def warmup_batch(self, t_ms: float, n_trials: int,
                     probes: Optional[Sequence] = None,
                     include_presim: bool = True) -> None:
        """Compile a batch run of this shape so a following ``run_batch``
        measures execution only.  Pure: no trial is executed (the fused
        backend AOT-lowers the vmapped program; sequential backends warm
        their per-trial compile caches)."""
        pr = self.probes if probes is None else probes_mod.resolve(probes)
        keys = jnp.stack([jax.random.PRNGKey(s)
                          for s in self._trial_seeds(n_trials, None)])
        states = jax.vmap(self.backend.init)(keys)
        if include_presim and self.t_presim > 0:
            self.backend.warmup_batch(states, self._steps(self.t_presim),
                                      ())
        self.backend.warmup_batch(states, self._steps(t_ms), pr)

    def run_batch(self, t_ms: float, n_trials: Optional[int] = None, *,
                  seeds: Optional[Sequence[int]] = None,
                  presim_ms: Optional[float] = None,
                  probes: Optional[Sequence] = None) -> BatchResult:
        """Simulate ``n_trials`` independent trials of ``t_ms`` each.

        Trial ``i`` starts from the seeded key ``PRNGKey(seeds[i])``
        (default seeds: ``config.seed + i``) and is bit-identical to a
        fresh session run with that key (``sim.reset(PRNGKey(s));
        sim.run(t_ms)``).  On the fused backend all trials execute as
        one vmapped device program over shared network tables; backends
        with per-step dispatch or a busy device mesh (instrumented,
        sharded) fall back to sequential per-trial runs behind the same
        surface.  The presim transient runs per trial, untimed.

        Stream-probe carries thread per trial (each trial's
        ``RunResult.streams`` snapshot covers that trial);
        ``BatchResult.validate()`` pools the moment carries across
        trials.  Spike-budget overflow across the batch is surfaced like
        a single run's (warning, or ``DeliveryOverflowError`` under
        ``strict_delivery``).  The session's own state is untouched.
        """
        with _span("repro.run"):
            seeds = self._trial_seeds(n_trials, seeds)
            pr = self.probes if probes is None else probes_mod.resolve(probes)
            step_probes, stream_probes = probes_mod.split_probes(pr)
            keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
            states = jax.vmap(self.backend.init)(keys)
            t_pre = self.t_presim if presim_ms is None else float(presim_ms)
            if t_pre > 0:
                with _span("repro.presim"):
                    states, _, _ = self.backend.run_batch(
                        states, self._steps(t_pre), ())
                    jax.block_until_ready(states)
            n_steps = self._steps(t_ms)
            # a warmed batch program re-compiling is a perf bug, not a warmup:
            # arm a zero-budget recompile guard exactly when warm
            guard = (RecompileGuard(0, caches=self.backend.caches(),
                                    what=f"run_batch({len(seeds)} trials x "
                                         f"{n_steps} steps) after warmup")
                     if self.backend.is_warm_batch(len(seeds), n_steps,
                                                   tuple(pr))
                     else contextlib.nullcontext())
            t0 = time.perf_counter()
            with guard, _span("repro.dispatch"):
                states, data, trial_walls = self.backend.run_batch(
                    states, n_steps, pr)
            with _span("repro.sync"):
                jax.block_until_ready((states, data))
            wall = time.perf_counter() - t0
            with _span("repro.overflow"):
                overflows = [self.backend.overflow(
                    jax.tree.map(lambda x: x[i], states))
                    for i in range(len(seeds))]

            vmapped = trial_walls is None
            trials = []
            for i in range(len(seeds)):
                data_i = {p.name: np.asarray(data[p.name][i])
                          for p in step_probes}
                streams_i = {}
                for p in stream_probes:
                    carry = jax.tree.map(lambda x: np.asarray(x[i]),
                                         data[p.name])
                    streams_i[p.name] = {"carry": carry, "meta": dict(p.meta)}
                trials.append(RunResult(
                    data=data_i, t_model_ms=n_steps * self.sim_config.dt,
                    n_steps=n_steps, dt=self.sim_config.dt,
                    wall_s=(wall / len(seeds) if vmapped else trial_walls[i]),
                    overflow=overflows[i],
                    streams=streams_i, _connectome=self.connectome))
            overflow = sum(r.overflow for r in trials)
            if overflow > 0:
                msg = (f"spike delivery dropped {overflow} spike(s) across "
                       f"{len(trials)} trial(s): the per-step spike_budget="
                       f"{self.sim_config.spike_budget} of strategy "
                       f"{self.sim_config.strategy!r} was exceeded — raise "
                       f"spike_budget (or leave it None for the rate-derived "
                       f"auto value)")
                if self.sim_config.strict_delivery:
                    from repro.core.delivery import DeliveryOverflowError
                    raise DeliveryOverflowError(msg)
                warnings.warn(msg, stacklevel=2)
            return BatchResult(trials=trials, wall_s=wall, vmapped=vmapped,
                               seeds=list(seeds))

    def run_chunked(self, t_ms: float, chunk_ms: float, *,
                    presim_ms: Optional[float] = None,
                    probes: Optional[Sequence] = None,
                    callback: Optional[Callable[[int, RunResult], None]] = None,
                    checkpoint_dir: Optional[str] = None,
                    checkpoint_every: int = 1) -> RunResult:
        """``run`` split into fixed chunks — the days-of-biological-time
        driver. Bit-identical to a single ``run(t_ms)`` of the same session
        (state threads through chunk boundaries), but probe data lands on
        the host after every chunk (bounded device memory), ``callback(i,
        chunk_result)`` can stream statistics, and ``checkpoint_dir``
        persists the session every ``checkpoint_every`` chunks.  If
        ``strict_delivery`` aborts the run mid-way, the raised
        ``DeliveryOverflowError`` carries the completed chunks as its
        ``partial`` attribute."""
        if chunk_ms <= 0:
            raise ValueError("chunk_ms must be positive")
        self._maybe_presim(presim_ms)
        total = self._steps(t_ms)
        per_chunk = max(1, self._steps(chunk_ms))
        chunks = []
        i = 0
        done = 0
        seen_sizes: set = set()      # chunk lengths already compiled
        while done < total:
            n = min(per_chunk, total - done)
            # chunks 2..N of a given length must hit the compile cache:
            # the whole point of chunking is that only the first chunk
            # (and a possibly-shorter last one) pays a trace+compile
            guard = (RecompileGuard(0, caches=self.backend.caches(),
                                    what=f"run_chunked chunk {i + 1} "
                                         f"({n} steps, already compiled)")
                     if n in seen_sizes else contextlib.nullcontext())
            try:
                with guard:
                    res = self.run(n * self.sim_config.dt, presim_ms=0,
                                   probes=probes)
                seen_sizes.add(n)
            except Exception as e:
                from repro.core.delivery import DeliveryOverflowError
                if isinstance(e, DeliveryOverflowError) and chunks:
                    # strict abort mid-run: don't lose the completed chunks
                    e.partial = results_mod.concat(chunks)
                raise
            res.data = {k: np.asarray(v) for k, v in res.data.items()}
            chunks.append(res)
            done += n
            i += 1
            if callback is not None:
                callback(i, res)
            if checkpoint_dir is not None and i % checkpoint_every == 0:
                self.save(checkpoint_dir)
        return results_mod.concat(chunks)

    # -- checkpoint / restore ----------------------------------------------

    def _package(self):
        return {
            "state": self._state,
            "presim_done": np.asarray(int(self._presim_done), np.int64),
            "steps_done": np.asarray(self._steps_done, np.int64),
            "t_model_ms": np.asarray(self._t_model_ms, np.float64),
        }

    def save(self, directory: str, keep: int = 3) -> str:
        """Persist the session (state + counters) for ``restore``."""
        self._require_state("save")
        from repro.checkpoint import checkpointer
        return checkpointer.save(self._package(), directory,
                                 step=self._steps_done, keep=keep)

    def suspend(self, directory: str, keep: int = 3) -> str:
        """Checkpoint the session, then release its device state.

        The serve subsystem's idle-session hook: a suspended session
        costs no device memory (the state pytree — neuron state, ring
        buffer, plastic weights — is dropped after the save), while the
        backend's compiled executables stay warm for the sessions still
        running.  ``resume`` reverses it exactly (bitwise: the restored
        run continues as if never suspended).  Returns the checkpoint
        path."""
        path = self.save(directory, keep=keep)
        self._state = None
        return path

    def resume(self, directory: str, step: Optional[int] = None) -> None:
        """Undo :meth:`suspend`: re-materialise the device state from the
        checkpoint.  Also valid on a non-suspended session (then equal to
        :meth:`restore`)."""
        if self._state is None:
            # restore() needs a target structure; a fresh init provides
            # the shapes/dtypes and is immediately overwritten
            self._state = self.backend.init(self._key)
        self.restore(directory, step=step)

    def restore(self, directory: str, step: Optional[int] = None) -> None:
        """Resume a saved session: state, presim flag, and step counters.

        The target structure comes from this Simulator, so config/backend
        must match what was saved — a version, structure or shape
        mismatch raises :class:`repro.checkpoint.checkpointer.
        CheckpointMismatchError` naming the offending leaf.

        Stream-probe statistics are NOT part of the checkpoint (their
        carry set depends on the probes of the restoring session, not the
        saving one): the accumulators restart empty at the restore point,
        so streamed statistics cover the post-restore window only —
        never a stale or double-counted one."""
        self._require_state("restore (use resume() on a suspended session)")
        from repro.checkpoint import checkpointer
        pkg = checkpointer.restore(directory, self._package(), step=step)
        self._state = pkg["state"]
        self._presim_done = bool(int(pkg["presim_done"]))
        self._steps_done = int(pkg["steps_done"])
        self._t_model_ms = float(pkg["t_model_ms"])
        self._overflow_seen = self.backend.overflow(self._state)
        self._stream_state = {}    # see docstring: stats restart, cleanly
