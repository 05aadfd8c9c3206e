"""Simulation engine: the update -> deliver -> communicate cycle as a scan.

Mirrors the phase structure the paper instruments (Fig. 1b):

* ``update``      — exact-integration LIF step + Poisson external drive
                    (optionally the fused Pallas ``lif_update`` kernel),
* ``deliver``     — spike propagation into the delay ring buffer, dispatched
                    through the :mod:`repro.core.delivery` strategy registry
                    (``event`` | ``dense`` | ``ell`` out of the box;
                    ``SimConfig.strategy`` names the registered strategy),
* ``communicate`` — in the sharded engine, the all-gather of the spike
                    registry (see ``repro.launch.dryrun`` / ``sharded_step``);
                    a no-op on a single device.

``simulate`` fuses the cycle into one ``lax.scan`` (production mode);
``PhaseRunner`` exposes each phase as a separately jitted function so the
benchmark harness can reproduce the paper's phase-breakdown measurement.

.. deprecated::
    ``simulate`` and ``PhaseRunner`` are kept as thin shims for existing
    callers; new code should drive runs through ``repro.api.Simulator``
    (``backend="fused"`` / ``backend="instrumented"``), which adds probes,
    chunked long runs, checkpointing, and RTF accounting on top of the
    same phase functions.  Plasticity composes at that layer too: the
    fused backend swaps the bound rule's live weight view into the
    delivery step (``DeliveryStrategy.live_tables``) and advances the
    plastic state next to ``SimState`` — see ``repro.core.plasticity``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import delivery as dlv
from repro.core import kernel_policy as kpol
from repro.core import stimulus as stim
from repro.core.connectivity import Connectome
from repro.core.kernel_policy import KernelPolicy
from repro.core.neuron import NeuronParams, NeuronState, Propagators, lif_step
from repro.core.params import InputParams
from repro.perf.scopes import scope

_DEFAULT_BG_RATE = 8.0


@dataclasses.dataclass(frozen=True)
class SimConfig:
    dt: float = 0.1
    strategy: str = "event"            # a repro.core.delivery registry name:
                                       # "event" | "dense" | "ell" | custom
    spike_budget: Optional[int] = None # max spikes delivered per step
                                       # (event/ell); None -> rate-derived
                                       # auto via resolve_sim_config
    strict_delivery: bool = False      # raise DeliveryOverflowError instead
                                       # of warning when spikes were dropped
    record: str = "pop_counts"         # "spikes" | "pop_counts" | "none"
    use_lif_kernel: bool = False       # deprecated: kernels=KernelPolicy(
                                       # lif="pallas")
    use_deliver_kernel: bool = False   # deprecated: kernels=KernelPolicy(
                                       # deliver="pallas")
    bg_rate: float = _DEFAULT_BG_RATE  # deprecated: set stimulus= instead
    state_dtype: type = jnp.float32    # V / currents / ring precision
    stimulus: Optional[tuple] = None   # tuple of repro.core.stimulus.Stimulus
                                       # (None -> the bg_rate Poisson drive;
                                       # resolve_sim_config fills it)
    kernels: Optional[Any] = None      # KernelPolicy | mode string
                                       # ("auto"|"fused"|"split"|"reference");
                                       # resolve_sim_config resolves it


def resolve_sim_config(cfg: SimConfig, c: Connectome) -> SimConfig:
    """Fill connectome-dependent defaults: validates the strategy name,
    derives ``spike_budget`` from the expected firing rates when unset,
    resolves the kernel policy against the platform/connectome, and
    normalises the stimulus timeline (an unset ``stimulus`` becomes the
    ``poisson_background`` registry entry carrying the legacy ``bg_rate``).
    The api backends call this in ``build``; direct ``deliver_phase`` users
    must resolve before tracing."""
    dlv.get_strategy(cfg.strategy)
    if cfg.spike_budget is None:
        cfg = dataclasses.replace(
            cfg, spike_budget=dlv.auto_spike_budget(c, cfg.dt))
    if kpol.policy_of(cfg) is None:
        if cfg.use_lif_kernel or cfg.use_deliver_kernel:
            warnings.warn(
                "SimConfig.use_lif_kernel / use_deliver_kernel are "
                "deprecated; select kernels with SimConfig.kernels=, e.g. "
                "kernels=KernelPolicy(lif='pallas', deliver='pallas') or "
                "kernels='split'", DeprecationWarning, stacklevel=3)
        cfg = dataclasses.replace(cfg, kernels=kpol.resolve(
            cfg.kernels, strategy=cfg.strategy, state_dtype=cfg.state_dtype,
            n_total=c.n_total, d_max_bins=c.d_max_bins,
            use_lif_kernel=cfg.use_lif_kernel,
            use_deliver_kernel=cfg.use_deliver_kernel))
    if cfg.stimulus is None:
        if cfg.bg_rate != _DEFAULT_BG_RATE:
            warnings.warn(
                "SimConfig.bg_rate is deprecated; declare the drive with "
                "stimulus registry entries instead, e.g. stimulus="
                f"(repro.core.stimulus.PoissonBackground(rate_hz="
                f"{cfg.bg_rate}),)", DeprecationWarning, stacklevel=3)
        cfg = dataclasses.replace(
            cfg, stimulus=(stim.PoissonBackground(rate_hz=cfg.bg_rate),))
    else:
        cfg = dataclasses.replace(
            cfg, stimulus=stim.resolve_timeline(cfg.stimulus))
    return cfg


class Network(NamedTuple):
    """Device-resident network tables (pytree).

    ``tables`` is whatever the selected delivery strategy's ``prepare``
    returned (EventTables for event/ell, DenseTables for dense, any pytree
    for custom registrations).
    """
    tables: Any
    k_ext: jnp.ndarray      # [N]
    i_dc: jnp.ndarray       # [N]
    pop_of: jnp.ndarray     # [N] int32
    v0_mean: jnp.ndarray
    v0_sd: jnp.ndarray

    @property
    def event(self) -> Optional[dlv.EventTables]:
        """Deprecated accessor kept for pre-registry callers."""
        warnings.warn("Network.event is deprecated; use Network.tables",
                      DeprecationWarning, stacklevel=2)
        t = self.tables
        return t if isinstance(t, dlv.EventTables) else None

    @property
    def dense(self) -> Optional[dlv.DenseTables]:
        """Deprecated accessor kept for pre-registry callers."""
        warnings.warn("Network.dense is deprecated; use Network.tables",
                      DeprecationWarning, stacklevel=2)
        t = self.tables
        return t if isinstance(t, dlv.DenseTables) else None


class SimState(NamedTuple):
    neuron: NeuronState
    ring: jnp.ndarray       # [D, 2, N+1]
    t: jnp.ndarray          # int32 step counter (ring phase)
    key: jnp.ndarray
    overflow: jnp.ndarray   # int32 cumulative spike-budget overflow


def prepare_network(c: Connectome, cfg: SimConfig,
                    dense_dtype=jnp.float32) -> Network:
    """Build the device tables of the registered delivery strategy named by
    ``cfg.strategy`` (raises with the available names on a miss).

    Every strategy is called through the uniform ``prepare(c, cfg)``
    protocol; ``dense_dtype`` is honoured only for the stock dense
    strategy's weight tensor (and only when non-default — custom
    registrations are never forced to accept extra keywords).
    """
    strategy = dlv.get_strategy(cfg.strategy)
    if (dense_dtype is not jnp.float32
            and type(strategy) is dlv.DenseDelivery):
        tables = strategy.prepare(c, cfg, dtype=dense_dtype)
    else:
        tables = strategy.prepare(c, cfg)
    return Network(
        tables=tables,
        k_ext=jnp.asarray(c.k_ext),
        i_dc=jnp.asarray(c.i_dc),
        pop_of=jnp.asarray(c.pop_of),
        v0_mean=jnp.asarray(c.v0_mean),
        v0_sd=jnp.asarray(c.v0_sd),
    )


def init_state(c: Connectome, key, state_dtype=jnp.float32,
               w_ext_dtype=None) -> SimState:
    """Optimized initial conditions (Rhodes et al. 2019), as in the paper.

    ``state_dtype`` sets the precision of the dynamical state (V, synaptic
    currents, ring buffer).  The old name ``w_ext_dtype`` was misleading (it
    never touched the external weights) and is kept only as a deprecated
    alias.
    """
    if w_ext_dtype is not None:
        warnings.warn(
            "init_state(w_ext_dtype=...) is deprecated; the parameter sets "
            "the state precision — use state_dtype=... (or "
            "SimConfig.state_dtype)", DeprecationWarning, stacklevel=2)
        state_dtype = w_ext_dtype
    n = c.n_total
    k_v, k_sim = jax.random.split(jax.random.PRNGKey(0) if key is None else key)
    V = (jnp.asarray(c.v0_mean)
         + jnp.asarray(c.v0_sd) * jax.random.normal(k_v, (n,), jnp.float32))
    neuron = NeuronState(
        V=V.astype(state_dtype),
        I_ex=jnp.zeros((n,), state_dtype),
        I_in=jnp.zeros((n,), state_dtype),
        refrac=jnp.zeros((n,), jnp.int32),
    )
    ring = jnp.zeros((c.d_max_bins, 2, n + 1), state_dtype)
    return SimState(neuron=neuron, ring=ring, t=jnp.zeros((), jnp.int32),
                    key=k_sim, overflow=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def _external_drive(state: SimState, net: Network, cfg: SimConfig,
                    w_ext: float, dtype,
                    drive: Optional[stim.Drive] = None):
    """Advance the step key and evaluate the external drive.

    Returns ``(key, ext_ex, i_dc)`` where ``ext_ex`` is the external
    excitatory current contribution (already scaled by ``w_ext``; None when
    the drive produces no spike input this step) and ``i_dc`` the effective
    DC term.  Shared between the phase-split path and the fused one-kernel
    step so both see bitwise-identical drive values.
    """
    with scope("drive"):
        i_dc = net.i_dc
        if drive is None:
            key, sub = jax.random.split(state.key)
            lam = net.k_ext * (cfg.bg_rate * cfg.dt * 1e-3)
            ext = jax.random.poisson(sub, lam, dtype=jnp.int32)
            ext_ex = w_ext * ext.astype(dtype)
        else:
            keys = jax.random.split(state.key, drive.n_keys + 1)
            key = keys[0]
            I_ext, ext_in = drive(tuple(keys[1:]), state.t, state)
            ext_ex = (None if ext_in is None
                      else w_ext * ext_in.astype(dtype))
            if I_ext is not None:
                i_dc = i_dc + I_ext
    return key, ext_ex, i_dc


def fused_drive(state: SimState, net: Network, cfg: SimConfig,
                w_ext: float, n: int, drive: Optional[stim.Drive] = None):
    """:func:`_external_drive` with its terms as the fused kernels take
    them: ``ext_ex`` and ``i_dc`` both dense ``[n]`` in the ring dtype."""
    dtype = state.ring.dtype
    with scope("drive"):
        key, ext_ex, i_dc = _external_drive(state, net, cfg, w_ext, dtype,
                                            drive)
        if ext_ex is None:
            ext_ex = jnp.zeros((n,), dtype)
        i_dc = jnp.broadcast_to(i_dc, (n,)).astype(dtype)
    return key, ext_ex, i_dc


def update_phase(state: SimState, net: Network, prop: Propagators,
                 cfg: SimConfig, w_ext: float, n: int,
                 drive: Optional[stim.Drive] = None):
    """Read ring slot, add the external drive, integrate, detect spikes.

    ``drive`` is a compiled stimulus timeline (``repro.core.stimulus.
    compile_drive``); the engine splits the step key into ``drive.n_keys
    + 1`` subkeys and applies the drive's spike counts through ``w_ext``
    and its currents through the DC term.  ``drive=None`` keeps the
    pre-registry hardcoded Poisson path (reads ``cfg.bg_rate``) — the
    bitwise reference the equivalence tests pin the default timeline to.
    """
    key, ext_ex, i_dc = _external_drive(state, net, cfg, w_ext,
                                        state.ring.dtype, drive)
    with scope("lif_update"):
        D = state.ring.shape[0]
        slot = state.t % D
        arrivals = jax.lax.dynamic_index_in_dim(
            state.ring, slot, axis=0, keepdims=False)       # [2, N+1]
        in_ex = arrivals[0, :n]
        in_in = arrivals[1, :n]
        if ext_ex is not None:
            in_ex = in_ex + ext_ex

        pol = kpol.policy_of(cfg)
        use_kernel = (cfg.use_lif_kernel if pol is None
                      else pol.lif == "pallas")
        if use_kernel:
            from repro.kernels import ops as kops
            neuron, spiked = kops.lif_update(
                state.neuron, prop, in_ex, in_in, i_dc,
                interpret=None if pol is None else pol.interpret)
        else:
            neuron, spiked = lif_step(state.neuron, prop, in_ex, in_in,
                                      i_dc)

        # consume the slot
        ring = jax.lax.dynamic_update_index_in_dim(
            state.ring, jnp.zeros_like(arrivals), slot, axis=0)
    return SimState(neuron, ring, state.t, key, state.overflow), spiked


def fused_update_phase(state: SimState, net: Network, prop: Propagators,
                       cfg: SimConfig, w_ext: float, n: int, n_exc: int,
                       spiked_prev: jnp.ndarray,
                       drive: Optional[stim.Drive] = None):
    """One rotated step of the fused one-kernel path (static weights).

    Iteration ``i`` of the fused loop delivers the *previous* step's spikes
    (at ring phase ``t-1``) and then integrates step ``i`` — the same
    global op sequence as ``update_phase``/``deliver_phase`` interleaved,
    so the trajectory is bitwise-identical.  The caller seeds
    ``spiked_prev`` with zeros and must flush the final step's spikes with
    a trailing ``deliver_phase``-style call after the scan (the backends'
    epilogue does this).

    Returns ``(state, spiked)`` with ``state.t`` advanced by one.
    """
    from repro.kernels import ops as kops
    pol = kpol.policy_of(cfg)
    key, ext_ex, i_dc = fused_drive(state, net, cfg, w_ext, n, drive)
    with scope("fused_step"):
        neuron, ring, spiked, ovf = kops.lif_deliver(
            state.neuron, state.ring, state.t, spiked_prev, net.tables,
            prop, ext_ex, i_dc, n_exc=n_exc, spike_budget=cfg.spike_budget,
            interpret=None if pol is None else pol.interpret)
    return SimState(neuron, ring, state.t + 1, key,
                    state.overflow + ovf), spiked


def deliver_phase(state: SimState, net: Network, cfg: SimConfig,
                  spiked: jnp.ndarray, n_exc: int):
    """Dispatch one step's spikes through the registered delivery strategy.

    ``cfg.strategy`` is a plain string (jit-static), resolved against the
    :data:`repro.core.delivery.REGISTRY` at trace time; the strategy's
    ``deliver`` scatters into the ring and reports budget overflow.
    """
    strategy = dlv.get_strategy(cfg.strategy)
    with scope("deliver"):
        ring, ovf = strategy.deliver(state.ring, net.tables, spiked,
                                     state.t, n_exc, cfg)
    return SimState(state.neuron, ring, state.t + 1, state.key,
                    state.overflow + ovf)


# ---------------------------------------------------------------------------
# Fused production loop
# ---------------------------------------------------------------------------

def make_step(net: Network, prop: Propagators, cfg: SimConfig,
              w_ext: float, n: int, n_exc: int, n_pops: int = 8,
              record_fn: Optional[Callable] = None,
              drive: Optional[stim.Drive] = None):
    """Build the fused update+deliver step.

    ``record_fn(state, spiked) -> pytree`` overrides the legacy
    ``cfg.record`` enum (the probe system in ``repro.api`` uses this hook).
    ``n_pops`` is the static population count for pop_counts recording —
    derive it from the ``Connectome`` (``len(c.pop_sizes)``), not a literal.
    ``drive`` threads a compiled stimulus timeline into ``update_phase``.
    """
    def step(state: SimState, _):
        state, spiked = update_phase(state, net, prop, cfg, w_ext, n, drive)
        state = deliver_phase(state, net, cfg, spiked, n_exc)
        if record_fn is not None:
            out = record_fn(state, spiked)
        elif cfg.record == "spikes":
            out = spiked
        elif cfg.record == "pop_counts":
            out = jax.ops.segment_sum(
                spiked.astype(jnp.int32), net.pop_of,
                num_segments=n_pops, indices_are_sorted=True)
        else:
            out = jnp.zeros((), jnp.int32)
        return state, out
    return step


@functools.partial(jax.jit, static_argnames=("n_steps", "cfg", "prop",
                                             "w_ext", "n", "n_exc", "n_pops",
                                             "drive"))
def _run(state, net, n_steps: int, cfg: SimConfig, prop: Propagators,
         w_ext: float, n: int, n_exc: int, n_pops: int = 8,
         drive: Optional[stim.Drive] = None):
    step = make_step(net, prop, cfg, w_ext, n, n_exc, n_pops, drive=drive)
    return jax.lax.scan(step, state, None, length=n_steps)


def simulate(c: Connectome, t_sim_ms: float, cfg: SimConfig,
             neuron: Optional[NeuronParams] = None,
             key=None, net: Optional[Network] = None,
             state: Optional[SimState] = None):
    """Build (if needed), run ``t_sim_ms`` of model time, return results.

    Returns (final_state, recorded, net) where ``recorded`` has leading axis
    n_steps.

    .. deprecated:: use ``repro.api.Simulator`` for new code; this shim
       stays for the original single-shot call signature.
    """
    warnings.warn(
        "repro.core.engine.simulate is deprecated; use repro.api.Simulator",
        DeprecationWarning, stacklevel=2)
    neuron = neuron or NeuronParams()
    explicit_stimulus = cfg.stimulus is not None
    cfg = resolve_sim_config(cfg, c)
    # an explicitly declared timeline compiles; the default stays on the
    # legacy inline path (drive=None) so this shim remains the bitwise
    # pre-registry reference the equivalence tests compare against
    drive = (stim.compile_drive(cfg.stimulus, c, cfg, neuron)
             if explicit_stimulus else None)
    prop = Propagators.make(neuron, cfg.dt)
    if net is None:
        net = prepare_network(c, cfg)
    if state is None:
        state = init_state(c, key, cfg.state_dtype)
    n_steps = int(round(t_sim_ms / cfg.dt))
    final, recorded = _run(state, net, n_steps, cfg, prop,
                           c.w_ext, c.n_total, c.n_exc,
                           n_pops=len(c.pop_sizes), drive=drive)
    return final, recorded, net


# ---------------------------------------------------------------------------
# Instrumented mode: per-phase timers (paper Fig. 1b bottom)
# ---------------------------------------------------------------------------

class PhaseRunner:
    """Runs the cycle with each phase a separate jitted function.

    .. deprecated:: thin shim over ``repro.api.backends.
       InstrumentedBackend`` — use ``Simulator(cfg,
       backend="instrumented")`` in new code; its ``RunResult.timers``
       carries the same per-phase accounting.
    """

    def __init__(self, c: Connectome, cfg: SimConfig,
                 neuron: Optional[NeuronParams] = None, key=None):
        warnings.warn(
            "PhaseRunner is deprecated; use repro.api.Simulator with "
            "backend='instrumented'", DeprecationWarning, stacklevel=2)
        from repro.api.backends import InstrumentedBackend
        self._backend = InstrumentedBackend()
        self._backend.build(c, cfg, neuron)
        self.cfg = cfg
        self.prop = self._backend.prop
        self.net = self._backend.net
        self.state = self._backend.init(key)
        self.n, self.n_exc = c.n_total, c.n_exc
        self.w_ext = c.w_ext

    def step_timed(self, timers: dict):
        self.state, spiked = self._backend.step_timed(self.state, timers)
        return spiked
