"""Spike-delivery strategies: a pluggable protocol plus a registry.

NEST delivers spikes event-wise: each spiking neuron's target list is walked
and weights are accumulated into per-target ring buffers at slot
``(t + delay) mod D``.  The TPU adaptations keep the semantics but change the
mechanism (DESIGN.md section 2).  Every mechanism is a
:class:`DeliveryStrategy` registered under a name; ``SimConfig.strategy``
selects one and the engine (``engine.deliver_phase``) dispatches through the
registry instead of hardcoding branches:

* ``event`` — budgeted event-driven: the <=S spike ids of the step gather
  their padded ELL rows, and one large ``scatter-add`` accumulates all
  ``S x K`` (target, weight, slot) triples into the ring buffer.  The
  per-step spike capacity ``spike_budget`` is rate-derived automatically
  when left unset (:func:`auto_spike_budget`); spikes beyond the budget are
  counted in the ``overflow`` state (surfaced by ``RunResult`` — never
  silently dropped).

* ``dense`` — delay-binned matrix delivery: the 0/1 spike vector multiplies
  ``W[D, N_pre, N_post]`` on the MXU.  FLOP-wasteful (density ~0.1 per bin)
  but bandwidth-streaming; the Pallas ``spike_deliver`` kernel recovers the
  sparsity by skipping weight tiles whose source-spike block is empty.
  ``W`` is O(N^2) per delay bin, so ``prepare`` is guarded by a host-side
  byte estimate — at full scale (N=77k, D=46 bins) it would be ~1.1 TB in
  f32, two orders of magnitude past device HBM.

* ``ell`` — sparse-ELL delivery backed by a Pallas kernel
  (``repro.kernels.ell_deliver``): the step's spike ids are scalar-
  prefetched, their padded ELL rows are gathered tile-by-tile straight from
  HBM, and the (target, weight, slot) triples scatter-add into the ring
  on-chip, walking only the row tiles that hold real synapses.  Work
  follows the delivered rows' real lengths, memory is O(N*K) — the only
  layout that reaches the paper's full scale (~0.3 billion explicit
  synapses).  Off-TPU the
  strategy runs the same math through the pure-jnp gather/scatter path
  unless the resolved ``SimConfig.kernels`` policy
  (``KernelPolicy(deliver='pallas')``) forces the (interpret-mode) kernel.

All strategies write into ``ring[D, 2, N+1]``: channel 0/1 = excitatory/
inhibitory arrivals, one trailing dump column absorbs padded scatters.

Registering a new mechanism is one class::

    @register
    class MyDelivery(DeliveryStrategy):
        name = "mine"
        def prepare(self, c, cfg): ...
        def deliver(self, ring, tables, spiked, t, n_exc, cfg): ...
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernel_policy as kpol


def _wants_pallas_deliver(cfg) -> bool:
    """Kernel selection for the delivery phase: the resolved KernelPolicy
    when the config carries one, else the legacy boolean flag."""
    pol = kpol.policy_of(cfg)
    if pol is not None:
        return pol.deliver == "pallas"
    return bool(cfg.use_deliver_kernel)


class DeliveryOverflowError(RuntimeError):
    """Raised (``SimConfig.strict_delivery``) when spikes exceeded the
    per-step ``spike_budget`` and were dropped by the event/ell path."""


class EventTables(NamedTuple):
    """Padded ELL out-adjacency, plus one sentinel row at index N.

    ``row_len`` is each row's real length (:func:`row_lengths`): the ELL
    kernels walk only the row tiles below it."""
    targets: jnp.ndarray   # [N+1, K] int32 in [0, N]; N == dump
    weights: jnp.ndarray   # [N+1, K] float32
    dbins: jnp.ndarray     # [N+1, K] int32 >= 1
    row_len: jnp.ndarray   # [N+1] int32; 0 on the sentinel (and pad) rows


class DenseTables(NamedTuple):
    """Signed delay-binned weights, in one of two layouts.

    Bin-major ``W[D, N_pre, N_post]`` feeds the Pallas activity-gated
    kernel (``use_deliver_kernel``), whose block map walks delay-bin tiles.
    The default is source-major: ``W_ex[n_exc, D*N]`` / ``W_in[n_inh,
    D*N]``, pre-split at the Dale boundary so delivery is two contiguous
    rank-1 GEMMs — bitwise equal to the einsum over ``W`` but streamed at
    memory bandwidth (the runtime row-slice ``W[:, :n_exc]`` defeated
    XLA's fusion and cost ~10x).
    """
    W: Optional[jnp.ndarray] = None        # [D, N_pre, N_post] bin-major
    W_ex: Optional[jnp.ndarray] = None     # [n_exc, D * N_post]
    W_in: Optional[jnp.ndarray] = None     # [N - n_exc, D * N_post]


@jax.jit
def row_lengths(targets, sentinel) -> jnp.ndarray:
    """Real length of each ELL row ``[R]`` int32: one past its last entry
    that does not point at the dump column ``sentinel``, 0 for a row of
    sentinel entries only.  Rows are front-packed
    (``connectivity.build_connectome``), so this is the out-degree."""
    col = jnp.arange(1, targets.shape[1] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(targets != sentinel, col, 0), axis=1)


def make_event_tables(targets, weights, dbins) -> EventTables:
    """Append the sentinel source row (all entries point at the dump slot)."""
    n, k = targets.shape
    pad_t = jnp.full((1, k), n, dtype=targets.dtype)
    pad_w = jnp.zeros((1, k), dtype=weights.dtype)
    pad_d = jnp.ones((1, k), dtype=dbins.dtype)
    targets = jnp.concatenate([targets, pad_t], axis=0)
    return EventTables(
        targets=targets,
        weights=jnp.concatenate([weights, pad_w], axis=0),
        dbins=jnp.concatenate([dbins, pad_d], axis=0),
        row_len=row_lengths(targets, n),
    )


def deliver_event(ring: jnp.ndarray, tables: EventTables,
                  spiked: jnp.ndarray, t: jnp.ndarray,
                  n_exc: int, spike_budget: int):
    """Event-driven delivery. Returns (ring', n_overflow)."""
    D, _, n_cols = ring.shape
    n = spiked.shape[0]
    n_spikes = jnp.sum(spiked, dtype=jnp.int32)
    # Padded spike-id extraction; fill with the sentinel source row `n`.
    (ids,) = jnp.nonzero(spiked, size=spike_budget, fill_value=n)

    tg = tables.targets[ids]                     # [S, K] in [0, n]
    w = tables.weights[ids]                      # [S, K]
    db = tables.dbins[ids]                       # [S, K]
    ch = (ids >= n_exc).astype(jnp.int32)        # Dale's law: row sign by src
    slot = (t + db) % D                          # [S, K]

    lin = (slot * (2 * n_cols)
           + ch[:, None] * n_cols
           + tg)
    ring = ring.reshape(-1).at[lin.reshape(-1)].add(
        w.reshape(-1), mode="drop").reshape(D, 2, n_cols)
    overflow = jnp.maximum(n_spikes - spike_budget, 0)
    return ring, overflow


def deliver_dense(ring: jnp.ndarray, tables: DenseTables,
                  spiked: jnp.ndarray, t: jnp.ndarray, n_exc: int,
                  matvec=None):
    """Delay-binned dense delivery. Returns (ring', overflow=0).

    With the source-major split layout (``W_ex``/``W_in``) the matvec is a
    contiguous rank-1 GEMM per channel (bitwise equal to the einsum, but
    memory-bandwidth-bound instead of batched GEMVs).  For the bin-major
    ``W``, ``matvec(s, W)`` with ``s``[P] and ``W``[D, P, N] -> [D, N] can
    be swapped for the Pallas activity-gated kernel; default is a jnp
    einsum.
    """
    D, _, n_cols = ring.shape
    n = spiked.shape[0]
    if tables.W is None:
        if matvec is not None:
            raise ValueError(
                "custom matvec (the gated Pallas kernel) needs the "
                "bin-major W[D, P, N] layout, but these DenseTables hold "
                "the split GEMM layout — rebuild the tables with "
                "kernels=KernelPolicy(deliver='pallas') "
                "(DenseDelivery.prepare)")
        s = spiked.astype(tables.W_ex.dtype)
        matvec = lambda v, W: jnp.matmul(
            v[None, :], W,
            preferred_element_type=jnp.float32).reshape(D, n)
        upd_ex = matvec(s[:n_exc], tables.W_ex)          # [D, N]
        upd_in = matvec(s[n_exc:], tables.W_in)          # [D, N]
    else:
        s = spiked.astype(tables.W.dtype)
        if matvec is None:
            matvec = lambda v, W: jnp.einsum(
                "p,dpn->dn", v, W, preferred_element_type=jnp.float32)
        upd_ex = matvec(s[:n_exc], tables.W[:, :n_exc, :])   # [D, N]
        upd_in = matvec(s[n_exc:], tables.W[:, n_exc:, :])   # [D, N]
    upd = jnp.stack([upd_ex, upd_in], axis=1)            # [D, 2, N]
    upd = jnp.pad(upd, ((0, 0), (0, 0), (0, n_cols - n)))
    # bin d arrives at slot (t + d) mod D
    upd = jnp.roll(upd, shift=t, axis=0)
    return ring + upd.astype(ring.dtype), jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# Spike-budget sizing
# ---------------------------------------------------------------------------

def auto_spike_budget(c, dt: float, safety: float = 8.0,
                      quantum: int = 128) -> int:
    """Rate-derived per-step spike capacity for the event/ell strategies.

    Expected spikes per step at the full-scale reference rates (the
    validation target band) times a ``safety`` headroom factor, rounded up
    to a ``quantum`` (lane-aligned gather widths), and capped at the padded
    network size (more than N spikes per step is impossible).
    """
    from repro.core.params import FULL_MEAN_RATES
    pop_sizes = np.asarray(c.pop_sizes)
    if pop_sizes.shape[0] == FULL_MEAN_RATES.shape[0]:
        expected = float((pop_sizes * FULL_MEAN_RATES).sum()) * dt * 1e-3
    else:
        # non-microcircuit population structure: assume every neuron fires
        # at the hottest reference rate (conservative)
        expected = c.n_total * float(FULL_MEAN_RATES.max()) * dt * 1e-3
    budget = max(quantum, math.ceil(expected * safety / quantum) * quantum)
    n_cap = math.ceil(c.n_total / quantum) * quantum
    return int(min(budget, n_cap))


def _require_budget(cfg) -> int:
    if cfg.spike_budget is None:
        raise ValueError(
            "SimConfig.spike_budget is unresolved (None means rate-derived "
            "auto); call repro.core.engine.resolve_sim_config(cfg, "
            "connectome) first — the api backends do this in build()")
    return int(cfg.spike_budget)


# ---------------------------------------------------------------------------
# The strategy protocol and registry
# ---------------------------------------------------------------------------

class DeliveryStrategy:
    """One spike-propagation mechanism.

    Stateless: ``prepare`` builds the device-resident tables (any pytree)
    on the host, ``deliver`` is the traced hot path that scatters one step's
    spikes into the delay ring buffer.  Instances are singletons living in
    :data:`REGISTRY`; the engine resolves ``SimConfig.strategy`` (a plain,
    hashable string — jit-static) through :func:`get_strategy`.
    """

    name: str = "abstract"

    # -- host side ----------------------------------------------------------
    def prepare(self, c, cfg) -> Any:
        """Build device tables for connectome ``c`` (returns a pytree)."""
        raise NotImplementedError

    def memory_bytes(self, c) -> int:
        """Host-side estimate of the table footprint in bytes."""
        raise NotImplementedError

    def localize(self, c, n_dev: int, k_loc: Optional[int] = None):
        """Shard transform for the sharded backend: regroup the tables by
        target-owning device.  Strategies without a distributed layout
        raise ``NotImplementedError``."""
        raise NotImplementedError(
            f"delivery strategy {self.name!r} has no shard transform")

    @property
    def supports_sharding(self) -> bool:
        return False

    #: True when ``live_tables`` is implemented — the plasticity subsystem
    #: (``Simulator(plasticity=...)``) needs a strategy whose weights can
    #: be swapped per step.
    supports_live_weights: bool = False

    # -- traced hot path ----------------------------------------------------
    def deliver(self, ring: jnp.ndarray, tables: Any, spiked: jnp.ndarray,
                t: jnp.ndarray, n_exc: int, cfg
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Scatter one step's spikes. Returns (ring', n_overflow)."""
        raise NotImplementedError

    def live_tables(self, tables: Any, weights: jnp.ndarray) -> Any:
        """Per-step view of ``tables`` with live ``weights`` swapped in.

        ``weights`` is the canonical ``[N+1, K]`` plastic weight view (a
        plasticity rule's ``weight_view``); the returned pytree feeds
        ``deliver`` for this step.  Traced inside the scan — must be a
        cheap re-wrapping (replace/pad), never a host-side rebuild.
        """
        raise NotImplementedError(
            f"delivery strategy {self.name!r} has no live-weight path "
            f"(live_tables); plasticity requires 'event' or 'ell'")


REGISTRY: Dict[str, DeliveryStrategy] = {}


def register(cls: Type[DeliveryStrategy]) -> Type[DeliveryStrategy]:
    """Class decorator: instantiate and register under ``cls.name``.

    Name collisions raise — silently replacing a registered strategy would
    change delivery semantics process-wide; ``del REGISTRY[name]`` first to
    replace one deliberately.
    """
    if not getattr(cls, "name", None) or cls.name == "abstract":
        raise ValueError(f"{cls.__name__} needs a concrete .name")
    if cls.name in REGISTRY:
        raise ValueError(
            f"delivery strategy {cls.name!r} is already registered "
            f"({type(REGISTRY[cls.name]).__name__}); del REGISTRY[name] "
            f"first to replace it")
    REGISTRY[cls.name] = cls()
    return cls


def get_strategy(name: str) -> DeliveryStrategy:
    """Resolve a registered strategy by name (the ``SimConfig.strategy``
    string); raises with the available names on a miss."""
    if isinstance(name, DeliveryStrategy):
        return name
    try:
        return REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown delivery strategy {name!r}; "
                         f"available: {available_strategies()}") from None


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(REGISTRY))


# ---------------------------------------------------------------------------
# Registered implementations
# ---------------------------------------------------------------------------

@register
class EventDelivery(DeliveryStrategy):
    """Budgeted event-driven gather + one large XLA scatter-add."""

    name = "event"

    def prepare(self, c, cfg) -> EventTables:
        return make_event_tables(
            jnp.asarray(c.targets), jnp.asarray(c.weights),
            jnp.asarray(c.dbins))

    def memory_bytes(self, c) -> int:
        n, k = c.targets.shape
        return (n + 1) * k * (4 + 4 + 4)

    def localize(self, c, n_dev, k_loc=None):
        from repro.core.distributed import localize_ell
        return localize_ell(c, n_dev, k_loc)

    @property
    def supports_sharding(self) -> bool:
        return True

    supports_live_weights = True

    def deliver(self, ring, tables, spiked, t, n_exc, cfg):
        return deliver_event(ring, tables, spiked, t, n_exc,
                             _require_budget(cfg))

    def live_tables(self, tables: EventTables,
                    weights: jnp.ndarray) -> EventTables:
        return tables._replace(weights=weights)


@register
class DenseDelivery(DeliveryStrategy):
    """Delay-binned matrix delivery on the MXU (O(N^2) memory — guarded)."""

    name = "dense"

    def prepare(self, c, cfg, dtype=jnp.float32) -> DenseTables:
        from repro.core.connectivity import dense_delay_binned
        W = dense_delay_binned(c)                     # [D, N, N]
        if _wants_pallas_deliver(cfg):
            # the gated Pallas kernel's block map walks delay-bin tiles
            return DenseTables(W=jnp.asarray(W, dtype=dtype))
        # source-major split GEMM layout (see DenseTables); intermediates
        # are freed eagerly so the host peak stays ~2x the table estimate
        Wt = np.ascontiguousarray(W.transpose(1, 0, 2)).reshape(
            c.n_total, -1)
        del W
        W_ex = jnp.asarray(Wt[:c.n_exc], dtype=dtype)
        W_in = jnp.asarray(Wt[c.n_exc:], dtype=dtype)
        del Wt
        return DenseTables(W_ex=W_ex, W_in=W_in)

    def memory_bytes(self, c, itemsize: int = 4) -> int:
        return c.d_max_bins * c.n_total * c.n_total * itemsize

    def deliver(self, ring, tables, spiked, t, n_exc, cfg):
        matvec = None
        if _wants_pallas_deliver(cfg):
            from repro.kernels import ops as kops
            matvec = kops.gated_spike_matvec
        return deliver_dense(ring, tables, spiked, t, n_exc, matvec=matvec)


@register
class EllDelivery(DeliveryStrategy):
    """Sparse-ELL delivery backed by the Pallas ``ell_deliver`` kernel.

    Same ELL tables as ``event`` (rows padded to a lane-aligned K so the
    kernel's tile loop divides evenly).  On TPU — or when the resolved
    ``KernelPolicy`` says ``deliver='pallas'`` — the kernel scalar-
    prefetches the spike ids, gathers only the S spiking rows tile-by-tile
    from HBM and scatter-adds on-chip; elsewhere the identical math runs
    through the pure-jnp gather/scatter (interpret-mode kernels are
    tracing-bound on CPU, the repo-wide convention is opt-in via the
    kernel policy).
    """

    name = "ell"
    block_k = 128            # ELL row tile width (lane-aligned)
    row_tile = 8             # the kernels DMA aligned 8-row (sublane) tiles
    #: The kernel holds the whole [2D, N+1] ring update in VMEM; past this
    #: budget (full scale needs ~28 MB) the automatic TPU path keeps the
    #: XLA gather/scatter.  An explicit ``KernelPolicy(deliver='pallas')``
    #: still forces the kernel.
    kernel_max_ring_bytes = kpol.FUSED_MAX_RING_BYTES

    def prepare(self, c, cfg) -> EventTables:
        """ELL tables padded to whole ``(row_tile, block_k)`` tiles: extra
        columns, the sentinel row N and any rows after it point at the dump
        slot with weight 0.  Padded on the host, so each table crosses to
        the device once (at full scale they are ~2 GB each)."""
        n, k = c.targets.shape
        k_pad = max(self.block_k,
                    -(-k // self.block_k) * self.block_k)
        rows = -(-(n + 1) // self.row_tile) * self.row_tile
        pad = ((0, rows - n), (0, k_pad - k))
        targets = jnp.asarray(np.pad(c.targets, pad, constant_values=n))
        return EventTables(
            targets=targets,
            weights=jnp.asarray(np.pad(c.weights, pad)),
            dbins=jnp.asarray(np.pad(c.dbins, pad, constant_values=1)),
            row_len=row_lengths(targets, n))

    def memory_bytes(self, c) -> int:
        n, k = c.targets.shape
        k_pad = max(self.block_k, -(-k // self.block_k) * self.block_k)
        rows = -(-(n + 1) // self.row_tile) * self.row_tile
        return rows * k_pad * (4 + 4 + 4)

    def localize(self, c, n_dev, k_loc=None):
        # The sharded engine consumes the same ELL layout (its deliver is
        # the event-style scatter over localized columns).
        from repro.core.distributed import localize_ell
        return localize_ell(c, n_dev, k_loc)

    @property
    def supports_sharding(self) -> bool:
        return True

    supports_live_weights = True

    def live_tables(self, tables: EventTables,
                    weights: jnp.ndarray) -> EventTables:
        """Pad the canonical [N+1, K] live weights to this strategy's
        tile-padded table (padded entries already point at the dump
        slot)."""
        rows_pad, k_pad = tables.targets.shape
        rows, k = weights.shape
        if (rows_pad, k_pad) != (rows, k):
            weights = jnp.pad(weights, ((0, rows_pad - rows),
                                        (0, k_pad - k)))
        return tables._replace(weights=weights)

    def deliver(self, ring, tables, spiked, t, n_exc, cfg):
        budget = _require_budget(cfg)
        pol = kpol.policy_of(cfg)
        if pol is not None:
            use_kernel = pol.deliver == "pallas"
            interpret = pol.interpret
        else:                 # unresolved config: legacy flag + TPU gate
            D, _, n_cols = ring.shape
            upd_bytes = 2 * D * (-(-n_cols // 128) * 128) * 4
            use_kernel = (cfg.use_deliver_kernel
                          or (jax.default_backend() == "tpu"
                              and upd_bytes <= self.kernel_max_ring_bytes))
            interpret = None
        if use_kernel:
            from repro.kernels import ops as kops
            return kops.ell_deliver(ring, tables, spiked, t, n_exc, budget,
                                    block_k=self.block_k,
                                    interpret=interpret)
        return deliver_event(ring, tables, spiked, t, n_exc, budget)
