"""KernelPolicy: one object naming which Pallas kernels run the hot loop.

Historically kernel selection was scattered over booleans
(``SimConfig.use_lif_kernel``, ``SimConfig.use_deliver_kernel``) plus a
platform gate buried in ``EllDelivery.deliver``.  ``KernelPolicy``
replaces all of them: ``SimConfig.kernels=`` (or ``Simulator(kernels=...)``)
takes either a mode string or a policy object, and
``resolve_sim_config`` resolves it exactly once against the connectome
and platform.  After resolution every field is concrete, so the engine,
the delivery strategies, and the backends just read it — no re-deciding
at trace time.

Modes
-----
``auto``       pick the fastest eligible path for the platform: the fused
               one-kernel step on TPU when the ELL strategy, f32 state and
               VMEM ring-residency gate allow it; per-phase Pallas kernels
               on TPU otherwise; plain XLA off-TPU.
``fused``      force the fused ``lif_deliver`` step (interpret-mode off
               TPU).  Raises unless strategy == "ell" and f32 state.
``split``      force the per-phase Pallas kernels (``lif_update`` +
               delivery kernel), never the fused step.
``reference``  pure-XLA reference path (``lif_step`` + XLA scatter
               delivery) — the bitwise oracle the kernels are pinned to.

Per-op overrides (``step=``, ``lif=``, ``deliver=``) beat the mode, and
``interpret=`` pins Pallas interpret mode (default: on whenever the
default backend is not TPU).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax

MODES = ("auto", "fused", "split", "reference")

#: VMEM budget for keeping the full delay ring resident in the fused /
#: ELL kernels (mirrors EllDelivery.kernel_max_ring_bytes).
FUSED_MAX_RING_BYTES = 12 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Hashable kernel-selection policy (jit-static inside ``SimConfig``).

    Unresolved fields are ``None``; ``resolve`` (called from
    ``resolve_sim_config``) fills every field and sets ``resolved=True``.
    """
    mode: str = "auto"                 # one of MODES
    step: Optional[str] = None         # "fused" | "split"
    lif: Optional[str] = None          # "pallas" | "xla"
    deliver: Optional[str] = None      # "pallas" | "xla"
    interpret: Optional[bool] = None   # Pallas interpret mode (off-TPU dev)
    resolved: bool = False

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"KernelPolicy.mode {self.mode!r} not in {MODES}")
        if self.step not in (None, "fused", "split"):
            raise ValueError(f"KernelPolicy.step {self.step!r}")
        if self.lif not in (None, "pallas", "xla"):
            raise ValueError(f"KernelPolicy.lif {self.lif!r}")
        if self.deliver not in (None, "pallas", "xla"):
            raise ValueError(f"KernelPolicy.deliver {self.deliver!r}")

    def describe(self) -> str:
        """Compact one-line form for logs and ledger entries, e.g.
        ``fused[step=fused,lif=pallas,deliver=pallas,interpret]``."""
        parts = [f"step={self.step}", f"lif={self.lif}",
                 f"deliver={self.deliver}"]
        if self.interpret:
            parts.append("interpret")
        body = ",".join(parts)
        tag = self.mode if self.resolved else f"{self.mode}?"
        return f"{tag}[{body}]"


def as_policy(kernels: Union[None, str, KernelPolicy]) -> KernelPolicy:
    """Normalise the ``SimConfig.kernels`` field to a KernelPolicy."""
    if kernels is None:
        return KernelPolicy()
    if isinstance(kernels, str):
        return KernelPolicy(mode=kernels)
    if isinstance(kernels, KernelPolicy):
        return kernels
    raise TypeError(
        f"kernels= takes a mode string {MODES} or a KernelPolicy, "
        f"got {type(kernels).__name__}")


def _ring_bytes(n_total: int, d_max_bins: int) -> int:
    """Bytes of the tile-padded f32 ring the kernels keep in VMEM."""
    from repro.kernels.ell_deliver import ring_lanes
    return 2 * d_max_bins * ring_lanes(n_total + 1) * 4


def fused_eligible(strategy: str, state_dtype, n_total: int,
                   d_max_bins: int) -> tuple[bool, str]:
    """(eligible, reason-if-not) for the fused one-kernel step."""
    import jax.numpy as jnp
    if strategy != "ell":
        return False, (f"the fused step requires the 'ell' delivery "
                       f"strategy (got {strategy!r})")
    if jnp.dtype(state_dtype) != jnp.dtype(jnp.float32):
        return False, (f"the fused step requires float32 state "
                       f"(got {jnp.dtype(state_dtype).name})")
    bytes_ = _ring_bytes(n_total, d_max_bins)
    if bytes_ > FUSED_MAX_RING_BYTES:
        return False, (f"delay ring ({bytes_} B) exceeds the VMEM "
                       f"residency budget ({FUSED_MAX_RING_BYTES} B)")
    return True, ""


def resolve(kernels: Union[None, str, KernelPolicy], *, strategy: str,
            state_dtype, n_total: int, d_max_bins: int,
            use_lif_kernel: bool = False,
            use_deliver_kernel: bool = False) -> KernelPolicy:
    """Resolve a policy against the connectome and platform.  Idempotent:
    an already-resolved policy is returned unchanged (legacy flags are
    only folded in on first resolution)."""
    pol = as_policy(kernels)
    if pol.resolved:
        return pol

    # fold the deprecated per-kernel booleans (resolve_sim_config warns)
    if use_lif_kernel and pol.lif is None:
        pol = dataclasses.replace(pol, lif="pallas")
    if use_deliver_kernel and pol.deliver is None:
        pol = dataclasses.replace(pol, deliver="pallas")

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    interpret = pol.interpret if pol.interpret is not None else not on_tpu

    eligible, why = fused_eligible(strategy, state_dtype, n_total,
                                   d_max_bins)
    if pol.mode == "reference":
        step, lif, deliver = "split", "xla", "xla"
    elif pol.mode == "split":
        step, lif, deliver = "split", "pallas", "pallas"
    elif pol.mode == "fused":
        if not eligible:
            raise ValueError(f"KernelPolicy(mode='fused'): {why}")
        step = "fused"
        lif = "pallas" if on_tpu else "xla"
        deliver = "pallas" if on_tpu else "xla"
    else:  # auto
        step = "fused" if (on_tpu and eligible) else "split"
        lif = "pallas" if on_tpu else "xla"
        if strategy == "ell" and on_tpu and _ring_bytes(
                n_total, d_max_bins) <= FUSED_MAX_RING_BYTES:
            deliver = "pallas"
        else:
            deliver = "xla"

    # per-op overrides beat the mode
    if pol.step is not None:
        if pol.step == "fused" and not eligible:
            raise ValueError(f"KernelPolicy(step='fused'): {why}")
        step = pol.step
    if pol.lif is not None:
        lif = pol.lif
    if pol.deliver is not None:
        deliver = pol.deliver

    return dataclasses.replace(pol, step=step, lif=lif, deliver=deliver,
                               interpret=interpret, resolved=True)


def policy_of(cfg) -> Optional[KernelPolicy]:
    """The resolved policy carried by a SimConfig, or None when the config
    was never passed through ``resolve_sim_config`` (direct phase users);
    callers fall back to the legacy boolean flags in that case."""
    pol = getattr(cfg, "kernels", None)
    return pol if isinstance(pol, KernelPolicy) and pol.resolved else None
