"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy: on TPU the compiled kernels run natively; everywhere else
(this CPU container, unit tests) they run in ``interpret=True`` mode, which
executes the same kernel body under the Pallas interpreter.  ``ref.py`` holds
the pure-jnp oracles used by the allclose test sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.neuron import NeuronState, Propagators
from repro.kernels.ell_deliver import ell_deliver_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lif_deliver import (lif_deliver_pallas,
                                       lif_deliver_plastic_pallas)
from repro.kernels.lif_update import lif_update_pallas
from repro.kernels.spike_deliver import gated_spike_matvec_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def lif_update(state: NeuronState, prop: Propagators,
               in_ex: jnp.ndarray, in_in: jnp.ndarray, i_dc: jnp.ndarray,
               interpret: bool | None = None):
    """Fused neuron update. Drop-in for core.neuron.lif_step."""
    interpret = _interpret_default() if interpret is None else interpret
    V, I_ex, I_in, refrac, spiked = lif_update_pallas(
        state.V, state.I_ex, state.I_in, state.refrac, in_ex, in_in, i_dc,
        prop=prop, interpret=interpret)
    return NeuronState(V, I_ex, I_in, refrac), spiked


def gated_spike_matvec(s: jnp.ndarray, W: jnp.ndarray,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Activity-gated dense delivery. Drop-in matvec for deliver_dense."""
    interpret = _interpret_default() if interpret is None else interpret
    return gated_spike_matvec_pallas(s, W, interpret=interpret)


def ell_deliver(ring: jnp.ndarray, tables, spiked: jnp.ndarray,
                t: jnp.ndarray, n_exc: int, spike_budget: int,
                block_k: int = 128, interpret: bool | None = None):
    """Sparse-ELL ring delivery (the ``ell`` strategy's kernel path).

    Drop-in for ``delivery.deliver_event``: returns (ring', n_overflow).
    """
    interpret = _interpret_default() if interpret is None else interpret
    D, _, n_cols = ring.shape
    n = spiked.shape[0]
    n_spikes = jnp.sum(spiked, dtype=jnp.int32)
    (ids,) = jnp.nonzero(spiked, size=spike_budget, fill_value=n)
    upd = ell_deliver_pallas(
        ids.astype(jnp.int32), tables.row_len[ids], tables.targets,
        tables.weights, tables.dbins, t, d_bins=D, n_cols=n_cols,
        n_exc=n_exc, block_k=block_k, interpret=interpret)
    overflow = jnp.maximum(n_spikes - spike_budget, 0)
    return ring + upd.astype(ring.dtype), overflow


def lif_deliver(state: NeuronState, ring: jnp.ndarray, t: jnp.ndarray,
                spiked_prev: jnp.ndarray, tables, prop: Propagators,
                ext_ex: jnp.ndarray, i_dc: jnp.ndarray, *, n_exc: int,
                spike_budget: int, block_k: int = 128,
                interpret: bool | None = None):
    """Fused one-kernel step (static weights): deliver the previous step's
    spikes at ring phase ``t - 1``, then integrate step ``t``.

    Drop-in for ``deliver_phase(t-1)`` + ``update_phase(t)`` fused; see
    :mod:`repro.kernels.lif_deliver` for the loop rotation.  Returns
    ``(neuron', ring', spiked, n_overflow)`` where ``n_overflow`` accounts
    the *delivered* (previous) step's budget excess.
    """
    interpret = _interpret_default() if interpret is None else interpret
    D, _, n_cols = ring.shape
    n = spiked_prev.shape[0]
    n_spikes = jnp.sum(spiked_prev, dtype=jnp.int32)
    (ids,) = jnp.nonzero(spiked_prev, size=spike_budget, fill_value=n)
    t_prev = jnp.asarray(t, jnp.int32) - 1
    ring_out, V, I_ex, I_in, refrac, spiked = lif_deliver_pallas(
        ids.astype(jnp.int32), tables.row_len[ids], tables.targets,
        tables.weights, tables.dbins, ring, state.V, state.I_ex,
        state.I_in, state.refrac, ext_ex, i_dc, t_prev, d_bins=D,
        n_cols=n_cols, n=n, n_exc=n_exc, prop=prop, block_k=block_k,
        interpret=interpret)
    overflow = jnp.maximum(n_spikes - spike_budget, 0)
    return (NeuronState(V, I_ex, I_in, refrac),
            ring_out.astype(ring.dtype).reshape(ring.shape),
            spiked, overflow)


def lif_deliver_plastic(state: NeuronState, ring: jnp.ndarray,
                        t: jnp.ndarray, spiked_prev: jnp.ndarray, tables,
                        w_live: jnp.ndarray, pmask: jnp.ndarray,
                        x_pre: jnp.ndarray, x_post: jnp.ndarray,
                        prop: Propagators, ext_ex: jnp.ndarray,
                        i_dc: jnp.ndarray, *, n_exc: int,
                        spike_budget: int, dep_coef: float, decay_p: float,
                        decay_m: float, block_k: int = 128,
                        interpret: bool | None = None):
    """Plastic fused step: the static step plus in-tile pair-STDP
    depression and on-chip trace decay (potentiation + clip stay in XLA —
    ``repro.core.plasticity.stdp_pot_clip``).

    ``w_live`` is the live ELL-padded plastic weight table ``[N+1, K]``
    (also the delivery weights), ``pmask`` its plastic mask.  Returns
    ``(neuron', ring', spiked, w_live', x_pre', x_post', ids,
    n_overflow)`` — ``ids`` are the delivered spike ids, reusable for the
    potentiation gather.
    """
    interpret = _interpret_default() if interpret is None else interpret
    D, _, n_cols = ring.shape
    n = spiked_prev.shape[0]
    n_spikes = jnp.sum(spiked_prev, dtype=jnp.int32)
    (ids,) = jnp.nonzero(spiked_prev, size=spike_budget, fill_value=n)
    ids = ids.astype(jnp.int32)
    t_prev = jnp.asarray(t, jnp.int32) - 1
    spk_prev = spiked_prev.astype(jnp.float32)
    (ring_out, w_out, V, I_ex, I_in, refrac, spiked, xpre_o,
     xpost_o) = lif_deliver_plastic_pallas(
        ids, tables.row_len[ids], tables.targets, w_live, tables.dbins,
        pmask, ring, state.V, state.I_ex, state.I_in, state.refrac, ext_ex,
        i_dc, x_pre, x_post, spk_prev, t_prev, d_bins=D, n_cols=n_cols, n=n,
        n_exc=n_exc, prop=prop, dep_coef=dep_coef, decay_p=decay_p,
        decay_m=decay_m, block_k=block_k, interpret=interpret)
    overflow = jnp.maximum(n_spikes - spike_budget, 0)
    return (NeuronState(V, I_ex, I_in, refrac),
            ring_out.astype(ring.dtype).reshape(ring.shape),
            spiked, w_out, xpre_o, xpost_o, ids, overflow)


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    interpret: bool | None = None):
    """Blocked GQA attention. Drop-in for ref.mha_ref."""
    interpret = _interpret_default() if interpret is None else interpret
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  interpret=interpret)
