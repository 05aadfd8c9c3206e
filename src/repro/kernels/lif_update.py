"""Pallas TPU kernel: fused LIF exp-PSC update + spike detection.

The `update` phase reads/writes 6 state/input arrays per neuron; unfused, XLA
emits one HBM round-trip per elementwise op.  This kernel performs the whole
exact-integration step (propagator application, DC term, refractory clamp,
threshold/reset) in one VPU pass: each [block_n] tile is loaded into VMEM
once, all arithmetic happens in registers, and the five outputs are written
once — the update phase becomes perfectly bandwidth-bound (roofline: bytes =
r+w of the state, no intermediate traffic).

Propagators are Python floats, baked into the kernel body as immediates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.neuron import Propagators

# f32 VPU tile: 8 sublanes x 128 lanes.
_LANE = 128
_DEFAULT_BLOCK = 8 * _LANE * 4   # 4096 neurons per grid step


def _kernel(V_ref, iex_ref, iin_ref, ref_ref, inex_ref, inin_ref, idc_ref,
            Vo_ref, iexo_ref, iino_ref, refo_ref, spk_ref,
            *, prop: Propagators):
    V = V_ref[...]
    I_ex = iex_ref[...]
    I_in = iin_ref[...]
    refrac = ref_ref[...]

    V_new = (prop.E_L
             + (V - prop.E_L) * prop.P22
             + I_ex * prop.P21_ex
             + I_in * prop.P21_in
             + idc_ref[...] * prop.P20)

    iexo_ref[...] = I_ex * prop.P11_ex + inex_ref[...]
    iino_ref[...] = I_in * prop.P11_in + inin_ref[...]

    refractory = refrac > 0
    V_new = jnp.where(refractory, prop.V_reset, V_new)
    spiked = (V_new >= prop.V_th) & jnp.logical_not(refractory)

    Vo_ref[...] = jnp.where(spiked, prop.V_reset, V_new)
    refo_ref[...] = jnp.where(
        spiked, prop.ref_steps, jnp.maximum(refrac - 1, 0)
    ).astype(refrac.dtype)
    spk_ref[...] = spiked


@functools.partial(jax.jit,
                   static_argnames=("prop", "block", "interpret"))
def lif_update_pallas(V, I_ex, I_in, refrac, in_ex, in_in, i_dc,
                      *, prop: Propagators, block: int = _DEFAULT_BLOCK,
                      interpret: bool = False):
    """Returns (V', I_ex', I_in', refrac', spiked). All inputs are [N]."""
    n = V.shape[0]
    n_pad = -(-n // block) * block
    pad = lambda x: jnp.pad(x, (0, n_pad - n))
    args = [pad(x) for x in (V, I_ex, I_in, refrac, in_ex, in_in, i_dc)]

    grid = (n_pad // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    out_shapes = (
        jax.ShapeDtypeStruct((n_pad,), V.dtype),
        jax.ShapeDtypeStruct((n_pad,), I_ex.dtype),
        jax.ShapeDtypeStruct((n_pad,), I_in.dtype),
        jax.ShapeDtypeStruct((n_pad,), refrac.dtype),
        jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
    )
    outs = pl.pallas_call(
        functools.partial(_kernel, prop=prop),
        grid=grid,
        in_specs=[spec] * 7,
        out_specs=(spec,) * 5,
        out_shape=out_shapes,
        interpret=interpret,
        name="lif_update",
    )(*args)
    return tuple(o[:n] for o in outs)
