"""Pallas TPU kernel: the fused one-kernel simulation step.

The phase-split hot loop costs one HBM round-trip per phase: ``deliver``
scatters the previous step's spikes into the ring buffer, ``update`` reads
a ring slot plus five state arrays, integrates, and writes them back.
This kernel keeps the whole delay ring, the membrane state, and the
scalar-prefetched spike ids resident on-chip across one step:

* the ring is DMA'd from HBM into a VMEM scratch buffer at the first grid
  step and back (aliased in place) after the last, in the tiled layout of
  :mod:`repro.kernels.ell_deliver`;
* grid ``(S+1, K/block_k)`` — the first ``S`` rows replay the sparse-ELL
  delivery of the *previous* step's spike ids (row tiles DMA'd from HBM
  into SMEM, masked-tile scatter into the resident ring, s-major / k-minor
  order, exactly :mod:`repro.kernels.ell_deliver`).  Work follows the
  delivered rows' real tiles: grid step ``(s, kb)`` with ``s < S`` walks
  its tile only if ``kb * block_k < len[s]``, the real length of row
  ``ids[s]`` (scalar-prefetched; 0 for the sentinel rows that pad ``ids``
  to the budget), and otherwise starts no DMA and runs no loop;
* the final grid row (``s == S, kb == 0``) runs the whole-network LIF
  update of :mod:`repro.kernels.lif_update` against the just-scattered
  ring, tile by tile: it reads the current slot's arrival rows, integrates
  with the propagator immediates, detects spikes, and zeroes the consumed
  slot — all before the ring is written back to HBM once.

Because the kernel can only prefetch spike ids that exist *before* it
runs, the fused loop is rotated one step: iteration ``i`` delivers
``spiked[i-1]`` (at ring phase ``t-1``) and then updates step ``i``.  The
global op sequence — ``update_0, deliver_0, update_1, deliver_1, ...`` —
is identical to the phase-split path, so trajectories match bitwise; the
backends flush the final step's spikes with a split-path delivery
epilogue after the scan.

``lif_deliver_plastic`` additionally folds the pair-STDP depression and
trace decay into the same pass: each fetched ELL weight tile is written
back depressed (``w -= lr*A_minus*w_ref*x_post[target]`` on plastic
synapses) while it is on-chip for the ring scatter (a tile that is not
walked holds no plastic synapse of the row and is not written back),
and the pre/post traces decay+bump in the LIF phase.  The potentiation
scatter (indexed by the transposed in-adjacency, a different access
pattern) and the weight clip stay in XLA —
``repro.core.plasticity.stdp_pot_clip`` applies them to the kernel's
output in ``stdp_step``'s op order.

Everything is f32 and the full ring must fit in VMEM
(``kernel_policy.FUSED_MAX_RING_BYTES``); ``kernel_policy.resolve`` gates
eligibility.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.neuron import Propagators
from repro.kernels.ell_deliver import (LANE, SUB, TILE, deliver_row_tile,
                                       dma, fetch_row_tile, gather_lane,
                                       pad_table, ring_from_tiles,
                                       ring_lanes, ring_to_tiles,
                                       scatter_add, store_row_tile, to_tiles,
                                       vmem_limit, when_live)


def _lif_math(V, I_ex, I_in, refrac, in_ex, in_in, i_dc,
              prop: Propagators):
    """The exact op order of ``lif_update._kernel`` (and ``lif_step``)."""
    V_new = (prop.E_L
             + (V - prop.E_L) * prop.P22
             + I_ex * prop.P21_ex
             + I_in * prop.P21_in
             + i_dc * prop.P20)
    iexo = I_ex * prop.P11_ex + in_ex
    iino = I_in * prop.P11_in + in_in
    refractory = refrac > 0
    V_new = jnp.where(refractory, prop.V_reset, V_new)
    spiked = (V_new >= prop.V_th) & jnp.logical_not(refractory)
    Vo = jnp.where(spiked, prop.V_reset, V_new)
    refo = jnp.where(
        spiked, prop.ref_steps, jnp.maximum(refrac - 1, 0)
    ).astype(refrac.dtype)
    return Vo, iexo, iino, refo, spiked


def _lif_phase(meta_ref, ins, ring, outs, ring_out, sem, *, d_bins: int,
               n_tiles: int, prop: Propagators):
    """Integrate against the just-delivered ring, tile by tile, consume the
    slot, then write the ring back to HBM."""
    V_ref, iex_ref, iin_ref, ref_ref, ext_ref, idc_ref = ins
    Vo_ref, iexo_ref, iino_ref, refo_ref, spk_ref = outs
    slot = jax.lax.rem(meta_ref[0] + 1, d_bins)
    zeros = jnp.zeros((SUB, LANE), jnp.float32)

    def body(b, _):
        res = _lif_math(V_ref[b], iex_ref[b], iin_ref[b], ref_ref[b],
                        ring[slot * 2, b] + ext_ref[b], ring[slot * 2 + 1, b],
                        idc_ref[b], prop)
        for o, x in zip(outs, res):
            o[b] = x.astype(o.dtype)
        ring[slot * 2, b] = zeros
        ring[slot * 2 + 1, b] = zeros
        return 0

    jax.lax.fori_loop(0, n_tiles, body, 0)
    dma(ring, ring_out, sem)


def _kernel_static(ids_ref, meta_ref, lens_ref, tgt_hbm, w_hbm, db_hbm,
                   ring_in, V_ref, iex_ref, iin_ref, ref_ref, ext_ref, idc_ref,
                   ring_out, Vo_ref, iexo_ref, iino_ref, refo_ref, spk_ref,
                   tgt_s, w_s, db_s, ring, sems,
                   *, d_bins: int, block_k: int, s_budget: int,
                   n_tiles: int, prop: Propagators):
    s = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when((s == 0) & (kb == 0))
    def _init():
        dma(ring_in, ring, sems.at[0])

    @pl.when(s < s_budget)
    def _deliver():
        deliver_row_tile(s, kb, ids_ref, meta_ref, lens_ref,
                         (tgt_hbm, w_hbm, db_hbm), (tgt_s, w_s, db_s), ring,
                         sems, d_bins=d_bins, block_k=block_k)

    @pl.when((s == s_budget) & (kb == 0))
    def _update():
        _lif_phase(meta_ref,
                   (V_ref, iex_ref, iin_ref, ref_ref, ext_ref, idc_ref),
                   ring, (Vo_ref, iexo_ref, iino_ref, refo_ref, spk_ref),
                   ring_out, sems.at[0], d_bins=d_bins, n_tiles=n_tiles,
                   prop=prop)


def _kernel_plastic(ids_ref, meta_ref, lens_ref, tgt_hbm, w_hbm, db_hbm,
                    pm_hbm, ring_in, V_ref, iex_ref, iin_ref, ref_ref,
                    ext_ref, idc_ref, xpre_ref, xpost_ref, spkprev_ref,
                    ring_out, w_out, Vo_ref, iexo_ref, iino_ref, refo_ref,
                    spk_ref, xpreo_ref, xposto_ref,
                    tgt_s, w_s, db_s, pm_s, ring, sems,
                    *, d_bins: int, block_k: int, s_budget: int,
                    n_tiles: int, prop: Propagators, dep_coef: float,
                    decay_p: float, decay_m: float):
    s = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when((s == 0) & (kb == 0))
    def _init():
        dma(ring_in, ring, sems.at[0])

    @pl.when(s < s_budget)
    def _deliver():
        @when_live(s, kb, lens_ref, block_k=block_k)
        def _walk():
            t_prev = meta_ref[0]
            sid = ids_ref[s]
            ch = jnp.where(sid >= meta_ref[1], 1, 0).astype(jnp.int32)
            # weights come from the aliased output: an earlier spike in
            # the same 8-row tile has already written its depressed row
            r = fetch_row_tile(sid, kb, (tgt_hbm, w_out, db_hbm, pm_hbm),
                               (tgt_s, w_s, db_s, pm_s), sems,
                               block_k=block_k)

            def body(j, _):
                tg = tgt_s[r, j]
                w = w_s[r, j]
                slot = jax.lax.rem(t_prev + db_s[r, j], d_bins)
                scatter_add(ring, slot * 2 + ch, tg, w)
                # pair-STDP depression on the fetched tile while it's
                # on-chip: same single-rounded coefficient as stdp_step
                xp = gather_lane(xpost_ref, tg)
                dw = jnp.where(pm_s[r, j] != 0, -(dep_coef * xp), 0.0)
                w_s[r, j] = w + dw
                return 0

            jax.lax.fori_loop(0, block_k, body, 0)
            store_row_tile(sid, kb, w_s, w_out, sems.at[0],
                           block_k=block_k)

    @pl.when((s == s_budget) & (kb == 0))
    def _update():
        _lif_phase(meta_ref,
                   (V_ref, iex_ref, iin_ref, ref_ref, ext_ref, idc_ref),
                   ring, (Vo_ref, iexo_ref, iino_ref, refo_ref, spk_ref),
                   ring_out, sems.at[0], d_bins=d_bins, n_tiles=n_tiles,
                   prop=prop)
        spkf = spkprev_ref[...]
        xpreo_ref[...] = xpre_ref[...] * decay_p + spkf
        xposto_ref[...] = xpost_ref[...] * decay_m + spkf


def _call(kernel, name, ids, lens, t_prev, n_exc, tables, ring, vecs,
          n_vec_out, extra_out, aliases, *, d_bins, n_cols, block_k,
          interpret):
    """Shared ``pallas_call`` plumbing of the two fused kernels: three
    scalar-prefetch operands (``ids``, ``[t_prev, n_exc]``, ``lens``), HBM
    tables and ring, tiled per-neuron vectors, one VMEM ring scratch;
    ``name`` names the kernel on the device.
    ``vecs`` are the per-neuron input vectors; the last outputs are
    ``n_vec_out`` tiled vectors, f32 but for the fourth (refractory
    counter) and fifth (spikes, 0/1), which are int32."""
    s_budget = ids.shape[0]
    assert s_budget >= 1, "fused step needs spike_budget >= 1"
    n_lanes = ring_lanes(n_cols)
    n_tiles = n_lanes // TILE
    ring_bytes = 2 * d_bins * n_lanes * 4
    meta = jnp.stack([jnp.asarray(t_prev, jnp.int32),
                      jnp.full((), n_exc, jnp.int32)])
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vec = pl.BlockSpec((n_tiles, SUB, LANE),
                       lambda s, kb, ids, meta, lens: (0, 0, 0))
    tile = jax.ShapeDtypeStruct((n_tiles, SUB, LANE), jnp.float32)
    vec_out = [tile] * n_vec_out
    vec_out[3] = jax.ShapeDtypeStruct(tile.shape, jnp.int32)      # refrac
    vec_out[4] = jax.ShapeDtypeStruct(tile.shape, jnp.int32)      # spiked
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_budget + 1, tables[0].shape[1] // block_k),
        in_specs=[hbm] * (len(tables) + 1) + [vec] * len(vecs),
        out_specs=[hbm] * (1 + len(extra_out)) + [vec] * n_vec_out,
        scratch_shapes=[pltpu.SMEM((SUB, block_k), tb.dtype)
                        for tb in tables] + [
            pltpu.VMEM((2 * d_bins, n_tiles, SUB, LANE), jnp.float32),
            pltpu.SemaphoreType.DMA((len(tables),)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(kernel, d_bins=d_bins, block_k=block_k,
                          s_budget=s_budget, n_tiles=n_tiles),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(
            (2 * d_bins, n_tiles, SUB, LANE), jnp.float32)] + extra_out
        + vec_out,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            ring_bytes, len(vecs) + n_vec_out, n_lanes)),
        interpret=interpret,
        name=name,
    )(ids, meta, lens, *tables, ring_to_tiles(ring, n_lanes),
      *[to_tiles(x, n_lanes) for x in vecs])
    ring_out = ring_from_tiles(outs[0], n_cols)
    return ring_out, outs[1:]


def _vec(x, n):
    return x.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=(
    "d_bins", "n_cols", "n", "n_exc", "prop", "block_k", "interpret"))
def lif_deliver_pallas(ids, lens, targets, weights, dbins, ring, V, I_ex,
                       I_in, refrac, ext_ex, i_dc, t_prev, *, d_bins: int,
                       n_cols: int, n: int, n_exc: int, prop: Propagators,
                       block_k: int = 128, interpret: bool = False):
    """One fused step: deliver ``ids`` at ring phase ``t_prev``, then
    integrate step ``t_prev + 1``.

    ``ids``[S] int32 in [0, N] (N = sentinel), ``lens``[S] int32 the real
    lengths of their rows (0 for the sentinel), ELL tables ``[N+1, K]``
    (rows past N, if any, are sentinel rows),
    ``ring``[D, 2, n_cols] f32, state vectors [n] (n = n_cols - 1),
    ``ext_ex``/``i_dc`` the pre-scaled external drive.  Returns
    ``(ring', V', I_ex', I_in', refrac', spiked)``.
    """
    tables = (pad_table(targets, block_k, n_cols - 1),
              pad_table(weights, block_k, 0.0),
              pad_table(dbins, block_k, 1))
    ring_out, (Vo, iexo, iino, refo, spk) = _call(
        functools.partial(_kernel_static, prop=prop), "lif_deliver_static",
        ids, lens, t_prev, n_exc, tables, ring,
        [V, I_ex, I_in, refrac, ext_ex, i_dc], 5, [],
        # input 6 is the ring (indices count the 3 prefetch operands)
        {6: 0}, d_bins=d_bins, n_cols=n_cols, block_k=block_k,
        interpret=interpret)
    return (ring_out, _vec(Vo, n), _vec(iexo, n), _vec(iino, n),
            _vec(refo, n), _vec(spk, n) != 0)


@functools.partial(jax.jit, static_argnames=(
    "d_bins", "n_cols", "n", "n_exc", "prop", "block_k", "interpret",
    "dep_coef", "decay_p", "decay_m"))
def lif_deliver_plastic_pallas(ids, lens, targets, weights, dbins, pmask,
                               ring,
                               V, I_ex, I_in, refrac, ext_ex, i_dc,
                               x_pre, x_post, spk_prev, t_prev, *,
                               d_bins: int, n_cols: int, n: int,
                               n_exc: int, prop: Propagators,
                               dep_coef: float, decay_p: float,
                               decay_m: float, block_k: int = 128,
                               interpret: bool = False):
    """Plastic fused step: static step + in-tile pair-STDP depression and
    on-chip trace decay.

    ``ids`` and ``lens`` as for :func:`lif_deliver_pallas`.  ``weights``
    must be the *live* plastic weight table (ELL-padded view
    of the flat plastic weights) and ``pmask`` its plastic-synapse mask
    (bool or int32), both shaped like ``targets``; ``spk_prev`` is
    ``spiked_prev`` as f32 (the trace bump of the step whose spikes are
    being delivered).  Returns ``(ring', weights', V', I_ex', I_in',
    refrac', spiked, x_pre', x_post')`` with ``weights'`` shaped like
    ``weights`` — potentiation and clipping stay in XLA
    (``repro.core.plasticity.stdp_pot_clip``).
    """
    tables = (pad_table(targets, block_k, n_cols - 1),
              pad_table(weights, block_k, 0.0),
              pad_table(dbins, block_k, 1),
              pad_table(pmask.astype(jnp.int32), block_k, 0))
    w_shape = jax.ShapeDtypeStruct(tables[1].shape, jnp.float32)
    ring_out, (w_out, Vo, iexo, iino, refo, spk, xpreo, xposto) = _call(
        functools.partial(_kernel_plastic, prop=prop, dep_coef=dep_coef,
                          decay_p=decay_p, decay_m=decay_m),
        "lif_deliver_plastic", ids, lens, t_prev, n_exc, tables, ring,
        [V, I_ex, I_in, refrac, ext_ex, i_dc, x_pre, x_post, spk_prev], 7,
        [w_shape],
        # ring -> ring', live weights -> depressed weights (input indices
        # count the 3 prefetch operands)
        {7: 0, 4: 1}, d_bins=d_bins, n_cols=n_cols, block_k=block_k,
        interpret=interpret)
    rows, k = weights.shape
    return (ring_out, w_out[:rows, :k], _vec(Vo, n), _vec(iexo, n),
            _vec(iino, n), _vec(refo, n), _vec(spk, n) != 0,
            _vec(xpreo, n), _vec(xposto, n))
