"""Pallas TPU kernel: sparse-ELL spike delivery (the ``ell`` strategy).

Event delivery is a gather of S spiking rows from the padded ELL
out-adjacency ``[N+1, K]`` followed by a scatter-add of the ``S x K``
(target, weight, delay-bin) triples into the ring buffer.  The XLA lowering
of that pattern materialises the ``[S, K]`` gathered rows in HBM and runs
the scatter as a second pass; this kernel fuses both:

* the step's spike ids and their rows' lengths are **scalar-prefetched**
  (SMEM), and the three ELL tables stay in HBM (``memory_space=ANY``).
  Each grid step that has work DMAs the ``(8, block_k)`` tile that holds
  row ``ids[s]`` into SMEM scratch — the TPU DMA engine moves whole
  ``(8, 128)`` tiles, so a lone row cannot be a block — and reads that
  row's triples as scalars,
* each triple is **scatter-added on-chip** into a VMEM-resident ring laid
  out as ``[2D, N_lanes/1024, 8, 128]`` (rows ``slot*2 + channel``): the
  ``(8, 128)`` tile holding the target gets a masked add at the target's
  sublane and lane.  Mosaic cannot store a scalar into VMEM, so this is
  the narrowest write it accepts.  Padded entries land in the trailing
  dump column with weight 0.

The ring update accumulates across the whole grid in one VMEM scratch
buffer and is DMA'd to HBM once, at the last grid step.  Memory is
O(N*K).  The single resident ring caps the kernel at
``kernel_policy.FUSED_MAX_RING_BYTES`` of VMEM; past that ``auto`` keeps
the XLA gather/scatter.

Grid: ``(S, K/block_k)`` — spikes outer, row tiles inner, so the scatter
order (s-major, k-minor) matches the XLA scatter of ``deliver_event`` and
results agree bitwise.  Work follows the delivered rows' real tiles, not
the grid: the real length ``len[s]`` of row ``ids[s]``
(``EventTables.row_len``, 0 for the sentinel row) is scalar-prefetched
too, and grid step ``(s, kb)`` fetches and walks its tile only if the
tile holds a real synapse, ``kb * block_k < len[s]`` (:func:`when_live`).
Sentinel rows that pad ``ids`` up to the budget and the padded tail of
each row cost a grid step each and nothing more; a skipped entry would
only have added weight 0 into the dump column.  :func:`walked_tiles`
counts the tiles walked.  The per-synapse loop is scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB, LANE = 8, 128            # f32 vreg tile
TILE = SUB * LANE             # neurons per ring tile
#: VMEM headroom over the buffers a kernel declares (Mosaic internal
#: scratch, pipeline bookkeeping).
VMEM_HEADROOM = 4 * 1024 * 1024


def ring_lanes(n_cols: int) -> int:
    """Ring columns padded to whole ``(8, 128)`` tiles."""
    return -(-n_cols // TILE) * TILE


def to_tiles(x: jnp.ndarray, n_lanes: int) -> jnp.ndarray:
    """A per-neuron vector ``[n]`` as zero-padded tiles ``[n_lanes/1024, 8,
    128]``."""
    x = jnp.pad(x, (0, n_lanes - x.shape[0]))
    return x.reshape(n_lanes // TILE, SUB, LANE)


def ring_to_tiles(ring: jnp.ndarray, n_lanes: int) -> jnp.ndarray:
    """``ring[D, 2, n_cols]`` -> ``[2D, n_lanes/1024, 8, 128]``."""
    d, two, n_cols = ring.shape
    ring = jnp.pad(ring.reshape(d * two, n_cols),
                   ((0, 0), (0, n_lanes - n_cols)))
    return ring.reshape(d * two, n_lanes // TILE, SUB, LANE)


def ring_from_tiles(tiles: jnp.ndarray, n_cols: int) -> jnp.ndarray:
    """Inverse of :func:`ring_to_tiles`."""
    rows = tiles.shape[0]
    return tiles.reshape(rows // 2, 2, -1)[:, :, :n_cols]


def pad_table(table: jnp.ndarray, block_k: int, fill) -> jnp.ndarray:
    """Pad an ELL table to whole ``(8, block_k)`` tiles with ``fill``
    (sentinel) entries.  ``EllDelivery.prepare`` pre-pads, so on the
    strategy path this is a no-op."""
    rows, k = table.shape
    pad = ((0, -rows % SUB), (0, -k % block_k))
    if pad == ((0, 0), (0, 0)):
        return table
    return jnp.pad(table, pad, constant_values=fill)


def vmem_limit(ring_bytes: int, n_vec_blocks: int, n_lanes: int) -> int:
    """Scoped-VMEM limit for a kernel holding the ring in scratch plus
    ``n_vec_blocks`` double-buffered per-neuron f32/int32 blocks."""
    return ring_bytes + 2 * n_vec_blocks * n_lanes * 4 + VMEM_HEADROOM


def fetch_row_tile(sid, kb, tables, smem, sems, *, block_k: int):
    """DMA the ``(8, block_k)`` tile holding row ``sid`` of each HBM table
    into its SMEM scratch.  Returns the row's index within the tile."""
    r0 = pl.multiple_of(sid - sid % SUB, SUB)
    cols = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
    copies = [pltpu.make_async_copy(src.at[pl.ds(r0, SUB), cols], dst,
                                    sems.at[i])
              for i, (src, dst) in enumerate(zip(tables, smem))]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()
    return sid % SUB


def dma(src, dst, sem):
    """Blocking DMA ``src -> dst``."""
    c = pltpu.make_async_copy(src, dst, sem)
    c.start()
    c.wait()


def store_row_tile(sid, kb, smem, table, sem, *, block_k: int):
    """Write an SMEM tile fetched by :func:`fetch_row_tile` back to HBM."""
    r0 = pl.multiple_of(sid - sid % SUB, SUB)
    cols = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
    dma(smem, table.at[pl.ds(r0, SUB), cols], sem)


def _hit(tg):
    """Mask of neuron ``tg`` within its ``(8, 128)`` tile."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 1)
    return (sub == (tg // LANE) % SUB) & (lane == tg % LANE)


def scatter_add(ring_ref, row, tg, w):
    """``ring[row, tg] += w`` on a tiled ring, as a masked tile update."""
    blk = tg // TILE
    tile = ring_ref[row, blk]
    ring_ref[row, blk] = jnp.where(_hit(tg), tile + w, tile)


def gather_lane(vec_ref, tg):
    """``vec[tg]`` of a tiled per-neuron vector, as a scalar."""
    tile = vec_ref[tg // TILE]
    return jnp.max(jnp.where(_hit(tg), tile, -jnp.inf))


def walked_tiles(lens: jnp.ndarray, block_k: int) -> jnp.ndarray:
    """Row tiles the ELL kernels walk for delivered rows of real lengths
    ``lens``: ``ceil(len / block_k)`` each, the tiles :func:`when_live`
    admits, summed (int32)."""
    return jnp.sum(-(-lens // block_k), dtype=jnp.int32)


def when_live(s, kb, lens_ref, *, block_k: int):
    """``pl.when`` over the grid steps with work: tile ``kb`` of delivered
    spike ``s``'s row holds a real synapse, ``kb * block_k < len[s]``.
    Every real entry lies below its row's length, so this skips only the
    sentinel rows (length 0) and each row's padded tail."""
    return pl.when(kb * block_k < lens_ref[s])


def deliver_row_tile(s, kb, ids_ref, meta_ref, lens_ref, tables, smem, ring,
                     sems, *, d_bins: int, block_k: int):
    """Scatter tile ``kb`` of spike ``s``'s ELL row into the resident ring,
    at ring phase ``meta[0]`` (``meta = [t, n_exc]``), if the tile holds a
    real synapse."""

    @when_live(s, kb, lens_ref, block_k=block_k)
    def _walk():
        t = meta_ref[0]
        sid = ids_ref[s]
        r = fetch_row_tile(sid, kb, tables, smem, sems, block_k=block_k)
        # Dale's law: the source row sets the sign channel.  Padded
        # entries carry weight 0 into the dump column.
        ch = jnp.where(sid >= meta_ref[1], 1, 0).astype(jnp.int32)
        tgt_s, w_s, db_s = smem

        def body(j, _):
            slot = jax.lax.rem(t + db_s[r, j], d_bins)
            scatter_add(ring, slot * 2 + ch, tgt_s[r, j], w_s[r, j])
            return 0

        jax.lax.fori_loop(0, block_k, body, 0)


def _kernel(ids_ref, meta_ref, lens_ref, tgt_hbm, w_hbm, db_hbm, out_hbm,
            tgt_s, w_s, db_s, acc, sems, *, d_bins: int, block_k: int):
    s = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when((s == 0) & (kb == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    deliver_row_tile(s, kb, ids_ref, meta_ref, lens_ref,
                     (tgt_hbm, w_hbm, db_hbm), (tgt_s, w_s, db_s), acc, sems,
                     d_bins=d_bins, block_k=block_k)

    @pl.when((s == pl.num_programs(0) - 1) & (kb == pl.num_programs(1) - 1))
    def _flush():
        dma(acc, out_hbm, sems.at[0])


@functools.partial(jax.jit, static_argnames=("d_bins", "n_cols", "block_k",
                                             "n_exc", "interpret"))
def ell_deliver_pallas(ids: jnp.ndarray, lens: jnp.ndarray,
                       targets: jnp.ndarray, weights: jnp.ndarray,
                       dbins: jnp.ndarray, t: jnp.ndarray, *, d_bins: int,
                       n_cols: int, n_exc: int, block_k: int = 128,
                       interpret: bool = False) -> jnp.ndarray:
    """Ring update from S spike ids through ELL tables.

    ``ids``[S] int32 in [0, N] (N = sentinel row), ``lens``[S] int32 the
    real lengths of their rows (``EventTables.row_len[ids]``; 0 for the
    sentinel), tables ``[N+1, K]`` (rows past N, if any, are sentinel
    rows).  Returns ``upd[d_bins, 2, n_cols]`` f32 to be added onto the
    ring.
    """
    s_budget = ids.shape[0]
    n_sent = n_cols - 1
    targets = pad_table(targets, block_k, n_sent)
    weights = pad_table(weights, block_k, 0.0)
    dbins = pad_table(dbins, block_k, 1)
    n_lanes = ring_lanes(n_cols)
    ring_bytes = 2 * d_bins * n_lanes * 4
    meta = jnp.stack([jnp.asarray(t, jnp.int32),
                      jnp.full((), n_exc, jnp.int32)])
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_budget, targets.shape[1] // block_k),
        in_specs=[hbm, hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.SMEM((SUB, block_k), jnp.int32),
            pltpu.SMEM((SUB, block_k), jnp.float32),
            pltpu.SMEM((SUB, block_k), jnp.int32),
            pltpu.VMEM((2 * d_bins, n_lanes // TILE, SUB, LANE),
                       jnp.float32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, d_bins=d_bins, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (2 * d_bins, n_lanes // TILE, SUB, LANE), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(ring_bytes, 0, n_lanes)),
        interpret=interpret,
        name="ell_deliver",
    )(ids, meta, lens, targets, weights, dbins)
    return ring_from_tiles(out, n_cols)
