"""Connectivity construction for the microcircuit.

The reference model uses NEST's ``fixed_total_number`` rule per projection:
K[t, s] synapses are drawn with independently uniform source and target
neurons (multapses and autapses allowed).  We build two device-ready
representations of the same connectome:

* **ELL (event / ell strategies)** — padded per-source adjacency: for every
  source neuron a fixed-width row of (target id, weight, delay bin).  Rows
  are padded with a sentinel target ``N`` (one dump column is appended to
  the ring buffer so padded entries scatter into a discarded slot with
  weight 0).  O(N*K) — the layout that reaches full scale; the ``ell``
  strategy's Pallas kernel consumes it row-tile by row-tile.

* **Dense delay-binned (dense strategy)** — ``W[Dbins, N_pre, N_post]`` with
  the signed weight summed into its delay bin.  Multapses sum, exactly as the
  ring-buffer accumulation would.  O(N^2) per bin: construction is guarded
  by a byte estimate (``dense_bytes_estimate``) so large networks fail with
  a pointer to ``strategy="ell"`` instead of OOM-ing.

Both are produced by numpy on the host (this is model *instantiation*, the
paper excludes it from the timed simulation phase as well).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import params as P


@dataclasses.dataclass
class Connectome:
    """Host-side connectome in ELL layout plus metadata."""
    n_total: int
    n_exc: int                      # neurons [0, n_exc) are excitatory
    pop_sizes: np.ndarray           # [8]
    pop_offsets: np.ndarray         # [9] prefix sum
    # ELL out-adjacency
    targets: np.ndarray             # [N, K_max] int32, sentinel == n_total
    weights: np.ndarray             # [N, K_max] float32 (signed, pA)
    dbins: np.ndarray               # [N, K_max] int32, ring slot offset >= 1
    out_degree: np.ndarray          # [N] int32
    n_synapses: int
    d_max_bins: int                 # ring buffer length D (>= max dbin + 1)
    # Per-neuron external drive
    k_ext: np.ndarray               # [N] float32 external in-degree
    i_dc: np.ndarray                # [N] float32 DC compensation (pA)
    w_ext: float                    # external synaptic weight (pA)
    v0_mean: np.ndarray             # [N]
    v0_sd: np.ndarray               # [N]
    pop_of: np.ndarray              # [N] int32 population index
    k_scaling: float = 1.0          # in-degree scaling this net was built at
                                    # (stimuli scale their in-degrees by it)


def _truncated_normal(rng: np.random.Generator, mean, sd, low, high, size):
    """Draw normal(mean, sd) clipped into [low, high].

    NEST redraws out-of-range values; at the parameter settings of this model
    the clip region is >=4 sd from the mean so clipping == redrawing up to
    O(1e-5) effects. We clip (documented deviation, DESIGN.md section 7).
    """
    x = rng.normal(mean, sd, size=size)
    return np.clip(x, low, high)


def build_connectome(
    n_scaling: float = 1.0,
    k_scaling: float = 1.0,
    seed: int = 55,
    neuron: Optional[P.NeuronParams] = None,
    syn: Optional[P.SynapseParams] = None,
    inp: Optional[P.InputParams] = None,
    dt: float = 0.1,
    k_pad_to: Optional[int] = None,
    scale: Optional[float] = None,
) -> Connectome:
    """Instantiate the microcircuit at any scale.

    ``scale`` is the single NEST-style down-scaling knob: it sets both the
    neuron-count scaling ``n_scaling`` and the in-degree scaling
    ``k_scaling`` at once, with the lost recurrent/external mean input
    compensated by a per-population DC current (van Albada et al. 2015) so
    firing rates stay near the full-scale reference at every scale — the
    ladder every delivery strategy is exercised on, from toy (~0.01) to the
    paper's full density (1.0).  Passing ``scale`` together with an
    explicit ``n_scaling``/``k_scaling`` is a conflict and raises.
    """
    if scale is not None:
        if (n_scaling, k_scaling) != (1.0, 1.0):
            raise ValueError(
                "pass either scale= or n_scaling=/k_scaling=, not both "
                f"(got scale={scale}, n_scaling={n_scaling}, "
                f"k_scaling={k_scaling})")
        if not 0.0 < scale <= 1.0:
            raise ValueError(f"scale must be in (0, 1], got {scale}")
        n_scaling = k_scaling = float(scale)
    neuron = neuron or P.NeuronParams()
    syn = syn or P.SynapseParams()
    inp = inp or P.InputParams()
    rng = np.random.default_rng(seed)

    n_full = np.array([P.N_FULL[p] for p in P.POPULATIONS], dtype=np.int64)
    n_pop = P.scaled_counts(n_scaling)
    offsets = np.concatenate([[0], np.cumsum(n_pop)])
    n_total = int(offsets[-1])
    n_exc = int(offsets[P.N_EXC_POPS])

    k_per_proj = P.synapse_numbers(n_full, P.CONN_PROBS, n_pop, k_scaling)

    w_e = P.psc_from_psp(syn.PSP_e, neuron)          # ~87.8 pA
    w_i = syn.g * w_e
    w_sd_rel = syn.PSP_rel_sd

    dt_bins = dt
    d_mean = np.array([syn.delay_e, syn.delay_i])
    d_sd = d_mean * syn.delay_rel_sd
    d_hi = d_mean + syn.d_clip_sigmas * d_sd
    d_max_bins = int(np.ceil(d_hi.max() / dt_bins)) + 1

    # --- sample every projection -------------------------------------------
    srcs, tgts, ws, dbs = [], [], [], []
    for t_pop in range(8):
        for s_pop in range(8):
            k = int(k_per_proj[t_pop, s_pop])
            if k == 0:
                continue
            s = rng.integers(offsets[s_pop], offsets[s_pop + 1], size=k)
            t = rng.integers(offsets[t_pop], offsets[t_pop + 1], size=k)
            exc_src = s_pop < P.N_EXC_POPS
            w_mean = w_e if exc_src else w_i
            # L4E -> L23E doubled weight (PD 2014). POPULATIONS order:
            # L23E=0, L4E=1.
            if P.POPULATIONS[s_pop] == "L4E" and P.POPULATIONS[t_pop] == "L23E":
                w_mean = w_mean * syn.PSP_23e_4e_factor
            w_sd = abs(w_mean) * w_sd_rel
            if exc_src:
                w = _truncated_normal(rng, w_mean, w_sd, 0.0, np.inf, k)
            else:
                w = _truncated_normal(rng, w_mean, w_sd, -np.inf, 0.0, k)
            dm, ds, dh = ((d_mean[0], d_sd[0], d_hi[0]) if exc_src
                          else (d_mean[1], d_sd[1], d_hi[1]))
            d = _truncated_normal(rng, dm, ds, dt_bins, dh, k)
            db = np.maximum(1, np.round(d / dt_bins)).astype(np.int32)
            # 4-byte types from here on: at full scale (~0.3e9 synapses)
            # every 8-byte copy of a synapse array costs 2.4 GB of host RAM
            srcs.append(s.astype(np.int32)); tgts.append(t.astype(np.int32))
            ws.append(w.astype(np.float32)); dbs.append(db)

    src, tgt, w, db = (np.concatenate(x) for x in (srcs, tgts, ws, dbs))
    del srcs, tgts, ws, dbs
    n_syn = src.shape[0]

    # --- ELL layout: group synapses by source -------------------------------
    order = np.argsort(src, kind="stable")
    out_deg = np.bincount(src, minlength=n_total).astype(np.int32)
    del src
    k_max = int(out_deg.max()) if n_syn else 1
    if k_pad_to is not None:
        if k_pad_to < k_max:
            raise ValueError(f"k_pad_to={k_pad_to} < max out-degree {k_max}")
        k_max = k_pad_to
    # sorted by source, the synapses fill each row's first out_deg columns
    # in row-major order: exactly the True entries of ``filled``
    filled = np.arange(k_max, dtype=np.int32)[None, :] < out_deg[:, None]
    targets = np.full((n_total, k_max), n_total, dtype=np.int32)
    weights = np.zeros((n_total, k_max), dtype=np.float32)
    dbins = np.ones((n_total, k_max), dtype=np.int32)
    targets[filled] = tgt[order]
    del tgt
    weights[filled] = w[order]
    del w
    dbins[filled] = db[order]
    del db, order, filled

    # --- external drive + down-scaling DC compensation ----------------------
    pop_of = np.repeat(np.arange(8, dtype=np.int32), n_pop)
    k_ext_full = P.K_EXT.astype(np.float64)
    k_ext = k_ext_full * k_scaling

    w_scale = 1.0 / np.sqrt(k_scaling)
    weights *= np.float32(w_scale)
    w_ext = w_e * w_scale

    # van Albada et al. (2015): compensate the lost mean input with DC.
    # mean recurrent input of the full model per target population:
    indeg_full = (P.synapse_numbers(n_full, P.CONN_PROBS, n_full, 1.0)
                  / n_full[:, None])
    w_mat = np.where(np.arange(8)[None, :] < P.N_EXC_POPS, w_e, w_i)
    w_mat = np.broadcast_to(w_mat, (8, 8)).copy()
    s_l4e = P.POPULATIONS.index("L4E"); t_l23e = P.POPULATIONS.index("L23E")
    w_mat[t_l23e, s_l4e] *= syn.PSP_23e_4e_factor
    x1_rec = (indeg_full * w_mat * P.FULL_MEAN_RATES[None, :]).sum(axis=1)
    x1_ext = k_ext_full * w_e * inp.bg_rate
    tau_syn = neuron.tau_syn_ex
    i_dc_pop = 0.001 * tau_syn * (1.0 - np.sqrt(k_scaling)) * (x1_rec + x1_ext)

    return Connectome(
        n_total=n_total,
        n_exc=n_exc,
        pop_sizes=n_pop,
        pop_offsets=offsets,
        targets=targets,
        weights=weights,
        dbins=dbins,
        out_degree=out_deg,
        n_synapses=n_syn,
        d_max_bins=d_max_bins,
        k_ext=k_ext[pop_of].astype(np.float32),
        i_dc=i_dc_pop[pop_of].astype(np.float32),
        w_ext=float(w_ext),
        v0_mean=P.V0_MEAN[pop_of].astype(np.float32),
        v0_sd=P.V0_SD[pop_of].astype(np.float32),
        pop_of=pop_of,
        k_scaling=float(k_scaling),
    )


def dense_bytes_estimate(c: Connectome, itemsize: int = 4) -> int:
    """Host-side footprint of the dense ``W[D, N, N]`` before allocating it."""
    return int(c.d_max_bins) * int(c.n_total) ** 2 * itemsize


#: Allocation cap for the dense strategy (overridable per call). At full
#: scale the dense tensor is ~100 TB; the guard turns the inevitable OOM
#: into an actionable error before any allocation happens.
DENSE_MAX_BYTES = 8 * 1024 ** 3


def dense_delay_binned(c: Connectome, dtype=np.float32,
                       max_bytes: Optional[float] = None) -> np.ndarray:
    """``W[D, N_pre, N_post]`` dense representation (dense strategy).

    Multapses within the same (pre, post, delay-bin) sum — identical to what
    ring-buffer accumulation of individual events produces.

    Guarded by a host-side byte estimate: exceeding ``max_bytes`` (default:
    the module-level ``DENSE_MAX_BYTES``, read at call time so it can be
    raised) fails with the sparse alternative spelled out instead of
    OOM-ing mid-build.
    """
    if max_bytes is None:
        max_bytes = DENSE_MAX_BYTES
    D = c.d_max_bins
    n = c.n_total
    est = dense_bytes_estimate(c, np.dtype(dtype).itemsize)
    if est > max_bytes:
        raise ValueError(
            f"dense delay-binned tensor W[{D}, {n}, {n}] needs "
            f"{est / 1e9:.1f} GB (> cap {max_bytes / 1e9:.1f} GB). The "
            f"dense strategy is O(N^2) per delay bin and cannot reach this "
            f"network size — use strategy='ell' (O(N*K) sparse-ELL Pallas "
            f"delivery) or strategy='event', or shrink the network via "
            f"build_connectome(scale=...). To force the allocation anyway "
            f"call dense_delay_binned(c, max_bytes=...) directly or raise "
            f"repro.core.connectivity.DENSE_MAX_BYTES.")
    W = np.zeros((D, n, n), dtype=dtype)
    rows = np.repeat(np.arange(n), c.targets.shape[1])
    cols = c.targets.reshape(-1)
    ws = c.weights.reshape(-1)
    ds = c.dbins.reshape(-1)
    valid = cols < n
    np.add.at(W, (ds[valid], rows[valid], cols[valid]), ws[valid])
    return W
