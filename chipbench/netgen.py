"""The network instance a cell runs, made from its configuration file.

This is the benchmark's data, the counterpart of a model's weights: the
Potjans-Diesmann microcircuit at the configuration's scale, drawn from the
file's fixed ``network_seed``.  It follows the published connectivity rule
(NEST's ``fixed_total_number`` per projection, multapses and autapses
allowed), weights and delays as normals clipped to their ranges, and the
van Albada et al. (2015) DC compensation when in-degrees are scaled down.

The per-source synapse counts are drawn on the host (one multinomial per
projection, a few milliseconds); targets, weights and delays are drawn on
the device, block of rows by block of rows, straight into the padded
per-source (ELL) layout: row ``i`` holds source ``i``'s synapses grouped by
target population, padded with the sentinel target ``N``.

Nothing here imports the program under test.  ``network`` returns numpy
arrays; the harness wraps them into the program's input type and the plain
reference reads them as they are.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: ELL rows are padded to a whole number of these columns.
LANE = 128
#: Rows drawn per device call (bounds the generator's device memory).
BLOCK_ROWS = 8192


class Model(NamedTuple):
    """Sizes and constants derived from a configuration (host, small)."""
    pops: tuple              # population names, excitatory first
    n_pop: np.ndarray        # [P] neurons per population
    offsets: np.ndarray      # [P+1]
    n_total: int
    n_exc: int
    n_syn: np.ndarray        # [P, P] synapses per projection (target, source)
    w_mean: np.ndarray       # [P, P] mean weight (pA) before scaling
    w_rel_sd: float
    d_mean: tuple            # (excitatory, inhibitory) mean delay, ms
    d_sd: tuple
    d_hi: tuple
    d_max_bins: int
    w_scale: float           # 1 / sqrt(k_scaling)
    w_ext: float             # external weight (pA)
    k_ext: np.ndarray        # [P] external in-degree at this scale
    i_dc: np.ndarray         # [P] DC compensation (pA)
    v0_mean: np.ndarray      # [P]
    v0_sd: np.ndarray        # [P]
    rates: np.ndarray        # [P] published mean rates (Hz)
    dt: float


class Network(NamedTuple):
    """One network instance in ELL layout (host numpy)."""
    model: Model
    targets: np.ndarray      # [N, K] int32, sentinel N
    weights: np.ndarray      # [N, K] float32, signed pA
    dbins: np.ndarray        # [N, K] int32 >= 1
    out_degree: np.ndarray   # [N] int32
    pop_of: np.ndarray       # [N] int32


def psc_from_psp(psp: float, C_m: float, tau_m: float, tau_s: float) -> float:
    """Peak PSC (pA) of an exponential current that gives a PSP of ``psp``
    mV, as in the NEST microcircuit's ``helpers.py``."""
    psc_over_psp = (C_m ** -1 * tau_m * tau_s / (tau_s - tau_m) * (
        (tau_m / tau_s) ** (-tau_m / (tau_m - tau_s))
        - (tau_m / tau_s) ** (-tau_s / (tau_m - tau_s)))) ** -1
    return psc_over_psp * psp


def _order(cfg: dict) -> list:
    """Indices of the configuration's populations, excitatory first (the
    simulator keeps neurons ``[0, n_exc)`` excitatory)."""
    pops = cfg["populations"]
    exc = [i for i, p in enumerate(pops) if p in cfg["excitatory"]]
    return exc + [i for i in range(len(pops)) if i not in exc]


def model(cfg: dict) -> Model:
    """Every size and constant of the configuration, in simulator order."""
    idx = _order(cfg)
    pick = lambda key: np.asarray(cfg[key], np.float64)[idx]
    n_full = pick("n_full")
    probs = np.asarray(cfg["conn_probs"], np.float64)[np.ix_(idx, idx)]
    n_pop = np.maximum(1, np.round(n_full * cfg["n_scaling"])).astype(np.int64)
    k_scaling = float(cfg["k_scaling"])

    # fixed_total_number: K = ln(1 - p) / ln(1 - 1/(N_t N_s)) at full
    # size, kept per target neuron and scaled by k_scaling
    prod = np.outer(n_full, n_full)
    with np.errstate(divide="ignore"):
        k_full = np.where(probs > 0,
                          np.log1p(-probs) / np.log1p(-1.0 / prod), 0.0)
    indeg_full = k_full / n_full[:, None]
    n_syn = np.round(indeg_full * k_scaling * n_pop[:, None]).astype(np.int64)

    nrn, syn = cfg["neuron"], cfg["synapse"]
    n_exc_pops = len(cfg["excitatory"])
    pops = tuple(cfg["populations"][i] for i in idx)
    w_e = psc_from_psp(syn["PSP_e"], nrn["C_m"], nrn["tau_m"],
                       nrn["tau_syn_ex"])
    exc_src = np.arange(len(pops)) < n_exc_pops
    w_mean = np.broadcast_to(np.where(exc_src, w_e, syn["g"] * w_e),
                             probs.shape).copy()
    w_mean[pops.index("L23E"), pops.index("L4E")] *= syn["PSP_23e_4e_factor"]

    d_mean = (syn["delay_e"], syn["delay_i"])
    d_sd = tuple(d * syn["delay_rel_sd"] for d in d_mean)
    d_hi = tuple(m + syn["d_clip_sigmas"] * s for m, s in zip(d_mean, d_sd))
    dt = float(cfg["dt_ms"])

    # van Albada et al. 2015: the mean input lost to k_scaling < 1 as DC
    k_ext_full = pick("k_ext")
    rates = pick("full_mean_rates_hz")
    x1_rec = (indeg_full * w_mean * rates[None, :]).sum(axis=1)
    x1_ext = k_ext_full * w_e * cfg["bg_rate_hz"]
    i_dc = (0.001 * nrn["tau_syn_ex"] * (1.0 - math.sqrt(k_scaling))
            * (x1_rec + x1_ext))
    w_scale = 1.0 / math.sqrt(k_scaling)
    offsets = np.concatenate([[0], np.cumsum(n_pop)])
    return Model(
        pops=pops, n_pop=n_pop, offsets=offsets, n_total=int(offsets[-1]),
        n_exc=int(offsets[n_exc_pops]), n_syn=n_syn, w_mean=w_mean,
        w_rel_sd=float(syn["PSP_rel_sd"]), d_mean=d_mean, d_sd=d_sd,
        d_hi=d_hi, d_max_bins=int(math.ceil(max(d_hi) / dt)) + 1,
        w_scale=w_scale, w_ext=w_e * w_scale, k_ext=k_ext_full * k_scaling,
        i_dc=i_dc, v0_mean=pick("v0_mean_mV"), v0_sd=pick("v0_sd_mV"),
        rates=rates, dt=dt)


def out_counts(m: Model, seed: int) -> np.ndarray:
    """[N, P] synapses of each source neuron onto each target population:
    per projection, its total spread uniformly over the source population
    (one multinomial), as drawing each synapse's source uniformly does."""
    rng = np.random.default_rng(seed)
    counts = np.zeros((m.n_total, len(m.pops)), np.int32)
    for t in range(len(m.pops)):
        for s in range(len(m.pops)):
            k = int(m.n_syn[t, s])
            if k:
                n_s = int(m.n_pop[s])
                counts[m.offsets[s]:m.offsets[s + 1], t] = rng.multinomial(
                    k, np.full(n_s, 1.0 / n_s))
    return counts


@functools.partial(jax.jit, static_argnames=("k", "consts"))
def _fill(key, rows, counts, k: int, consts):
    """Targets, weights and delay bins of one block of rows ``[B, k]``."""
    (n, n_exc, offsets, w_mean, w_rel_sd, d_mean, d_sd, d_hi, dt,
     w_scale, pop_bounds) = consts
    offsets = jnp.asarray(offsets, jnp.int32)
    w_mean = jnp.asarray(w_mean, jnp.float32)
    n_p = w_mean.shape[0]
    col = jnp.arange(k, dtype=jnp.int32)[None, :]
    cum = jnp.cumsum(counts, axis=1)                        # [B, P]
    tpop = sum((col >= cum[:, i:i + 1]).astype(jnp.int32)
               for i in range(n_p))                         # P == padding
    valid = tpop < n_p
    tpop = jnp.minimum(tpop, n_p - 1)
    k_t, k_w, k_d = jax.random.split(key, 3)
    tgt = jax.random.randint(k_t, tpop.shape, offsets[tpop],
                             offsets[tpop + 1], dtype=jnp.int32)
    spop = jnp.searchsorted(jnp.asarray(pop_bounds, jnp.int32),
                            jnp.minimum(rows, n - 1), side="right")
    exc = (rows < n_exc)[:, None]
    mean = w_mean[tpop, spop[:, None]]
    w = mean + jnp.abs(mean) * w_rel_sd * jax.random.normal(
        k_w, tpop.shape, jnp.float32)
    w = jnp.where(exc, jnp.maximum(w, 0.0), jnp.minimum(w, 0.0))
    dm = jnp.where(exc, d_mean[0], d_mean[1])
    ds = jnp.where(exc, d_sd[0], d_sd[1])
    dh = jnp.where(exc, d_hi[0], d_hi[1])
    d = jnp.clip(dm + ds * jax.random.normal(k_d, tpop.shape, jnp.float32),
                 dt, dh)
    db = jnp.maximum(1, jnp.round(d / dt)).astype(jnp.int32)
    return (jnp.where(valid, tgt, n),
            jnp.where(valid, w * w_scale, 0.0).astype(jnp.float32),
            jnp.where(valid, db, 1))


def network(cfg: dict) -> Network:
    """The configuration's network instance (deterministic in
    ``network_seed``), drawn on the default device."""
    import jax
    m = model(cfg)
    seed = int(cfg["network_seed"])
    counts = out_counts(m, seed)
    deg = counts.sum(axis=1).astype(np.int32)
    k = max(LANE, -(-int(deg.max()) // LANE) * LANE)
    n = m.n_total
    consts = (n, m.n_exc, tuple(int(o) for o in m.offsets),
              tuple(map(tuple, m.w_mean.tolist())), m.w_rel_sd, m.d_mean,
              m.d_sd, m.d_hi, m.dt, m.w_scale,
              tuple(int(o) for o in m.offsets[1:-1]))
    targets = np.empty((n, k), np.int32)
    weights = np.empty((n, k), np.float32)
    dbins = np.empty((n, k), np.int32)
    key = jax.random.PRNGKey(seed)
    block = min(BLOCK_ROWS, n)
    for r0 in range(0, n, block):
        rows = np.arange(r0, r0 + block, dtype=np.int32)
        c = np.zeros((block, counts.shape[1]), np.int32)
        c[:min(block, n - r0)] = counts[r0:r0 + block]
        out = _fill(jax.random.fold_in(key, r0), rows, c, k=k, consts=consts)
        r1 = min(n, r0 + block)
        for dst, src in zip((targets, weights, dbins), out):
            dst[r0:r1] = np.asarray(src)[:r1 - r0]
    pop_of = np.repeat(np.arange(len(m.pops), dtype=np.int32), m.n_pop)
    return Network(model=m, targets=targets, weights=weights, dbins=dbins,
                   out_degree=deg, pop_of=pop_of)
