"""The plain reference: one chunk of the microcircuit, step by step.

A straightforward ``jax.numpy`` statement of what one simulation step
means, written from the model's definition and imported from nothing of
the program under test:

1. read this step's slot of the delay ring (excitatory and inhibitory
   arrivals), then clear it;
2. the paper's Poisson drive: split the step key into the next key and one
   subkey, draw ``k_ext * rate * dt`` Poisson counts per neuron, add them
   at the external weight.  Where the program splits the neurons over
   ``shards`` chips (NEST's scheme: chip ``d`` owns the contiguous slice
   ``[d * n_loc, (d + 1) * n_loc)``, ``n_loc = ceil(N / shards)``, the
   last slice padded with undriven neurons), the state holds one key per
   chip, ``[shards, 2]``, and slice ``d`` draws from its own:
   ``key_d, sub_d = split(key[d])``, then ``n_loc`` counts
   ``poisson(sub_d, basis[slice_d])`` over the basis padded with zeros,
   padding included; the padding's counts are dropped;
3. exact integration of the ``iaf_psc_exp`` neuron (Rotter & Diesmann
   1999): the membrane moves with the currents of the step before, the
   currents decay and take the arrivals; a refractory neuron is held at
   reset, a neuron at threshold fires and resets;
4. delivery: every synapse of every neuron that fired adds its weight to
   its target's ring slot ``(t + delay) mod D``, excitatory and inhibitory
   sources on their own channel, sources in index order (in reverse order
   under ``Consts.reverse_delivery``: a sound program that sums in
   another order, for the readings limits are set from);
5. with pair STDP on the E->E synapses (Morrison et al. 2008): a source
   that fired depresses its plastic synapses by ``A_minus * x_post`` of
   the target, a target that fired potentiates its incoming plastic
   synapses by ``A_plus * x_pre`` of the source, plastic weights clip to
   ``[0, w_max]``, and both traces decay and take this step's spikes.
   Delivery in a step uses the weights from before that step's update.

The chunk starts from a state the program handed over (its state before a
timed call) and returns the state after ``n_steps`` with each step's
spike count per population, for :mod:`chipbench.compare`.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.netgen import Network

W_REF_FULL = 87.8      # pA: the full-scale weight pair_stdp's w_ref is given in


class Stdp(NamedTuple):
    dep: float          # lr * A_minus * w_ref
    pot: float          # lr * A_plus * w_ref
    decay_pre: float
    decay_post: float
    w_max: float


class Consts(NamedTuple):
    """Everything static about a chunk (hashable: a jit static)."""
    n: int
    n_exc: int
    n_pops: int
    d_bins: int
    budget: int          # spikes delivered per step (all, or flagged)
    E_L: float
    V_th: float
    V_reset: float
    P22: float
    P21_ex: float
    P21_in: float
    P20: float
    P11_ex: float
    P11_in: float
    ref_steps: int
    w_ext: float
    dtype: str
    stdp: Optional[Stdp]
    shards: int = 1      # chips the drive's keys are split over
    reverse_delivery: bool = False   # sum each step's arrivals backwards


def spike_budget(net: Network) -> int:
    """Per-step spike capacity: eight times the spikes a step gets at the
    published rates, in whole 128s.  A step over it is flagged, never
    silently cut."""
    m = net.model
    expected = float((m.n_pop * m.rates).sum()) * m.dt * 1e-3
    return int(min(max(128, math.ceil(expected * 8 / 128) * 128),
                   math.ceil(m.n_total / 128) * 128))


def stdp_consts(cfg: dict, w_ext: float) -> Optional[Stdp]:
    """Pair-STDP constants; ``w_ref`` is given at full scale and follows
    the network's external weight."""
    p = cfg.get("plasticity")
    if not p:
        return None
    if p["kind"] != "pair_stdp":
        raise ValueError(f"no reference for plasticity {p['kind']!r}")
    w_ref = p["w_ref"] * float(w_ext) / W_REF_FULL
    dt = cfg["dt_ms"]
    return Stdp(dep=float(p["lr"] * p["A_minus"] * w_ref),
                pot=float(p["lr"] * p["A_plus"] * w_ref),
                decay_pre=float(np.exp(-dt / p["tau_plus"])),
                decay_post=float(np.exp(-dt / p["tau_minus"])),
                w_max=p["w_max_factor"] * w_ref)


def consts(net: Network, cfg: dict, dtype: str = "float32",
           shards: int = 1) -> Consts:
    nrn, m = cfg["neuron"], net.model
    dt, tau_m, C_m = m.dt, nrn["tau_m"], nrn["C_m"]
    p22 = float(np.exp(-dt / tau_m))

    def p21(tau_x):
        return float((np.exp(-dt / tau_x) - np.exp(-dt / tau_m))
                     / (C_m * (1.0 / tau_m - 1.0 / tau_x)))
    return Consts(
        n=m.n_total, n_exc=m.n_exc, n_pops=len(m.pops),
        d_bins=m.d_max_bins, budget=spike_budget(net),
        E_L=nrn["E_L"], V_th=nrn["V_th"], V_reset=nrn["V_reset"],
        P22=p22, P21_ex=p21(nrn["tau_syn_ex"]), P21_in=p21(nrn["tau_syn_in"]),
        P20=float(tau_m / C_m * (1.0 - p22)),
        P11_ex=float(np.exp(-dt / nrn["tau_syn_ex"])),
        P11_in=float(np.exp(-dt / nrn["tau_syn_in"])),
        ref_steps=int(round(nrn["t_ref"] / dt)), w_ext=float(m.w_ext),
        dtype=dtype, stdp=stdp_consts(cfg, m.w_ext), shards=int(shards))


def tables(net: Network, cfg: dict, device=None) -> dict:
    """The network on ``device`` (the default device where none is given),
    with one sentinel source row ``N`` whose synapses all point at the
    ring's spare column ``N`` with weight 0.  A plastic network's weights
    live in the state, so it has no ``weights`` table."""
    m = net.model
    n, k = net.targets.shape
    row = lambda a, fill: np.concatenate([a, np.full((1, k), fill, a.dtype)])
    put = jnp.asarray if device is None else (
        lambda a: jax.device_put(a, device))
    pop_of = net.pop_of
    basis = (m.k_ext[pop_of].astype(np.float32)
             * np.float32(cfg["bg_rate_hz"] * m.dt * 1e-3))
    targets = row(net.targets, n)
    tb = dict(targets=put(targets), dbins=put(row(net.dbins, 1)),
              basis=put(basis),
              i_dc=put(m.i_dc[pop_of].astype(np.float32)),
              pop_of=put(pop_of))
    if cfg.get("plasticity"):
        exc = np.arange(n + 1) < m.n_exc
        tb["plastic"] = put(exc[:, None] & (targets < m.n_exc))
        tb["in_syn"] = in_adjacency(targets, n, put)
    else:
        tb["weights"] = put(row(net.weights, 0.0))
    return tb


def in_adjacency(targets: np.ndarray, n: int, put):
    """``[N+1, K_in]`` flat synapse indices onto each target, in index
    order (row ``N`` and padding: the sentinel row's first slot, which is
    never plastic).  The table ``[N+1, K]`` is transposed on the host, a
    counting sort in ``O(N K)`` (scipy's CSR to CSC)."""
    import scipy.sparse
    m = targets.size
    by_target = scipy.sparse.csr_matrix(
        (np.arange(m, dtype=np.int32), targets.reshape(-1),
         np.arange(0, m + 1, targets.shape[1])),
        shape=(targets.shape[0], n + 2)).tocsc()
    order = by_target.data.astype(np.int32)
    starts = by_target.indptr[:n + 2].astype(np.int32)
    k_in = max(1, int(np.diff(starts)[:n].max()))
    return _gather_in(put(order), put(starts), n=n, k_in=k_in,
                      dump=n * targets.shape[1])


@functools.partial(jax.jit, static_argnames=("n", "k_in", "dump"))
def _gather_in(order, starts, n: int, k_in: int, dump: int):
    col = jnp.arange(k_in, dtype=jnp.int32)[None, :]
    lo = starts[:n + 1, None]
    hi = jnp.concatenate([starts[1:n + 1], starts[n:n + 1]])[:, None]
    pos = jnp.minimum(lo + col, order.shape[0] - 1)
    return jnp.where(lo + col < hi, order[pos], dump)


def _poisson(c: Consts, basis, key):
    """Step 2's draw: the next key (or one per chip) and the counts."""
    if c.shards == 1:
        key, sub = jax.random.split(key, 2)
        return key, jax.random.poisson(sub, basis, dtype=jnp.int32)
    n_loc = -(-c.n // c.shards)
    lam = jnp.pad(basis, (0, n_loc * c.shards - c.n)).reshape(c.shards, n_loc)
    pairs = [jax.random.split(key[d], 2) for d in range(c.shards)]
    ext = jnp.concatenate([jax.random.poisson(sub, lam[d], dtype=jnp.int32)
                           for d, (_, sub) in enumerate(pairs)])
    return jnp.stack([nxt for nxt, _ in pairs]), ext[:c.n]


def _step(c: Consts, tb: dict, st: dict):
    dt = jnp.dtype(c.dtype)
    n = c.n
    # 1. this step's arrivals, then the slot is free
    slot = st["t"] % c.d_bins
    arr = st["ring"][slot]
    ring = st["ring"].at[slot].set(jnp.zeros_like(arr))
    # 2. the Poisson drive
    key, ext = _poisson(c, tb["basis"], st["key"])
    in_ex = arr[0, :n] + c.w_ext * ext.astype(dt)
    in_in = arr[1, :n]
    # 3. exact integration, refractoriness, threshold
    V, I_ex, I_in, refrac = st["V"], st["I_ex"], st["I_in"], st["refrac"]
    V1 = (c.E_L + (V - c.E_L) * c.P22 + I_ex * c.P21_ex + I_in * c.P21_in
          + tb["i_dc"].astype(dt) * c.P20)
    I_ex = I_ex * c.P11_ex + in_ex
    I_in = I_in * c.P11_in + in_in
    held = refrac > 0
    V1 = jnp.where(held, c.V_reset, V1)
    spk = (V1 >= c.V_th) & ~held
    V1 = jnp.where(spk, c.V_reset, V1)
    refrac = jnp.where(spk, c.ref_steps,
                       jnp.maximum(refrac - 1, 0)).astype(refrac.dtype)
    # 4. delivery of every synapse of every source that fired
    n_spk = jnp.sum(spk, dtype=jnp.int32)
    (ids,) = jnp.nonzero(spk, size=c.budget, fill_value=n)
    w_tab = st["w"] if c.stdp is not None else tb["weights"]
    tg = tb["targets"][ids]
    slots = (st["t"] + tb["dbins"][ids]) % c.d_bins
    ch = (ids >= c.n_exc).astype(jnp.int32)
    lin = (slots * (2 * (n + 1)) + ch[:, None] * (n + 1) + tg).reshape(-1)
    arrivals = w_tab[ids].astype(dt).reshape(-1)
    if c.reverse_delivery:
        lin, arrivals = lin[::-1], arrivals[::-1]
    ring = ring.reshape(-1).at[lin].add(
        arrivals, mode="drop").reshape(ring.shape)
    out = dict(V=V1, I_ex=I_ex, I_in=I_in, refrac=refrac, ring=ring,
               t=st["t"] + 1, key=key,
               over=st["over"] + jnp.maximum(n_spk - c.budget, 0))
    # 5. pair STDP on the E->E synapses
    if c.stdp is not None:
        p = c.stdp
        w = st["w"]
        x_pre, x_post = st["x_pre"], st["x_post"]
        xpost_x = jnp.concatenate([x_post, jnp.zeros((1,), x_post.dtype)])
        xpre_x = jnp.concatenate([x_pre, jnp.zeros((1,), x_pre.dtype)])
        rows = w[ids]
        dep = p.dep * xpost_x[tg]
        rows = jnp.where(tb["plastic"][ids], rows + (-dep), rows)
        w = w.at[ids].set(rows)
        k = w.shape[1]
        syn = tb["in_syn"][ids].reshape(-1)
        flat = w.reshape(-1)
        pot = p.pot * xpre_x[syn // k]
        flat = flat.at[syn].add(
            jnp.where(tb["plastic"].reshape(-1)[syn], pot, 0.0), mode="drop")
        w = flat.reshape(w.shape)
        w = jnp.where(tb["plastic"], jnp.clip(w, 0.0, p.w_max), w)
        s = spk.astype(x_pre.dtype)
        out.update(w=w, x_pre=x_pre * p.decay_pre + s,
                   x_post=x_post * p.decay_post + s)
    counts = jax.ops.segment_sum(spk.astype(jnp.int32), tb["pop_of"],
                                 num_segments=c.n_pops,
                                 indices_are_sorted=True)
    return out, counts


@functools.partial(jax.jit, static_argnames=("n_steps", "c"))
def chunk(tb: dict, state: dict, n_steps: int, c: Consts):
    """``n_steps`` reference steps from ``state``; returns ``(state',
    counts [n_steps, n_pops])``.  ``state['over']`` counts spikes past the
    budget (the reference then did not deliver them)."""
    dt = jnp.dtype(c.dtype)
    st = dict(state)
    for name in ("V", "I_ex", "I_in", "ring", "w", "x_pre", "x_post"):
        if name in st:
            st[name] = st[name].astype(dt)
    st["over"] = jnp.zeros((), jnp.int32)
    return jax.lax.scan(lambda s, _: _step(c, tb, s), st, None,
                        length=n_steps)
