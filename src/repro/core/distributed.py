"""Sharded microcircuit simulation (NEST's distribution scheme on a mesh).

Ownership follows NEST exactly: each device owns the *state* and the
*incoming synapses* of a contiguous slice of neurons.  One simulation step:

  update      — local exact-integration LIF step (embarrassingly parallel)
  communicate — ``all_gather`` of the local spike bitmasks across the whole
                mesh (NEST: MPI_Allgather of the spike registry)
  deliver     — each device scatters the spikes of *global* sources into its
                *local* ring buffer through its local ELL columns

The connectome is laid out device-major: for every source neuron, its
synapses are grouped by owning device and padded to ``k_loc`` per device, so
the per-device table is just a [N_pad+1, k_loc] column block — an even
``PartitionSpec(None, 'flat')`` sharding of one global [N_pad+1, D*k_loc]
array.  Targets are stored pre-localised (0..n_loc-1, sentinel n_loc).

Executed through ``shard_map`` so the collective is explicit in the HLO —
the dry-run's roofline reads the communicate cost straight off it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.connectivity import Connectome
from repro.core.neuron import NeuronParams, Propagators


class ShardedTables(NamedTuple):
    targets: jnp.ndarray   # [N_pad+1, n_dev * k_loc] int32, localised
    weights: jnp.ndarray   # [N_pad+1, n_dev * k_loc] f32
    dbins: jnp.ndarray     # [N_pad+1, n_dev * k_loc] int32
    k_ext: jnp.ndarray     # [N_pad]
    i_dc: jnp.ndarray      # [N_pad]


def localize_ell(c: Connectome, n_dev: int,
                 k_loc: Optional[int] = None) -> Tuple[ShardedTables, dict]:
    """Regroup the ELL table by target-owning device (host-side numpy).

    This is the shard transform of the ELL-layout delivery strategies:
    the sharded backend reaches it through
    ``repro.core.delivery.DeliveryStrategy.localize`` (``event`` and
    ``ell`` register it; strategies without a distributed layout raise).
    The tables come back as host arrays, so the caller can place each
    device's columns on that device (:func:`table_specs`) without first
    holding the whole table on one of them.
    """
    n = c.n_total
    n_pad = -(-n // n_dev) * n_dev
    n_loc = n_pad // n_dev

    # owning device of every ELL entry; padding entries get none (n_dev)
    dev = np.where(c.targets < n, c.targets // n_loc, n_dev)
    k_max = max(int(np.count_nonzero(dev == d, axis=1).max())
                for d in range(n_dev)) if n else 1
    if k_loc is None:
        k_loc = max(k_max, 1)
    elif k_loc < k_max:
        raise ValueError(f"k_loc={k_loc} < max {k_max}")

    T = np.full((n_pad + 1, n_dev, k_loc), n_loc, dtype=np.int32)
    W = np.zeros((n_pad + 1, n_dev, k_loc), dtype=np.float32)
    D = np.ones((n_pad + 1, n_dev, k_loc), dtype=np.int32)
    for d in range(n_dev):
        # a source's synapses onto device d keep their ELL (k) order, so
        # every ring entry sums its arrivals in the single-device order
        mine = dev == d
        col = np.cumsum(mine, axis=1, dtype=np.int32) - 1
        src, k = np.nonzero(mine)
        T[src, d, col[src, k]] = c.targets[src, k] - d * n_loc
        W[src, d, col[src, k]] = c.weights[src, k]
        D[src, d, col[src, k]] = c.dbins[src, k]
        del mine, col, src, k

    k_ext = np.zeros(n_pad, np.float32)
    k_ext[:n] = c.k_ext
    i_dc = np.zeros(n_pad, np.float32)
    i_dc[:n] = c.i_dc

    tables = ShardedTables(
        targets=T.reshape(n_pad + 1, n_dev * k_loc),
        weights=W.reshape(n_pad + 1, n_dev * k_loc),
        dbins=D.reshape(n_pad + 1, n_dev * k_loc),
        k_ext=k_ext,
        i_dc=i_dc,
    )
    meta = {"n_pad": n_pad, "n_loc": n_loc, "k_loc": k_loc, "n_dev": n_dev}
    return tables, meta


def table_specs(axes) -> ShardedTables:
    """How the sharded step splits its tables over the mesh ``axes``:
    the ELL columns and the per-neuron drive by target-owning device."""
    from jax.sharding import PartitionSpec as P
    return ShardedTables(
        targets=P(None, axes), weights=P(None, axes), dbins=P(None, axes),
        k_ext=P(axes), i_dc=P(axes))


def abstract_sharded_tables(c_meta: dict, n_dev: int, k_loc: int,
                            n_pad: int) -> ShardedTables:
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    sd = jax.ShapeDtypeStruct
    cols = n_dev * k_loc
    return ShardedTables(
        targets=sd((n_pad + 1, cols), jnp.int32),
        weights=sd((n_pad + 1, cols), jnp.float32),
        dbins=sd((n_pad + 1, cols), jnp.int32),
        k_ext=sd((n_pad,), jnp.float32),
        i_dc=sd((n_pad,), jnp.float32),
    )


class ShardedSimState(NamedTuple):
    V: jnp.ndarray         # [N_pad]
    I_ex: jnp.ndarray
    I_in: jnp.ndarray
    refrac: jnp.ndarray    # int32
    ring: jnp.ndarray      # [D_ring, 2, N_pad + n_dev]  (+1 dump col/device)
    t: jnp.ndarray
    key: jnp.ndarray       # one key per device: [n_dev, 2] uint32
    overflow: jnp.ndarray  # [n_dev] int32


def abstract_state(n_pad: int, n_dev: int, d_ring: int) -> ShardedSimState:
    sd = jax.ShapeDtypeStruct
    return ShardedSimState(
        V=sd((n_pad,), jnp.float32),
        I_ex=sd((n_pad,), jnp.float32),
        I_in=sd((n_pad,), jnp.float32),
        refrac=sd((n_pad,), jnp.int32),
        ring=sd((d_ring, 2, n_pad + n_dev), jnp.float32),
        t=sd((), jnp.int32),
        key=sd((n_dev, 2), jnp.uint32),
        overflow=sd((n_dev,), jnp.int32),
    )


def make_sharded_step(mesh, meta: dict, prop: Propagators, *,
                      n_exc: int, w_ext: float, dt: float,
                      spike_budget: int, n_steps: int,
                      bg_rate: Optional[float] = None, drive=None,
                      pop_of=None, n_pops: int = 8, stream_probes=()):
    """Returns a shard_map'd ``sim_chunk(...) -> (state, counts, carries)``.

    The external drive comes from exactly one of two sources:

    * ``drive`` — a *separable* compiled stimulus timeline
      (``repro.core.stimulus.Drive``): the per-neuron basis arrays arrive
      as an extra sharded input, so ``sim_chunk(state, tables, carries,
      (spike_bases [Ks, N_pad], cur_bases [Kc, N_pad]))`` — each device
      draws/applies its local slice while the scalar time gates are
      replicated.  This is the path the api backends use.
    * ``bg_rate`` — the legacy hardcoded Poisson background read off
      ``tables.k_ext`` (no extra input: ``sim_chunk(state, tables,
      carries)``).  Kept for the dry-run (whose tables are abstract) and
      as the pre-registry bitwise reference.

    ``counts``: [n_steps, n_dev] spikes per device per step (cheap record).
    With ``pop_of`` (a [n_pad] global population index, sentinel ``n_pops``
    for padding neurons), counts become [n_steps, n_pops] per-population
    spike counts instead — reduced from the all-gathered spike registry, so
    identical on every device (replicated output).

    ``stream_probes`` (``repro.api.probes.StreamProbe``) accumulate inside
    the scan from the same all-gathered registry: each ``update(carry,
    spiked_global)`` sees the full (padded) global spike vector, which is
    replicated across devices, so the carries ride as replicated in/outputs
    — NEST-style streaming statistics without any extra collective.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if (bg_rate is None) == (drive is None):
        raise ValueError("pass exactly one of bg_rate= (legacy inline "
                         "Poisson) or drive= (compiled stimulus timeline)")
    axes = tuple(mesh.axis_names)
    n_loc = meta["n_loc"]
    if drive is not None:
        spike_plan, cur_plan = drive.plan()   # raises if not separable
        spike_gates = tuple(g for _, g in spike_plan)
        cur_gates = tuple(g for _, g in cur_plan)
    else:
        lam_scale = bg_rate * dt * 1e-3

    state_spec = ShardedSimState(
        V=P(axes), I_ex=P(axes), I_in=P(axes), refrac=P(axes),
        ring=P(None, None, axes), t=P(), key=P(axes), overflow=P(axes))
    tab_spec = table_specs(axes)
    stream_probes = tuple(stream_probes)
    carries_spec = jax.tree.map(
        lambda _: P(), tuple(p.init() for p in stream_probes))

    def step(carry, _, tab: ShardedTables, bases=None):
        st, scs = carry
        D_ring = st.ring.shape[0]
        slot = st.t % D_ring
        arrivals = jax.lax.dynamic_index_in_dim(st.ring, slot, 0, False)
        in_ex, in_in = arrivals[0, :n_loc], arrivals[1, :n_loc]

        # -- update (local): external drive, then exact integration --
        i_dc = tab.i_dc
        if drive is None:
            key, sub = jax.random.split(st.key[0])
            ext = jax.random.poisson(sub, tab.k_ext * lam_scale,
                                     dtype=jnp.int32)
            in_ex = in_ex + w_ext * ext.astype(in_ex.dtype)
        else:
            spike_bases, cur_bases = bases
            keys = jax.random.split(st.key[0], len(spike_gates) + 1)
            key = keys[0]
            ext = None
            for j, gate in enumerate(spike_gates):
                lam = spike_bases[j]
                if gate is not None:
                    lam = lam * gate(st.t)
                cnt = jax.random.poisson(keys[1 + j], lam, dtype=jnp.int32)
                ext = cnt if ext is None else ext + cnt
            if ext is not None:
                in_ex = in_ex + w_ext * ext.astype(in_ex.dtype)
            for j, gate in enumerate(cur_gates):
                amp = cur_bases[j]
                if gate is not None:
                    amp = amp * gate(st.t)
                i_dc = i_dc + amp
        V = (prop.E_L + (st.V - prop.E_L) * prop.P22
             + st.I_ex * prop.P21_ex + st.I_in * prop.P21_in
             + i_dc * prop.P20)
        I_ex = st.I_ex * prop.P11_ex + in_ex
        I_in = st.I_in * prop.P11_in + in_in
        refr = st.refrac > 0
        V = jnp.where(refr, prop.V_reset, V)
        spiked = (V >= prop.V_th) & ~refr
        V = jnp.where(spiked, prop.V_reset, V)
        refrac = jnp.where(spiked, prop.ref_steps,
                           jnp.maximum(st.refrac - 1, 0)).astype(jnp.int32)
        ring = jax.lax.dynamic_update_index_in_dim(
            st.ring, jnp.zeros_like(arrivals), slot, 0)

        # -- communicate: the spike registry all-gather (NEST's Allgather) --
        spiked_global = jax.lax.all_gather(spiked, axes, tiled=True)

        # -- deliver (into local ring via local ELL columns) --
        n_glob = spiked_global.shape[0]
        (ids,) = jnp.nonzero(spiked_global, size=spike_budget,
                             fill_value=n_glob)
        tg = tab.targets[ids]                      # [S, k_loc] local ids
        w = tab.weights[ids]
        db = tab.dbins[ids]
        ch = (ids >= n_exc).astype(jnp.int32)[:, None]
        slot2 = (st.t + db) % D_ring
        n_cols = n_loc + 1
        lin = slot2 * (2 * n_cols) + ch * n_cols + tg
        ring = ring.reshape(-1).at[lin.reshape(-1)].add(
            w.reshape(-1), mode="drop").reshape(D_ring, 2, n_cols)

        n_spk = jnp.sum(spiked_global, dtype=jnp.int32)
        overflow = st.overflow + jnp.maximum(n_spk - spike_budget, 0)
        new = ShardedSimState(V, I_ex, I_in, refrac, ring, st.t + 1,
                              key[None], overflow)
        scs = tuple(p.update(sc, spiked_global)
                    for p, sc in zip(stream_probes, scs))
        if pop_of is not None:
            # every device holds the full registry -> identical reduction
            counts = jax.ops.segment_sum(
                spiked_global.astype(jnp.int32), pop_of,
                num_segments=n_pops + 1, indices_are_sorted=True)[:n_pops]
        else:
            counts = jnp.sum(spiked, dtype=jnp.int32)[None]
        return (new, scs), counts

    counts_spec = P(None, None) if pop_of is not None else P(None, axes)

    if drive is not None:
        bases_spec = (P(None, axes), P(None, axes))

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(state_spec, tab_spec, carries_spec, bases_spec),
            out_specs=(state_spec, counts_spec, carries_spec),
            check_vma=False)
        def sim_chunk(state, tables, carries, bases):
            (state, carries), counts = jax.lax.scan(
                functools.partial(step, tab=tables, bases=bases),
                (state, carries), None, length=n_steps)
            return state, counts, carries

        return sim_chunk

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(state_spec, tab_spec, carries_spec),
        out_specs=(state_spec, counts_spec, carries_spec),
        check_vma=False)
    def sim_chunk(state, tables, carries):
        (state, carries), counts = jax.lax.scan(
            functools.partial(step, tab=tables), (state, carries), None,
            length=n_steps)
        return state, counts, carries

    return sim_chunk


# ---------------------------------------------------------------------------
# Dense (delay-binned matmul) strategy, pjit-sharded
# ---------------------------------------------------------------------------

class DenseSimState(NamedTuple):
    V: jnp.ndarray         # [N]
    I_ex: jnp.ndarray
    I_in: jnp.ndarray
    refrac: jnp.ndarray
    ring: jnp.ndarray      # [D_ring, 2, N]
    t: jnp.ndarray
    key: jnp.ndarray
    overflow: jnp.ndarray


def abstract_dense(n: int, d_ring: int, dtype=jnp.bfloat16):
    sd = jax.ShapeDtypeStruct
    state = DenseSimState(
        V=sd((n,), jnp.float32), I_ex=sd((n,), jnp.float32),
        I_in=sd((n,), jnp.float32), refrac=sd((n,), jnp.int32),
        ring=sd((d_ring, 2, n), jnp.float32), t=sd((), jnp.int32),
        key=sd((2,), jnp.uint32), overflow=sd((), jnp.int32))
    W = sd((d_ring, n, n), dtype)
    aux = {"k_ext": sd((n,), jnp.float32), "i_dc": sd((n,), jnp.float32)}
    return state, W, aux


def dense_shardings(mesh, state: DenseSimState, W, aux):
    """W 2D-sharded (pre over data axes, post over 'model'); the [N]-sized
    state is replicated (300 KB)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = mesh.axis_names
    pre = tuple(a for a in axes if a != "model") or (None,)
    rep = NamedSharding(mesh, P())
    w_sh = NamedSharding(mesh, P(None, pre, "model"))
    st = jax.tree.map(lambda _: rep, state)
    ax = jax.tree.map(lambda _: rep, aux)
    return st, w_sh, ax


def make_dense_step(mesh, prop: Propagators, *, n: int, n_exc: int,
                    w_ext: float, bg_rate: float, dt: float, n_steps: int):
    """pjit-ready ``sim_chunk(state, W, aux) -> (state, counts[n_steps])``."""
    # single-signed-channel delivery requires equal synaptic time constants
    assert prop.P11_ex == prop.P11_in and prop.P21_ex == prop.P21_in
    lam_scale = bg_rate * dt * 1e-3

    def step(st: DenseSimState, _, W, aux):
        D_ring = st.ring.shape[0]
        slot = st.t % D_ring
        arrivals = jax.lax.dynamic_index_in_dim(st.ring, slot, 0, False)
        in_ex, in_in = arrivals[0], arrivals[1]
        key, sub = jax.random.split(st.key)
        ext = jax.random.poisson(sub, aux["k_ext"] * lam_scale,
                                 dtype=jnp.int32)
        in_ex = in_ex + w_ext * ext.astype(in_ex.dtype)
        V = (prop.E_L + (st.V - prop.E_L) * prop.P22
             + st.I_ex * prop.P21_ex + st.I_in * prop.P21_in
             + aux["i_dc"] * prop.P20)
        I_ex = st.I_ex * prop.P11_ex + in_ex
        I_in = st.I_in * prop.P11_in + in_in
        refr = st.refrac > 0
        V = jnp.where(refr, prop.V_reset, V)
        spiked = (V >= prop.V_th) & ~refr
        V = jnp.where(spiked, prop.V_reset, V)
        refrac = jnp.where(spiked, prop.ref_steps,
                           jnp.maximum(st.refrac - 1, 0)).astype(jnp.int32)
        ring = jax.lax.dynamic_update_index_in_dim(
            st.ring, jnp.zeros_like(arrivals), slot, 0)

        # Equal tau_syn_ex/in (this model) => exc/inh currents obey the same
        # propagator, so delivery runs on ONE signed channel over the FULL
        # weight matrix.  The split variant sliced W at n_exc — a shard-
        # misaligned boundary that made GSPMD re-partition W with
        # collective-permutes every step (see EXPERIMENTS.md §Perf).
        s = spiked.astype(W.dtype)
        upd = jnp.einsum("p,dpn->dn", s, W,
                         preferred_element_type=jnp.float32)
        upd = jnp.stack([upd, jnp.zeros_like(upd)], axis=1)
        ring = ring + jnp.roll(upd, shift=st.t, axis=0).astype(ring.dtype)

        new = DenseSimState(V, I_ex, I_in, refrac, ring, st.t + 1, key,
                            st.overflow)
        return new, jnp.sum(spiked, dtype=jnp.int32)

    def sim_chunk(state, W, aux):
        return jax.lax.scan(
            functools.partial(step, W=W, aux=aux), state, None,
            length=n_steps)

    return sim_chunk
