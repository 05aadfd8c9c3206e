"""The one-kernel fused step and the KernelPolicy API.

Tentpole contract: ``kernels/lif_deliver`` fuses the previous step's
delivery with the current step's LIF update in one Pallas launch (loop
rotation), and is *bitwise* equal to the phase-split path — property-tested
against a split oracle on synthetic ELL nets at the edges (zero spikes,
budget saturation/overflow, tile remainders, refractory boundaries) and
pinned end-to-end at scale 0.05 across the fused, instrumented, and
sharded backends, static and plastic.  Policy resolution semantics
(``auto``/``fused``/``split``/``reference``, per-op overrides, eligibility
gates) are pinned alongside.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.simulator import Simulator
from repro.configs.microcircuit import MicrocircuitConfig
from repro.core import delivery as dlv
from repro.core import kernel_policy as kpol
from repro.core import neuron as neuron_mod
from repro.core.connectivity import build_connectome
from repro.core.engine import SimConfig, resolve_sim_config
from repro.core.kernel_policy import KernelPolicy
from repro.core.neuron import NeuronParams, NeuronState, Propagators
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# KernelPolicy resolution
# ---------------------------------------------------------------------------

def _resolve(kernels, strategy="ell", n=1000, d=20, dtype="float32", **kw):
    return kpol.resolve(kernels, strategy=strategy, state_dtype=dtype,
                        n_total=n, d_max_bins=d, **kw)


def test_policy_modes_resolve_off_tpu():
    on_tpu = jax.default_backend() == "tpu"
    auto = _resolve(None)
    assert auto.resolved and auto.mode == "auto"
    assert auto.step == ("fused" if on_tpu else "split")
    assert auto.interpret is (not on_tpu)

    ref = _resolve("reference")
    assert (ref.step, ref.lif, ref.deliver) == ("split", "xla", "xla")

    split = _resolve("split")
    assert split.step == "split"
    # mode "split" selects the per-op Pallas kernels (interpret off-TPU)
    assert split.lif == "pallas" and split.deliver == "pallas"

    fused = _resolve("fused")
    assert fused.step == "fused"


def test_policy_fused_eligibility_gates():
    with pytest.raises(ValueError, match="ell"):
        _resolve("fused", strategy="event")
    with pytest.raises(ValueError, match="float32"):
        _resolve("fused", dtype="bfloat16")
    with pytest.raises(ValueError, match="VMEM|ring"):
        _resolve("fused", n=10_000_000)
    # auto degrades instead of raising
    assert _resolve(None, strategy="event").step == "split"
    assert _resolve(None, n=10_000_000).step == "split"


def test_policy_per_op_overrides_and_idempotency():
    p = _resolve(KernelPolicy(lif="pallas", deliver="xla"))
    assert p.lif == "pallas" and p.deliver == "xla"
    assert kpol.resolve(p, strategy="ell", state_dtype="float32",
                        n_total=1000, d_max_bins=20) == p  # idempotent
    # legacy flags fold in only when the field is unset
    q = _resolve(None, use_lif_kernel=True)
    assert q.lif == "pallas"
    r = _resolve(KernelPolicy(lif="xla"), use_lif_kernel=True)
    assert r.lif == "xla"
    with pytest.raises(ValueError):
        KernelPolicy(mode="warp")
    with pytest.raises(TypeError):
        kpol.as_policy(42)


def test_resolve_sim_config_resolves_policy_once():
    c = build_connectome(scale=0.01, seed=13)
    cfg = resolve_sim_config(SimConfig(strategy="ell", kernels="auto"), c)
    assert cfg.kernels.resolved
    assert resolve_sim_config(cfg, c).kernels == cfg.kernels


# ---------------------------------------------------------------------------
# Property tests: fused kernel vs the phase-split oracle (synthetic nets)
# ---------------------------------------------------------------------------

def _synthetic_net(n, k, d_bins, n_exc, seed=0):
    """Hand-built ELL tables + random state, for exact-N edge geometry."""
    rng = np.random.default_rng(seed)
    targets = rng.integers(0, n, size=(n, k)).astype(np.int32)
    weights = rng.normal(scale=20.0, size=(n, k)).astype(np.float32)
    dbins = rng.integers(1, d_bins, size=(n, k)).astype(np.int32)
    cut = rng.integers(1, k + 1, size=n)
    pad = np.arange(k)[None, :] >= cut[:, None]
    targets[pad] = n
    weights[pad] = 0.0
    dbins[pad] = 1
    tables = dlv.make_event_tables(jnp.asarray(targets),
                                   jnp.asarray(weights), jnp.asarray(dbins))
    ring = jnp.asarray(
        np.abs(rng.normal(size=(d_bins, 2, n + 1))).astype(np.float32))
    prop = Propagators.make(NeuronParams(), 0.1)
    V = jnp.asarray(rng.uniform(-75.0, -49.0, size=n).astype(np.float32))
    I_ex = jnp.asarray(np.abs(rng.normal(scale=50.0, size=n))
                       .astype(np.float32))
    I_in = -jnp.asarray(np.abs(rng.normal(scale=50.0, size=n))
                        .astype(np.float32))
    refrac = jnp.asarray(rng.integers(0, 3, size=n).astype(np.int32))
    neuron = NeuronState(V, I_ex, I_in, refrac)
    ext_ex = jnp.asarray(np.abs(rng.normal(scale=30.0, size=n))
                         .astype(np.float32))
    i_dc = jnp.asarray(rng.normal(scale=5.0, size=n).astype(np.float32))
    return tables, ring, neuron, prop, ext_ex, i_dc


import functools


@functools.partial(jax.jit, static_argnames=("t", "prop", "n_exc", "budget"))
def _split_oracle(neuron, ring, t, spiked_prev, tables, prop, ext_ex, i_dc,
                  n_exc, budget):
    """deliver(t-1) then update(t), exactly as the phase-split loop.

    Jitted like the engine's runners: op-by-op eager execution rounds
    each multiply-add separately, while XLA contracts them to FMAs —
    the bitwise contract holds between the two *compiled* paths."""
    t_prev = t - 1
    ring2, ovf = dlv.deliver_event(ring, tables, spiked_prev,
                                   jnp.asarray(t_prev, jnp.int32), n_exc,
                                   budget)
    D = ring2.shape[0]
    n = spiked_prev.shape[0]
    slot = (t_prev + 1) % D
    in_ex = ring2[slot, 0, :n] + ext_ex
    in_in = ring2[slot, 1, :n]
    neuron2, spiked = neuron_mod.lif_step(neuron, prop, in_ex, in_in, i_dc)
    ring2 = ring2.at[slot].set(0.0)
    return neuron2, ring2, spiked, ovf


CASES = ["zero_spikes", "budget_exact", "budget_overflow", "tile_remainder",
         "refractory_edge", "random_state"]


@pytest.mark.parametrize("case", CASES)
def test_fused_kernel_matches_split_oracle(case):
    n, k, d_bins, n_exc, budget, t = 64, 7, 5, 40, 16, 7
    seed = CASES.index(case) * 11 + 3
    if case == "tile_remainder":
        n, n_exc = 128, 100                  # n_cols = 129 = one lane over
    tables, ring, neuron, prop, ext_ex, i_dc = _synthetic_net(
        n, k, d_bins, n_exc, seed=seed)
    rng = np.random.default_rng(seed + 1)
    if case == "zero_spikes":
        spiked_prev = np.zeros(n, bool)
    elif case == "budget_exact":
        spiked_prev = np.zeros(n, bool)
        spiked_prev[rng.choice(n, size=budget, replace=False)] = True
    elif case == "budget_overflow":
        spiked_prev = np.zeros(n, bool)
        spiked_prev[rng.choice(n, size=budget + 5, replace=False)] = True
    else:
        spiked_prev = rng.random(n) < 0.15
    if case == "refractory_edge":
        # pin the boundaries: refrac exactly 1 (released this step) and a
        # V already above threshold that must not fire while refractory
        refrac = np.asarray(neuron.refrac).copy()
        refrac[: n // 4] = 1
        refrac[n // 4: n // 2] = 0
        V = np.asarray(neuron.V).copy()
        V[: n // 2] = -49.5                   # just under V_th after decay
        neuron = NeuronState(jnp.asarray(V), neuron.I_ex, neuron.I_in,
                             jnp.asarray(refrac))
    spiked_prev = jnp.asarray(spiked_prev)

    got = kops.lif_deliver(neuron, ring, jnp.asarray(t, jnp.int32),
                           spiked_prev, tables, prop, ext_ex, i_dc,
                           n_exc=n_exc, spike_budget=budget, interpret=True)
    g_neuron, g_ring, g_spiked, g_ovf = got
    want = _split_oracle(neuron, ring, t, spiked_prev, tables, prop,
                         ext_ex, i_dc, n_exc, budget)
    w_neuron, w_ring, w_spiked, w_ovf = want

    np.testing.assert_array_equal(np.asarray(g_ring), np.asarray(w_ring))
    for name in NeuronState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(g_neuron, name)),
            np.asarray(getattr(w_neuron, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(g_spiked),
                                  np.asarray(w_spiked))
    assert int(g_ovf) == int(w_ovf)
    if case == "budget_overflow":
        assert int(g_ovf) == 5
    if case == "zero_spikes":
        assert int(g_ovf) == 0


def test_fused_kernel_multi_step_trajectory():
    """Several consecutive fused steps (spikes feeding back through the
    rotation) track the oracle bitwise, including ring wraparound."""
    n, k, d_bins, n_exc, budget = 96, 5, 3, 60, 32
    tables, ring, neuron, prop, ext_ex, i_dc = _synthetic_net(
        n, k, d_bins, n_exc, seed=99)
    rng = np.random.default_rng(7)
    spiked = jnp.asarray(rng.random(n) < 0.1)
    g_neuron = w_neuron = neuron
    g_ring = w_ring = ring
    g_spk = w_spk = spiked
    for t in range(1, 8):                    # wraps d_bins=3 twice
        tt = jnp.asarray(t, jnp.int32)
        g_neuron, g_ring, g_spk, _ = kops.lif_deliver(
            g_neuron, g_ring, tt, g_spk, tables, prop, ext_ex, i_dc,
            n_exc=n_exc, spike_budget=budget, interpret=True)
        w_neuron, w_ring, w_spk, _ = _split_oracle(
            w_neuron, w_ring, t, w_spk, tables, prop, ext_ex, i_dc,
            n_exc, budget)
        np.testing.assert_array_equal(np.asarray(g_ring),
                                      np.asarray(w_ring), err_msg=f"t={t}")
        np.testing.assert_array_equal(np.asarray(g_spk),
                                      np.asarray(w_spk), err_msg=f"t={t}")
    np.testing.assert_array_equal(np.asarray(g_neuron.V),
                                  np.asarray(w_neuron.V))


def test_fused_plastic_kernel_depresses_rows_sharing_a_tile():
    """Spiking rows in one 8-row table tile each keep their depression:
    the kernel moves whole tiles, so a later row's write-back must not
    restore an earlier row's weights."""
    from repro.kernels.lif_deliver import lif_deliver_plastic_pallas
    n, k, d_bins, dep_coef = 40, 128, 5, 0.5
    rng = np.random.default_rng(0)
    targets = rng.integers(0, n, (n + 1, k)).astype(np.int32)
    targets[n] = n
    weights = rng.normal(size=(n + 1, k)).astype(np.float32)
    weights[n] = 0.0
    dbins = rng.integers(1, d_bins, (n + 1, k)).astype(np.int32)
    pmask = np.ones((n + 1, k), np.int32)
    pmask[n] = 0
    x_post = rng.uniform(size=n).astype(np.float32)
    rows = [0, 1, 2, 9]                  # three in tile 0, one in tile 1
    zeros = jnp.zeros(n, jnp.float32)
    out = lif_deliver_plastic_pallas(
        jnp.asarray(rows + [n, n], jnp.int32),
        jnp.asarray([k] * len(rows) + [0, 0], jnp.int32),
        jnp.asarray(targets), jnp.asarray(weights), jnp.asarray(dbins),
        jnp.asarray(pmask),
        jnp.zeros((d_bins, 2, n + 1)), zeros - 65.0, zeros, zeros,
        jnp.zeros(n, jnp.int32), zeros, zeros, zeros, jnp.asarray(x_post),
        zeros, jnp.asarray(3, jnp.int32), d_bins=d_bins, n_cols=n + 1, n=n,
        n_exc=30, prop=Propagators.make(NeuronParams(), 0.1),
        dep_coef=dep_coef, decay_p=0.9, decay_m=0.9, interpret=True)
    want = weights.copy()
    x_pad = np.append(x_post, np.float32(0.0))
    for r in rows:
        want[r] = weights[r] - dep_coef * x_pad[targets[r]]
    np.testing.assert_array_equal(np.asarray(out[1]), want)


# ---------------------------------------------------------------------------
# Row tiles past a row's real length: all three ELL kernels vs XLA
# ---------------------------------------------------------------------------

#: Geometry of the walked-tile cases: N % 8 != 0, so the sentinel row N
#: shares its 8-row table tile with real rows; K spans three row tiles.
WN, WK, WD, WEXC, WBUDGET, BK = 45, 384, 5, 30, 12, 128
#: Rows of chosen real length (the rest draw theirs at random).
EMPTY_ROW, WHOLE_TILES_ROW, FULL_ROW = 3, 5, 6          # 0, 2*BK, K
SHARED_TILE_ROWS = (16, 19)                             # one 8-row tile
SENTINEL_TILE_ROWS = (41, 44)                           # with row N = 45
WALK_CASES = {
    "zero_spikes": [],
    "budget_exact": list(range(0, 2 * WBUDGET, 2)),
    "budget_overflow": list(range(0, 2 * WBUDGET + 6, 2)),
    "empty_row": [EMPTY_ROW, 8, 30],
    "whole_tiles_row": [WHOLE_TILES_ROW, 9],
    "full_row": [FULL_ROW, 33],
    "shared_tile": list(SHARED_TILE_ROWS),
    "sentinel_tile": [2, *SENTINEL_TILE_ROWS],
}
WALK_KERNELS = ("ell_deliver", "lif_deliver_static", "lif_deliver_plastic")


def _walk_net(seed=0):
    """Front-packed ELL tables over three row tiles, with rows of length
    0, exactly two tiles and the full width K, a plastic mask, and
    random neuron state."""
    rng = np.random.default_rng(seed)
    n, k = WN, WK
    lens = rng.integers(1, k, size=n)
    lens[EMPTY_ROW], lens[WHOLE_TILES_ROW], lens[FULL_ROW] = 0, 2 * BK, k
    pad = np.arange(k)[None, :] >= lens[:, None]
    targets = rng.integers(0, n, size=(n, k)).astype(np.int32)
    weights = rng.normal(scale=20.0, size=(n, k)).astype(np.float32)
    dbins = rng.integers(1, WD, size=(n, k)).astype(np.int32)
    pmask = (rng.random((n, k)) < 0.5).astype(np.int32)
    targets[pad], weights[pad], dbins[pad], pmask[pad] = n, 0.0, 1, 0
    tables = dlv.make_event_tables(jnp.asarray(targets),
                                   jnp.asarray(weights), jnp.asarray(dbins))
    np.testing.assert_array_equal(np.asarray(tables.row_len),
                                  np.append(lens, 0))
    pmask = jnp.asarray(np.vstack([pmask, np.zeros((1, k), np.int32)]))
    _, ring, neuron, prop, ext_ex, i_dc = _synthetic_net(n, 2, WD, WEXC,
                                                         seed=seed + 1)
    x_pre = jnp.asarray(rng.uniform(size=n).astype(np.float32))
    x_post = jnp.asarray(rng.uniform(size=n).astype(np.float32))
    return tables, pmask, ring, neuron, prop, ext_ex, i_dc, x_pre, x_post


@functools.partial(jax.jit, static_argnames=("budget", "coef", "dp", "dm"))
def _plastic_oracle(w, tables, pmask, x_pre, x_post, spiked_prev, budget,
                    coef, dp, dm):
    """Depression of the delivered rows' plastic synapses as
    ``stdp_step`` scatters it, and the trace decay + bump."""
    n = spiked_prev.shape[0]
    (ids,) = jnp.nonzero(spiked_prev, size=budget, fill_value=n)
    xp = jnp.append(x_post, 0.0)
    dw = jnp.where(pmask[ids] != 0, -(coef * xp[tables.targets[ids]]), 0.0)
    rows = jnp.where(ids < n, ids, w.shape[0])
    spk = spiked_prev.astype(jnp.float32)
    return (w.at[rows].add(dw, mode="drop"), x_pre * dp + spk,
            x_post * dm + spk)


@pytest.mark.parametrize("case", sorted(WALK_CASES))
@pytest.mark.parametrize("kernel", WALK_KERNELS)
def test_ell_kernels_walk_real_tiles_bitwise(kernel, case):
    """Each ELL kernel walks only the row tiles below the delivered rows'
    real lengths and still equals the XLA path bit for bit: ring,
    neuron state and spikes; for the plastic kernel also the depressed
    weights and the traces."""
    (tables, pmask, ring, neuron, prop, ext_ex, i_dc, x_pre,
     x_post) = _walk_net(seed=WALK_KERNELS.index(kernel))
    spiked_prev = np.zeros(WN, bool)
    spiked_prev[WALK_CASES[case]] = True
    spiked_prev = jnp.asarray(spiked_prev)
    n_spikes = len(WALK_CASES[case])
    t = 7
    tt = jnp.asarray(t, jnp.int32)

    if kernel == "ell_deliver":
        got, ovf = kops.ell_deliver(ring, tables, spiked_prev, tt, WEXC,
                                    WBUDGET, interpret=True)
        upd, want_ovf = dlv.deliver_event(jnp.zeros_like(ring), tables,
                                          spiked_prev, tt, WEXC, WBUDGET)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ring + upd))
        assert int(ovf) == int(want_ovf) == max(0, n_spikes - WBUDGET)
        return

    w_neuron, w_ring, w_spiked, w_ovf = _split_oracle(
        neuron, ring, t, spiked_prev, tables, prop, ext_ex, i_dc, WEXC,
        WBUDGET)
    if kernel == "lif_deliver_static":
        g_neuron, g_ring, g_spiked, g_ovf = kops.lif_deliver(
            neuron, ring, tt, spiked_prev, tables, prop, ext_ex, i_dc,
            n_exc=WEXC, spike_budget=WBUDGET, interpret=True)
    else:
        coef, dp, dm = 0.25, 0.9, 0.8
        (g_neuron, g_ring, g_spiked, g_w, g_xpre, g_xpost, _,
         g_ovf) = kops.lif_deliver_plastic(
            neuron, ring, tt, spiked_prev, tables, tables.weights, pmask,
            x_pre, x_post, prop, ext_ex, i_dc, n_exc=WEXC,
            spike_budget=WBUDGET, dep_coef=coef, decay_p=dp, decay_m=dm,
            interpret=True)
        want = _plastic_oracle(tables.weights, tables, pmask, x_pre, x_post,
                               spiked_prev, WBUDGET, coef, dp, dm)
        for name, g, w in zip(("weights", "x_pre", "x_post"),
                              (g_w, g_xpre, g_xpost), want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=name)
    np.testing.assert_array_equal(np.asarray(g_ring), np.asarray(w_ring))
    for name in NeuronState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(g_neuron, name)),
            np.asarray(getattr(w_neuron, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(g_spiked),
                                  np.asarray(w_spiked))
    assert int(g_ovf) == int(w_ovf) == max(0, n_spikes - WBUDGET)


@pytest.mark.parametrize("kernel", WALK_KERNELS)
def test_ell_kernels_skip_tiles_past_len(kernel):
    """The kernels take the row lengths they are given at their word: a
    full-width row delivered with length ``BK`` scatters (and depresses)
    its first row tile only."""
    from repro.kernels.ell_deliver import ell_deliver_pallas
    from repro.kernels.lif_deliver import (lif_deliver_pallas,
                                           lif_deliver_plastic_pallas)
    (tables, pmask, ring, neuron, prop, ext_ex, i_dc, x_pre,
     x_post) = _walk_net()
    ids = jnp.asarray([FULL_ROW, WN], jnp.int32)
    lens = jnp.asarray([BK, 0], jnp.int32)
    cut = tables._replace(
        targets=tables.targets.at[FULL_ROW, BK:].set(WN),
        weights=tables.weights.at[FULL_ROW, BK:].set(0.0),
        dbins=tables.dbins.at[FULL_ROW, BK:].set(1))
    t = jnp.asarray(2, jnp.int32)
    geo = dict(d_bins=WD, n_cols=WN + 1, n_exc=WEXC, interpret=True)
    state = (neuron.V, neuron.I_ex, neuron.I_in, neuron.refrac, ext_ex,
             i_dc)
    coefs = dict(dep_coef=0.25, decay_p=0.9, decay_m=0.8)
    spk = jnp.zeros(WN, jnp.float32)
    outs = []
    for tb in (tables, cut):
        if kernel == "ell_deliver":
            outs.append(ell_deliver_pallas(ids, lens, tb.targets, tb.weights,
                                           tb.dbins, t, **geo))
        elif kernel == "lif_deliver_static":
            outs.append(lif_deliver_pallas(
                ids, lens, tb.targets, tb.weights, tb.dbins, ring, *state,
                t, n=WN, prop=prop, **geo))
        else:
            out = lif_deliver_plastic_pallas(
                ids, lens, tb.targets, tb.weights, tb.dbins, pmask, ring,
                *state, x_pre, x_post, spk, t, n=WN, prop=prop, **coefs,
                **geo)
            # the untouched tail of the full row keeps its weights
            np.testing.assert_array_equal(
                np.asarray(out[1][FULL_ROW, BK:]),
                np.asarray(tb.weights[FULL_ROW, BK:]))
            outs.append(out[:1] + out[2:])
    got, want = outs
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# End-to-end bitwise pins at scale 0.05, across backends
# ---------------------------------------------------------------------------

SCALE05 = MicrocircuitConfig(n_scaling=0.05, k_scaling=0.05, t_presim=0.0,
                             spike_budget=256, strategy="ell")


@pytest.fixture(scope="module")
def c05():
    return build_connectome(scale=0.05, seed=55)


def test_fused_policy_bitwise_static(c05):
    """Fused one-kernel runs == reference split runs, bitwise: spikes,
    final neuron state, ring, RNG key — and the per-step-dispatch
    backends (instrumented, sharded) agree on the spike trains."""
    t_ms, probes = 20.0, ("spikes",)
    runs = {}
    for mode in ("reference", "fused"):
        sim = Simulator(SCALE05, connectome=c05, kernels=mode,
                        probes=probes)
        runs[mode] = (sim.run(t_ms)["spikes"], sim._state)
        if mode == "fused":
            assert sim.sim_config.kernels.step == "fused"
    want, w_st = runs["reference"]
    got, g_st = runs["fused"]
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    for name in NeuronState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(w_st.neuron, name)),
            np.asarray(getattr(g_st.neuron, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(w_st.ring),
                                  np.asarray(g_st.ring))
    np.testing.assert_array_equal(np.asarray(w_st.key),
                                  np.asarray(g_st.key))

    # instrumented forces step="split" and must agree with fused
    inst = Simulator(SCALE05, connectome=c05, kernels="fused",
                     backend="instrumented", probes=probes)
    assert inst.sim_config.kernels.step == "split"
    np.testing.assert_array_equal(np.asarray(inst.run(t_ms)["spikes"]),
                                  np.asarray(got))

    # sharded (1 device on CPU) agrees on the per-population counts
    shard = Simulator(SCALE05, connectome=c05, kernels="fused",
                      backend="sharded", n_devices=1,
                      probes=("pop_counts",))
    assert shard.sim_config.kernels.step == "split"
    fus = Simulator(SCALE05, connectome=c05, kernels="fused",
                    probes=("pop_counts",))
    np.testing.assert_array_equal(
        np.asarray(shard.run(t_ms)["pop_counts"]),
        np.asarray(fus.run(t_ms)["pop_counts"]))


def test_fused_policy_bitwise_plastic(c05):
    """Plastic fused runs == reference: spikes and final plastic state
    bitwise; mid-run weight probes lag one step (the fused iteration
    carries the previous step's post-STDP weights) — pinned here."""
    t_ms = 20.0
    probes = ("spikes", "mean_plastic_weight")
    runs = {}
    for mode in ("reference", "fused"):
        sim = Simulator(SCALE05, connectome=c05, kernels=mode,
                        probes=probes, plasticity="pair_stdp")
        runs[mode] = (sim.run(t_ms), sim._state)
    (w_res, (w_st, w_ps)) = runs["reference"]
    (g_res, (g_st, g_ps)) = runs["fused"]
    np.testing.assert_array_equal(np.asarray(w_res["spikes"]),
                                  np.asarray(g_res["spikes"]))
    np.testing.assert_array_equal(np.asarray(w_ps.weights),
                                  np.asarray(g_ps.weights))
    np.testing.assert_array_equal(np.asarray(w_ps.x_pre),
                                  np.asarray(g_ps.x_pre))
    np.testing.assert_array_equal(np.asarray(w_ps.x_post),
                                  np.asarray(g_ps.x_post))
    np.testing.assert_array_equal(np.asarray(w_st.ring),
                                  np.asarray(g_st.ring))
    # one-step probe lag: fused step i reports the weights split reported
    # at step i-1 (final states above are still bitwise-identical)
    mw_w = np.asarray(w_res["mean_plastic_weight"])
    mw_g = np.asarray(g_res["mean_plastic_weight"])
    np.testing.assert_array_equal(mw_w[:-1], mw_g[1:])


def test_fused_policy_chunked_and_checkpoint_consistent(c05):
    """The scan epilogue makes chunk boundaries exact: a fused chunked
    run equals one fused run equals the reference, bitwise."""
    t_ms = 10.0
    one = Simulator(SCALE05, connectome=c05, kernels="fused",
                    probes=("spikes",)).run(t_ms)["spikes"]
    chunked = Simulator(SCALE05, connectome=c05, kernels="fused",
                        probes=("spikes",)) \
        .run_chunked(t_ms, chunk_ms=3.0)["spikes"]     # uneven chunks
    np.testing.assert_array_equal(np.asarray(one), np.asarray(chunked))


def test_dense_strategy_rejects_fused_mode(c05):
    with pytest.raises(ValueError, match="ell"):
        Simulator(dataclasses.replace(SCALE05, strategy="dense"),
                  connectome=c05, kernels="fused")
