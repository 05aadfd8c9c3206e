"""Delivery-strategy registry, equivalence, budgets, overflow, guards.

The tentpole contract: ``event`` / ``dense`` / ``ell`` are registered
:class:`~repro.core.delivery.DeliveryStrategy` implementations behind one
protocol, all producing the same ring-buffer arrivals (the ``ell`` Pallas
kernel bitwise-matches the event gather/scatter), with dropped spikes
surfaced instead of silent and O(N^2) allocations guarded.
"""
import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DeliveryOverflowError, Simulator
from repro.configs.microcircuit import MicrocircuitConfig, SMOKE
from repro.core import delivery as dlv
from repro.core.connectivity import (build_connectome, dense_bytes_estimate,
                                     dense_delay_binned)
from repro.core.engine import SimConfig, resolve_sim_config
from repro.core.kernel_policy import KernelPolicy

CFG = dataclasses.replace(SMOKE, t_presim=0.0)


# ---------------------------------------------------------------------------
# Registry protocol
# ---------------------------------------------------------------------------

def test_registry_has_the_three_strategies():
    assert {"event", "dense", "ell"} <= set(dlv.available_strategies())
    for name in ("event", "dense", "ell"):
        s = dlv.get_strategy(name)
        assert isinstance(s, dlv.DeliveryStrategy) and s.name == name


def test_unknown_strategy_raises_with_available_names():
    with pytest.raises(ValueError, match="ell"):
        dlv.get_strategy("nope")
    with pytest.raises(ValueError, match="unknown delivery strategy"):
        resolve_sim_config(SimConfig(strategy="nope"), None)


def test_register_custom_strategy_reaches_the_engine(small_connectome):
    calls = []

    @dlv.register
    class _Probe(dlv.EventDelivery):
        name = "probe_event"

        def deliver(self, ring, tables, spiked, t, n_exc, cfg):
            calls.append(1)
            return super().deliver(ring, tables, spiked, t, n_exc, cfg)

    try:
        sim = Simulator(CFG, connectome=small_connectome,
                        strategy="probe_event")
        res = sim.run(2.0)
        assert calls, "custom strategy's deliver was never dispatched"
        assert res["pop_counts"].shape[0] == res.n_steps
    finally:
        del dlv.REGISTRY["probe_event"]


def test_register_collision_raises():
    with pytest.raises(ValueError, match="already registered"):
        @dlv.register
        class _Clash(dlv.EventDelivery):
            name = "event"
    assert isinstance(dlv.get_strategy("event"), dlv.EventDelivery)


def test_dense_layout_vs_kernel_flag_mismatch(tiny_c):
    """A custom matvec (the gated kernel) on split-GEMM tables must fail
    loudly, not silently fall back to the plain GEMM."""
    c = tiny_c
    gemm_tables = dlv.get_strategy("dense").prepare(
        c, SimConfig(strategy="dense"))
    ring = jnp.zeros((c.d_max_bins, 2, c.n_total + 1), jnp.float32)
    kcfg = resolve_sim_config(SimConfig(
        strategy="dense", kernels=KernelPolicy(deliver="pallas")), c)
    with pytest.raises(ValueError, match="KernelPolicy"):
        dlv.get_strategy("dense").deliver(
            ring, gemm_tables, jnp.zeros(c.n_total, bool),
            jnp.asarray(0), c.n_exc, kcfg)


def test_sharding_support_flags():
    assert dlv.get_strategy("event").supports_sharding
    assert dlv.get_strategy("ell").supports_sharding
    assert not dlv.get_strategy("dense").supports_sharding
    with pytest.raises(NotImplementedError):
        dlv.get_strategy("dense").localize(None, 2)


# ---------------------------------------------------------------------------
# Single-step equivalence of all three strategies (+ the Pallas kernels)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_c():
    return build_connectome(scale=0.01, seed=13)


def _one_step_rings(c, budget=64, seed=0):
    rng = np.random.default_rng(seed)
    spiked = jnp.asarray(rng.random(c.n_total) < 40 / c.n_total)
    ring = jnp.zeros((c.d_max_bins, 2, c.n_total + 1), jnp.float32)
    t = jnp.asarray(5, jnp.int32)
    cfg = resolve_sim_config(SimConfig(spike_budget=budget), c)
    out = {}
    for name in ("event", "dense", "ell"):
        strat = dlv.get_strategy(name)
        scfg = dataclasses.replace(cfg, strategy=name)
        tables = strat.prepare(c, scfg)
        r, ovf = strat.deliver(ring, tables, spiked, t, c.n_exc, scfg)
        out[name] = np.asarray(r)
    # the kernel path of ell, forced off-TPU via the kernel policy
    kcfg = resolve_sim_config(SimConfig(
        spike_budget=budget, strategy="ell",
        kernels=KernelPolicy(deliver="pallas")), c)
    strat = dlv.get_strategy("ell")
    r, _ = strat.deliver(ring, strat.prepare(c, kcfg), spiked, t,
                         c.n_exc, kcfg)
    out["ell_kernel"] = np.asarray(r)
    return out


def test_one_step_ring_equivalence(tiny_c):
    rings = _one_step_rings(tiny_c)
    np.testing.assert_array_equal(rings["event"], rings["ell"])
    np.testing.assert_array_equal(rings["event"], rings["ell_kernel"])
    np.testing.assert_allclose(rings["event"], rings["dense"],
                               rtol=1e-6, atol=1e-4)


def test_ell_kernel_matches_ref_oracle(tiny_c):
    from repro.kernels import ops as kops
    from repro.kernels.ref import ell_deliver_ref
    c = tiny_c
    cfg = SimConfig(strategy="ell")
    tables = dlv.get_strategy("ell").prepare(c, cfg)
    rng = np.random.default_rng(3)
    ring = jnp.asarray(rng.normal(size=(c.d_max_bins, 2, c.n_total + 1))
                       .astype(np.float32))
    for _, t in ((0, 0), (1, 17), (2, 45)):
        spiked = jnp.asarray(rng.random(c.n_total) < 30 / c.n_total)
        tt = jnp.asarray(t, jnp.int32)
        got, ovf_g = kops.ell_deliver(ring, tables, spiked, tt, c.n_exc, 64)
        want, ovf_w = ell_deliver_ref(ring, tables, spiked, tt, c.n_exc, 64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-5)
        assert int(ovf_g) == int(ovf_w)


def _synthetic_ell(n, k, d_bins, n_exc, seed=0):
    """Hand-built ELL tables (no microcircuit), for exact-N edge geometry."""
    rng = np.random.default_rng(seed)
    targets = rng.integers(0, n, size=(n, k)).astype(np.int32)
    weights = rng.normal(size=(n, k)).astype(np.float32)
    dbins = rng.integers(1, d_bins, size=(n, k)).astype(np.int32)
    # ragged rows: sentinel-pad a random suffix of each row
    cut = rng.integers(1, k + 1, size=n)
    pad = np.arange(k)[None, :] >= cut[:, None]
    targets[pad] = n
    weights[pad] = 0.0
    dbins[pad] = 1
    tables = dlv.make_event_tables(jnp.asarray(targets),
                                   jnp.asarray(weights), jnp.asarray(dbins))
    ring = jnp.asarray(rng.normal(size=(d_bins, 2, n + 1)).astype(np.float32))
    return tables, ring


@pytest.mark.parametrize("case", ["zero_spikes", "budget_exact",
                                  "budget_overflow", "tile_remainder"])
def test_ell_kernel_interpret_edge_cases(case):
    """The interpret-mode ell kernel vs the event oracle at the edges:
    a spike-free step, a budget-saturating step (exactly full and
    overflowing), and a single-neuron tile remainder (N+1 = one column
    past the 128-lane tile, K far below one tile)."""
    from repro.kernels import ops as kops
    n, k, d_bins, n_exc, budget = 64, 7, 5, 40, 16
    if case == "tile_remainder":
        n, n_exc = 128, 100                  # n_cols = 129 = 128 + 1
    seed = {"zero_spikes": 11, "budget_exact": 22,
            "budget_overflow": 33, "tile_remainder": 44}[case]
    tables, ring = _synthetic_ell(n, k, d_bins, n_exc, seed=seed)
    rng = np.random.default_rng(1)
    if case == "zero_spikes":
        spiked = np.zeros(n, bool)
    elif case == "budget_exact":
        spiked = np.zeros(n, bool)
        spiked[rng.choice(n, size=budget, replace=False)] = True
    elif case == "budget_overflow":
        spiked = np.zeros(n, bool)
        spiked[rng.choice(n, size=budget + 5, replace=False)] = True
    else:
        spiked = rng.random(n) < 0.1
    spiked = jnp.asarray(spiked)
    t = jnp.asarray(3, jnp.int32)

    want, ovf_w = dlv.deliver_event(ring, tables, spiked, t, n_exc, budget)
    got, ovf_g = kops.ell_deliver(ring, tables, spiked, t, n_exc, budget,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)
    assert int(ovf_g) == int(ovf_w)
    if case == "zero_spikes":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ring))
        assert int(ovf_g) == 0
    elif case == "budget_exact":
        assert int(ovf_g) == 0
    elif case == "budget_overflow":
        assert int(ovf_g) == 5


def test_ell_strategy_zero_spike_step_full_cycle(tiny_c):
    """A spike-free step through the registered strategy's kernel path
    leaves the ring bit-identical (the sentinel rows scatter weight 0
    into the dump column only)."""
    c = tiny_c
    cfg = resolve_sim_config(SimConfig(
        strategy="ell", spike_budget=32,
        kernels=KernelPolicy(deliver="pallas")), c)
    strat = dlv.get_strategy("ell")
    tables = strat.prepare(c, cfg)
    ring = jnp.zeros((c.d_max_bins, 2, c.n_total + 1), jnp.float32)
    r2, ovf = strat.deliver(ring, tables, jnp.zeros(c.n_total, bool),
                            jnp.asarray(0, jnp.int32), c.n_exc, cfg)
    assert int(ovf) == 0
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(ring))


def test_ell_table_rows_are_lane_padded(tiny_c):
    tables = dlv.get_strategy("ell").prepare(tiny_c, SimConfig())
    assert tables.targets.shape[1] % dlv.EllDelivery.block_k == 0
    n = tiny_c.n_total
    rows = tables.targets.shape[0]
    assert rows % dlv.EllDelivery.row_tile == 0
    assert n + 1 <= rows < n + 1 + dlv.EllDelivery.row_tile
    # row N and every padding row after it are sentinel rows
    np.testing.assert_array_equal(np.asarray(tables.targets[n:]), n)
    np.testing.assert_array_equal(np.asarray(tables.weights[n:]), 0.0)


def test_row_len_is_the_out_degree_and_survives_live_tables(tiny_c):
    """``row_len`` is each row's real length: the connectome's out-degree,
    0 on the sentinel row N and every pad row after it; swapping in live
    weights keeps it."""
    c = tiny_c
    for name in ("ell", "event"):
        strat = dlv.get_strategy(name)
        tables = strat.prepare(c, SimConfig(strategy=name))
        row_len = np.asarray(tables.row_len)
        assert row_len.dtype == np.int32
        assert row_len.shape == (tables.targets.shape[0],)
        np.testing.assert_array_equal(row_len[:c.n_total], c.out_degree)
        np.testing.assert_array_equal(row_len[c.n_total:], 0)
        live = strat.live_tables(
            tables, jnp.zeros((c.n_total + 1, c.targets.shape[1])))
        assert live.row_len is tables.row_len


def test_row_lengths_reach_the_last_real_entry():
    """A row's length runs to its last real entry, so a row with a
    sentinel hole is walked in full; an all-sentinel row has length 0."""
    n = 5
    targets = np.full((4, 300), n, np.int32)
    targets[0, :129] = 1             # one entry into the second row tile
    targets[1, [3, 256]] = 2         # a hole: real entries 3 and 256
    targets[2, :] = 0                # the full width
    np.testing.assert_array_equal(np.asarray(dlv.row_lengths(targets, n)),
                                  [129, 257, 300, 0])


def test_walked_tiles_counts_the_live_grid_steps():
    """``walked_tiles`` is ceil(len / block_k) summed over the delivered
    rows: the (spike, tile) grid steps whose tile holds a real synapse."""
    from repro.kernels.ell_deliver import walked_tiles
    bk, n_tiles = 128, 3
    lens = np.array([0, 1, 127, 128, 129, 256, 384, 0], np.int32)
    live = sum(kb * bk < ln for ln in lens for kb in range(n_tiles))
    got = walked_tiles(jnp.asarray(lens), bk)
    assert got.dtype == jnp.int32
    assert int(got) == live == 0 + 1 + 1 + 1 + 2 + 2 + 3 + 0
    assert int(walked_tiles(jnp.zeros(16, jnp.int32), bk)) == 0


# ---------------------------------------------------------------------------
# Full-run acceptance: scale=0.05 microcircuit, all three strategies
# ---------------------------------------------------------------------------

def test_three_strategies_equivalent_at_scale_005():
    """The acceptance check: Simulator(config).run produces equivalent
    pop-counts under event / dense / ell on a scale=0.05 microcircuit."""
    cfg = MicrocircuitConfig(scale=0.05, seed=55, t_presim=0.0)
    recs = {}
    c = None
    for strat in ("event", "ell", "dense"):
        sim = Simulator(dataclasses.replace(cfg, strategy=strat),
                        connectome=c)
        c = sim.connectome
        recs[strat] = sim.run(10.0)["pop_counts"]
    np.testing.assert_array_equal(recs["event"], recs["ell"])
    # dense accumulates in a different order: dtype-tolerance equivalence
    assert (recs["event"] == recs["dense"]).mean() > 0.99
    np.testing.assert_allclose(recs["event"].sum(axis=0),
                               recs["dense"].sum(axis=0), rtol=0.02,
                               atol=3.0)


def test_ell_full_scale_builds_without_dense_materialization():
    """strategy='ell' at scale=1.0 must never touch an O(N^2) array: the
    footprint estimates stay O(N*K) while dense is guarded out."""
    c_full_meta = build_connectome(scale=0.05, seed=1)  # stand-in geometry
    n_full = 77169
    est_dense = dense_bytes_estimate(
        dataclasses.replace(c_full_meta, n_total=n_full))
    assert est_dense > 1e12          # ~1.1 TB: far past device HBM
    with pytest.raises(ValueError, match="ell"):
        dense_delay_binned(dataclasses.replace(c_full_meta, n_total=n_full))
    # the ELL footprint at full scale fits in device memory
    est_ell = dlv.get_strategy("ell").memory_bytes(
        dataclasses.replace(c_full_meta, n_total=n_full))
    assert est_ell < 1e11


@pytest.mark.skipif(os.environ.get("REPRO_FULL_SCALE") != "1",
                    reason="full-scale build is ~10 GB host RAM / minutes; "
                           "set REPRO_FULL_SCALE=1 to run")
def test_ell_full_scale_build_and_step():
    c = build_connectome(scale=1.0, seed=55)
    assert c.n_total == 77169
    cfg = resolve_sim_config(SimConfig(strategy="ell"), c)
    strat = dlv.get_strategy("ell")
    tables = strat.prepare(c, cfg)
    ring = jnp.zeros((c.d_max_bins, 2, c.n_total + 1), jnp.float32)
    spiked = jnp.zeros((c.n_total,), bool).at[:31].set(True)
    ring2, ovf = strat.deliver(ring, tables, spiked,
                               jnp.asarray(0, jnp.int32), c.n_exc, cfg)
    assert int(ovf) == 0 and float(jnp.abs(ring2).sum()) > 0


# ---------------------------------------------------------------------------
# Auto spike budget + overflow surfacing
# ---------------------------------------------------------------------------

def test_auto_spike_budget_is_rate_derived(small_connectome):
    c = small_connectome
    budget = dlv.auto_spike_budget(c, dt=0.1)
    from repro.core.params import FULL_MEAN_RATES
    expected = float((np.asarray(c.pop_sizes)
                      * FULL_MEAN_RATES).sum()) * 0.1 * 1e-3
    assert budget % 128 == 0
    assert budget >= max(128, expected)          # headroom over the mean
    cfg = resolve_sim_config(SimConfig(), c)
    assert cfg.spike_budget == budget
    # explicit budgets pass through untouched
    assert resolve_sim_config(SimConfig(spike_budget=7), c).spike_budget == 7


def test_unresolved_budget_fails_loudly(small_connectome):
    c = small_connectome
    cfg = SimConfig(strategy="event")            # spike_budget=None
    strat = dlv.get_strategy("event")
    tables = strat.prepare(c, cfg)
    ring = jnp.zeros((c.d_max_bins, 2, c.n_total + 1), jnp.float32)
    with pytest.raises(ValueError, match="resolve_sim_config"):
        strat.deliver(ring, tables, jnp.zeros(c.n_total, bool),
                      jnp.asarray(0), c.n_exc, cfg)


def test_overflow_is_surfaced_as_warning(small_connectome):
    sim = Simulator(CFG, connectome=small_connectome, spike_budget=1)
    with pytest.warns(UserWarning, match="dropped"):
        res = sim.run(20.0)
    assert res.overflow > 0


def test_strict_delivery_raises(small_connectome):
    sim = Simulator(CFG, connectome=small_connectome, spike_budget=1,
                    strict_delivery=True)
    with pytest.raises(DeliveryOverflowError, match="spike_budget"):
        sim.run(20.0)


def test_strict_run_chunked_preserves_partial(small_connectome, monkeypatch):
    """A strict abort mid-run_chunked carries the completed chunks.

    The overflow counter is stubbed to stay clean for the first two chunks
    so the abort deterministically lands mid-run."""
    sim = Simulator(CFG, connectome=small_connectome, spike_budget=1,
                    strict_delivery=True)
    real_overflow = sim.backend.overflow
    checks = []

    def overflow_after_two_chunks(state):
        checks.append(1)
        return 0 if len(checks) <= 2 else real_overflow(state)

    monkeypatch.setattr(sim.backend, "overflow", overflow_after_two_chunks)
    with pytest.raises(DeliveryOverflowError) as err:
        sim.run_chunked(40.0, chunk_ms=5.0)
    partial = err.value.partial
    assert partial.n_steps == 100          # exactly the two clean chunks
    assert partial["pop_counts"].shape[0] == 100


def test_no_overflow_no_warning(small_connectome):
    sim = Simulator(CFG, connectome=small_connectome)   # auto budget
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = sim.run(20.0)
    assert res.overflow == 0
    assert not [w for w in caught if "dropped" in str(w.message)]


# ---------------------------------------------------------------------------
# Dense memory guard
# ---------------------------------------------------------------------------

def test_dense_guard_is_actionable(small_connectome):
    big = dataclasses.replace(small_connectome, n_total=100_000)
    with pytest.raises(ValueError) as err:
        dense_delay_binned(big)
    assert "ell" in str(err.value) and "GB" in str(err.value)
    # explicit cap override is respected
    small = dense_delay_binned(small_connectome, max_bytes=float("inf"))
    assert small.shape[0] == small_connectome.d_max_bins


def test_dense_strategy_prepare_guarded(small_connectome):
    big = dataclasses.replace(small_connectome, n_total=100_000)
    with pytest.raises(ValueError, match="ell"):
        dlv.get_strategy("dense").prepare(big, SimConfig(strategy="dense"))


def test_memory_estimates_ordering(small_connectome):
    c = small_connectome
    ell = dlv.get_strategy("ell").memory_bytes(c)
    ev = dlv.get_strategy("event").memory_bytes(c)
    dn = dlv.get_strategy("dense").memory_bytes(c)
    assert ev <= ell < dn        # ELL pads K up; dense is O(N^2)
