"""The network instance follows the configuration (CPU only)."""
import json

import numpy as np
import pytest

from chipbench import netgen

from .helpers import REPO


@pytest.fixture(scope="module")
def cfg():
    c = json.loads((REPO / "chipbench/configs/pd14_full.json").read_text())
    c.update(n_scaling=0.02, k_scaling=0.02)
    return c


def test_projection_totals_and_layout(cfg):
    nw = netgen.network(cfg)
    m = nw.model
    n, k = nw.targets.shape
    assert n == m.n_total and k % netgen.LANE == 0
    valid = nw.targets < n
    np.testing.assert_array_equal(valid.sum(axis=1), nw.out_degree)
    src = np.broadcast_to(np.arange(n)[:, None], (n, k))[valid]
    proj = np.zeros_like(m.n_syn)
    np.add.at(proj, (nw.pop_of[nw.targets[valid]], nw.pop_of[src]), 1)
    np.testing.assert_array_equal(proj, m.n_syn)
    # Dale's law, delays in the ring, padding points at the sentinel
    w = nw.weights
    assert (w[:m.n_exc][valid[:m.n_exc]] >= 0).all()
    assert (w[m.n_exc:][valid[m.n_exc:]] <= 0).all()
    assert nw.dbins[valid].min() >= 1
    assert nw.dbins[valid].max() <= m.d_max_bins - 1
    assert (w[~valid] == 0).all() and (nw.dbins[~valid] == 1).all()


def test_same_seed_same_network(cfg):
    a, b = netgen.network(cfg), netgen.network(cfg)
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)


def test_model_matches_the_published_rule(cfg):
    """Totals, weights and compensation agree with the program's own
    instantiation of the same model at the same scale."""
    from repro.core import params as P
    from repro.core.connectivity import build_connectome
    m = netgen.model(cfg)
    c = build_connectome(n_scaling=0.02, k_scaling=0.02)
    n_full = np.array([P.N_FULL[p] for p in P.POPULATIONS])
    np.testing.assert_array_equal(
        m.n_syn, P.synapse_numbers(n_full, P.CONN_PROBS, m.n_pop, 0.02))
    assert m.pops == P.POPULATIONS
    assert m.w_ext == pytest.approx(c.w_ext, rel=1e-12)
    assert m.d_max_bins == c.d_max_bins
    # the program rounds the full-scale totals before taking in-degrees
    np.testing.assert_allclose(m.i_dc[c.pop_of], c.i_dc, rtol=1e-5)
