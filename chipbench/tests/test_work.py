"""The required-work count against a hand count (CPU only)."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import netgen, work
from chipbench.peaks import PEAKS
from chipbench.run import Call, metric_reader

from .helpers import REPO


@pytest.fixture(scope="module")
def net():
    cfg = json.loads((REPO / "chipbench/configs/pd14_full.json").read_text())
    cfg.update(n_scaling=0.01, k_scaling=0.01)
    return cfg, netgen.network(cfg)


def test_mean_out_degree_matches_the_network(net):
    _, nw = net
    m = nw.model
    deg = work.per_source(m)["out"]
    real = np.array([nw.out_degree[nw.pop_of == p].mean()
                     for p in range(len(m.pops))])
    np.testing.assert_allclose(deg, real, rtol=1e-12)


def test_synapse_bytes_packs_target_and_delay():
    # 77,169 neurons: 17 bits; 46 delay bins: 6 bits -> 3 bytes + weight
    assert work.synapse_bytes(77169, 46) == 7
    assert work.synapse_bytes(255, 2) == 4 + 2


def test_step_work_by_hand(net):
    _, nw = net
    m = nw.model
    counts = np.zeros((2, len(m.pops)), np.int32)
    counts[0, 1] = 3                   # three spikes in population 1
    counts[1, 5] = 1
    out = m.n_syn.sum(axis=0) / m.n_pop
    syn = 3 * out[1] + out[5]
    sb = work.synapse_bytes(m.n_total, m.d_max_bins)
    w = work.step_work(m, counts, plastic=False)
    assert w.bytes == pytest.approx(2 * m.n_total * 16 + syn * sb)
    assert w.flops == pytest.approx(2 * m.n_total * 15 + syn)
    wp = work.step_work(m, counts, plastic=True)
    ee_out = (m.n_syn[:4, :4].sum(axis=0) / m.n_pop[:4])
    ee_in = (m.n_syn[:4, :4].sum(axis=1) / m.n_pop[:4])
    dep = 3 * ee_out[1]                # population 5 is inhibitory
    pot = 3 * ee_in[1]
    assert wp.bytes == pytest.approx(w.bytes + 4 * dep + 2 * m.n_total * 8
                                     + 8 * pot)


@pytest.mark.parametrize("chips", [1, 4])
def test_step_mfu_is_least_time_over_call_time(net, chips):
    """The peak of ``chips`` chips is one chip's times the chips."""
    cfg, nw = net
    m = nw.model
    counts = np.ones((10, len(m.pops)), np.int32)
    peaks = PEAKS["TPU v5 lite"]
    # two calls with host time between them: the whole window counts
    calls = [Call(0.0, 0.5, 5, counts[:5]), Call(0.6, 1.0, 5, counts[5:])]
    run = SimpleNamespace(calls=calls, span=(0.0, 1.0), net=nw,
                          cfg=cfg, peaks=peaks, chips=chips)
    got = metric_reader(REPO / "chipbench", "step_mfu")(run)
    need = work.step_work(m, counts, False).bytes / (chips * peaks.hbm_bw)
    assert got == pytest.approx(100 * need / 1.0)
    assert metric_reader(REPO / "chipbench", "step_mfu")(
        SimpleNamespace(calls=[], net=nw, cfg=cfg, peaks=peaks,
                        chips=chips)) is None


def test_rtf_counts_host_time_between_calls(net):
    cfg, _ = net
    calls = [Call(0.0, 0.5, 5, None), Call(0.6, 1.0, 5, None)]
    run = SimpleNamespace(calls=calls, span=(0.0, 1.0), cfg=cfg)
    rtf = metric_reader(REPO / "chipbench", "rtf")(run)
    assert rtf == pytest.approx(1.0 / (10 * cfg["dt_ms"] * 1e-3))
    run.calls = []
    assert metric_reader(REPO / "chipbench", "rtf")(run) is None
