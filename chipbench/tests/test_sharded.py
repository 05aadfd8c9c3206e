"""The four-chip cells' harness on four virtual CPU devices: a tiny
``pd14_full_x4`` network (2 % of the neurons and the in-degree) through
``run.run_cell`` and the sharded backend, checked against the reference
that draws the drive per chip; and a tiny ``pd14_full_stdp_x4`` network
whose program is the reference itself, handing its state back in the
sharded plastic layout, checked on the host.  The cases run once, in a
process of their own (``sharded_cases.py``), as
``tests/test_distributed.py`` runs its multi-device cases; the state
mappings need no device."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import compare, layers, netgen, run
from repro.core.distributed import ShardedSimState, localize_ell

from .helpers import REPO

CASES = REPO / "chipbench" / "tests" / "sharded_cases.py"


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, str(CASES), str(tmp_path_factory.mktemp("x4"))],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_sharded_program_is_correct(cases):
    r = cases["sound"]
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] > 1
    assert r["counts_gap"] == 0 and r["clock_gap"] == 0
    assert r["state_gap"] == 0 and r["ref_overflow"] == 0


def test_one_key_reference_tells_the_drives_apart(cases):
    """The reference with ``shards=1`` (one key over all the neurons)
    does not pass against the program that draws per chip."""
    r = cases["one_key"]
    assert r["correct"] is False, r
    assert r["counts_gap"] > 0.25 and r["clock_gap"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half", "no_exchange",
                                   "spike_altered"])
def test_fault_is_not_correct(cases, fault):
    r = cases[fault]
    assert r["correct"] is False, r


def test_bfloat16_reference_in_place_is_not_correct(cases):
    low, same = cases["control"]["bfloat16"], cases["control"]["float32"]
    assert low["correct"] is False, low
    assert same["counts_gap"] == 0 and same["state_gap"] == 0
    assert same["clock_gap"] == 0 and same["correct"] is True


def test_exchange_reader_names_the_programs_collectives(cases):
    """Every collective of the warmed sharded programs, and nothing else,
    is what ``exchange_ms`` reads as the exchange."""
    c = cases["collectives"]
    assert c["programs"] >= 2            # the mix's call and the presim
    assert c["collective"], c
    assert c["read_as_collective"] == c["collective"]


def test_mesh_on_other_chips_is_refused(cases):
    assert "not the cell's chips" in cases["mesh_elsewhere"]


def test_sound_sharded_plastic_program_is_correct(cases):
    """The reference in the program's place, its state handed back in the
    sharded plastic layout, reads as itself."""
    r = cases["plastic"]["sound"]
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] > 1
    assert r["counts_gap"] == 0 and r["clock_gap"] == 0
    assert r["state_gap"] == 0 and r["ref_overflow"] == 0


@pytest.mark.parametrize("fault", ["blocks_swapped", "weights_unchanged",
                                   "traces_swapped", "flat_export"])
def test_sharded_plastic_fault_is_not_correct(cases, fault):
    r = cases["plastic"][fault]
    assert r["correct"] is False, r


def test_host_reference_tables_are_on_the_host(cases):
    """``reference_device: "host"`` commits the check's tables to the
    CPU device, whatever the default device is (here the fourth)."""
    r = cases["plastic"]["sound"]
    assert r["check_tables"], r
    for leaf, (devices, committed) in r["check_tables"].items():
        assert devices == [r["host"]] and committed is True, leaf


def test_reference_device_names_only_the_host():
    assert run.reference_device(None) is None
    assert run.reference_device("host") == "host"
    with pytest.raises(SystemExit):
        run.reference_device("chip")


@pytest.mark.parametrize("chips", [2, 4])
def test_sharded_columns_invert_the_programs_layout(chips):
    """The program's own layout of the weights (``localize_ell``), read
    back by the harness's map, is ``net.weights`` and the sentinel row,
    exactly; the map's inverse is the program's layout."""
    cfg = json.loads((REPO / "chipbench/configs/pd14_full.json")
                     .read_text())
    cfg.update(n_scaling=0.02, k_scaling=0.02)
    net = netgen.network(cfg)
    tables, meta = localize_ell(run.connectome(net, cfg), chips)
    cols = run.ShardedColumns(net, chips)
    assert cols.least_k_loc() == meta["k_loc"]
    n, k = net.targets.shape
    w = cols.to_reference(tables.weights)
    np.testing.assert_array_equal(w[:n], net.weights)
    assert w.shape == (n + 1, k) and not w[n].any()
    np.testing.assert_array_equal(cols.to_sharded(w, meta["k_loc"]),
                                  tables.weights)


def test_export_maps_sharded_state_to_reference_leaves():
    """A hand-built state of 4 chips: N = 10, n_loc = 3, N_pad = 12; the
    ring holds each chip's 3 columns and its dump column."""
    n, n_dev, n_loc, d = 10, 4, 3, 5
    n_pad = n_dev * n_loc
    ring = np.arange(d * 2 * (n_pad + n_dev), dtype=np.float32).reshape(
        d, 2, n_pad + n_dev)
    key = np.arange(2 * n_dev, dtype=np.uint32).reshape(n_dev, 2)
    st = ShardedSimState(
        V=np.arange(n_pad, dtype=np.float32),
        I_ex=np.arange(n_pad, dtype=np.float32) + 100,
        I_in=np.arange(n_pad, dtype=np.float32) + 200,
        refrac=np.arange(n_pad, dtype=np.int32), ring=ring,
        t=np.int32(7), key=key, overflow=np.zeros(n_dev, np.int32))
    out = run.export_state(st, n, 64)
    assert out["V"].tolist() == list(range(n))
    assert out["I_ex"][0] == 100 and out["I_in"][-1] == 209
    assert out["refrac"].shape == (n,)
    assert out["ring"].shape == (d, 2, n + 1)
    for j in range(n):                  # neuron j: chip j // 3, column j % 3
        col = (j // n_loc) * (n_loc + 1) + j % n_loc
        np.testing.assert_array_equal(out["ring"][..., j], ring[..., col])
    assert not out["ring"][..., n].any()            # the sentinel column
    np.testing.assert_array_equal(out["key"], key)
    assert int(out["t"]) == 7


def test_clock_gap_counts_every_chip_key():
    before = {"t": np.int32(0), "key": np.zeros((4, 2), np.uint32)}
    after = {"t": np.int32(5), "key": np.arange(8, dtype=np.uint32)
             .reshape(4, 2)}
    ref = dict(after, over=np.int32(0))
    ref["key"] = after["key"].copy()
    ref["key"][3, 1] += 1
    gaps = compare.sample_gaps(before, after, [[1]], ref, [[1]])
    assert gaps["clock"] == 1
    one = dict(ref, key=after["key"][0])            # one key, not four
    assert compare.sample_gaps(before, after, [[1]], one, [[1]])[
        "clock"] == 8


@pytest.mark.parametrize("name,yes", [
    ("%all-gather.3 = pred[16]{0} all-gather(pred[4]{0} %p)", True),
    ("%all-reduce.5 = u32[152,1,128]{2,1,0} all-reduce(%bitcast.111)", True),
    ("%all-gather-start.1 = (pred[4], pred[16]) all-gather-start(%p)", True),
    ("%all-reduce-done.2 = u32[8]{0} all-reduce-done(%s)", True),
    ("%collective-permute.4 = f32[8]{0} collective-permute(%x)", True),
    ("%fusion.64 = f32[7099640]{0} fusion(%a, %b)", False),
    ("%while.53 = (s32[], f32[77169]{0}) while(%t)", False),
    ("%copy.4 = f32[8]{0} copy(%x)", False),
    ("%all_gather.14 = pred[16]{0} all-gather(pred[4]{0} %p)", True),
    ("%fusion.2 = pred[16]{0} fusion(pred[16]{0} %all-gather.3)", False),
])
def test_is_collective(name, yes):
    assert layers.is_collective(name) is yes
