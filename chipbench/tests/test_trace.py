"""The trace reduction on small synthetic traces (CPU only)."""
from types import SimpleNamespace

import pytest

from chipbench import trace as T
from chipbench.run import metric_reader

from .helpers import REPO

E = T.Event


def tiny_trace():
    # two timed calls on the host; device ops overlap inside the first
    calls = (E(T.CALL_SPAN, 0, 100), E(T.CALL_SPAN, 150, 250))
    host = calls + (E("dispatch", 0, 10), E("readback", 90, 100),
                    E("harness", 100, 150))
    dev = ((E("fusion.1", 10, 50), E("_kernel_plastic", 40, 80),
            E("fusion.1", 160, 240)),)
    return T.Trace(device_ops=dev, host=tuple(sorted(host, key=lambda e: e.start)),
                   calls=calls)


def test_union_merges_overlaps_and_drops_empty():
    assert T.union([(5, 7), (0, 2), (1, 3), (4, 4)]) == [(0, 3), (5, 7)]


def test_covered_clips_to_window():
    assert T.covered([(0, 3), (5, 7)], 2, 6) == 2


def test_busy_and_window():
    tr = tiny_trace()
    assert T.window(tr) == (0, 250)
    assert T.busy(tr, 0, 250) == 70 + 80


def test_busy_averages_over_planes():
    tr = tiny_trace()._replace(device_ops=tiny_trace().device_ops
                               + ((E("x", 0, 250),),))
    assert T.busy(tr, 0, 250) == (150 + 250) / 2


def test_op_seconds_by_name():
    ops = T.op_seconds(tiny_trace(), 0, 250)
    assert ops == pytest.approx({"fusion.1": 120e-9, "_kernel_plastic": 40e-9})
    only = T.op_seconds(tiny_trace(), 0, 250, lambda n: "plastic" in n)
    assert only == pytest.approx({"_kernel_plastic": 40e-9})


def test_idle_gaps_are_named_by_innermost_host_span():
    gaps = T.idle_gaps(tiny_trace(), 0, 250)
    # gaps: [0,10] 10, [80,160] 80, [240,250] 10
    assert gaps[0] == ["harness", pytest.approx(80e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx([10e-9, 10e-9, 80e-9])
    assert ["dispatch", pytest.approx(10e-9)] in gaps


def test_host_label_outside_spans_is_idle():
    assert T.host_label(tiny_trace(), 1000) == "idle"


def test_metric_readers_on_the_synthetic_trace():
    run = SimpleNamespace(trace=tiny_trace())
    bench = REPO / "chipbench"
    idle = metric_reader(bench, "device_idle_share")(run)
    assert idle == pytest.approx(100 * (1 - 150 / 250))


def test_readers_return_nothing_without_a_trace():
    run = SimpleNamespace(trace=None)
    assert metric_reader(REPO / "chipbench", "device_idle_share")(run) is None


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def test_device_planes_keep_only_the_cells_chips():
    ev = SimpleNamespace(name="fusion", start_ns=0, end_ns=5)
    planes = [_plane("/host:CPU"),
              _plane("/device:TPU:0", **{"XLA Ops": [ev]}),
              _plane("/device:TPU:1", **{"XLA Ops": []}),
              _plane("/device:TPU:10", **{"XLA Ops": [ev]})]
    assert [p.name for p in T.device_planes(planes, [0])] == [
        "/device:TPU:0"]
    assert [p.name for p in T.device_planes(planes, [1, 10])] == [
        "/device:TPU:1", "/device:TPU:10"]
    assert len(T.device_planes(planes, None)) == 3


def test_device_ops_fall_back_to_modules_line():
    ev = SimpleNamespace(name="jit_run", start_ns=3, end_ns=9)
    got = T.device_ops(_plane("/device:TPU:0", **{"XLA Ops": [],
                                                  "XLA Modules": [ev]}))
    assert got == (E("jit_run", 3, 9),)
