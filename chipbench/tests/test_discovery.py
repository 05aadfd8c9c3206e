"""Cells, configurations, mixes and metrics are found by name, so a new
one is new files and BENCHMARK.json entries; the result's last line has
the driver's shape; without a TPU the harness refuses (CPU only)."""
import json
import os
import subprocess
import sys

from chipbench import run

from .helpers import REPO, run_tiny, tiny_root


def test_new_mix_and_metric_need_only_new_files(tmp_path):
    root, name = tiny_root(tmp_path, traffic="dummy",
                           mix={"chunk_ms": 0.5, "check_chunks": 1})
    (root / "chipbench/metrics/calls_done.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "calls_done", "unit": "calls",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell(name, root)
    assert cell.mix["chunk_ms"] == 0.5 and cell.cfg["name"] == "tiny"
    assert [m["name"] for m in cell.end_to_end] == [
        "rtf", "setup_s", "calls_done"]
    res = run_tiny(tmp_path / "again", traffic="dummy",
                   mix={"chunk_ms": 0.5, "check_chunks": 1})
    assert res["correct"] is True


def test_result_line_shape(tmp_path):
    res = run_tiny(tmp_path)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"rtf", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "chipbench/run.py"), "--workload",
         "pd14_full.scan20", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 3 and p.stdout == ""
    assert "TPU" in p.stderr


def test_trace_stops_after_the_cells_trace_calls(tmp_path, monkeypatch):
    """A cell file's ``trace_calls`` ends the trace after that many calls;
    the window goes on untraced and its calls are still checked."""
    import jax

    from chipbench import trace as T
    root, name = tiny_root(tmp_path)
    own = root / "chipbench" / "cells" / f"{name}.json"
    own.write_text(json.dumps(dict(json.loads(own.read_text()),
                                   trace_calls=1)))
    stops, loaded = [], []
    stop, load = jax.profiler.stop_trace, T.load
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stops.append(1) or stop())
    monkeypatch.setattr(T, "load",
                        lambda *a: loaded.append(load(*a)) or loaded[-1])
    cell = run.load_cell(name, root)
    assert cell.trace_calls == 1
    res = run.run_cell(cell, 2 ** 33 + 9, 3.0, True,
                       devices=jax.devices()[:1])
    assert res["correct"] is True and res["attempted"] > 1
    assert len(stops) == 1
    assert len(loaded[0].calls) == 1
