# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

  table1_rtf        — paper Table I (RTF + energy/synaptic event)
  strong_scaling    — paper Fig. 1b top (RTF vs scale/resources)
  delivery_ablation — beyond-paper: event vs dense vs gated-kernel delivery
  roofline          — deliverable (g): per-cell roofline terms from dry-run
  serve_throughput  — session-server load: sessions/sec, p50/p99 latency

Run: PYTHONPATH=src python -m benchmarks.run [name ...]
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (delivery_ablation, roofline, serve_throughput,
                            strong_scaling, table1_rtf)
    suites = {
        "table1_rtf": table1_rtf.main,
        "strong_scaling": strong_scaling.main,
        "delivery_ablation": delivery_ablation.main,
        "roofline": roofline.main,
        "serve_throughput": lambda: serve_throughput.main([]),
    }
    picked = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    failed = []
    for name in picked:
        try:
            suites[name]()
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name},nan,ERROR:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
