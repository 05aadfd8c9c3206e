"""Session-server load benchmark: sessions/sec + p50/p99 step latency.

Three measurements against one small scenario (compile excluded — the
first session warms the shared caches, which is exactly the serving
steady state the subsystem exists to provide):

  churn      create + run + destroy, one session at a time: sessions/sec
             of short-lived users against warm shared caches
  latency    one long-lived session issuing many small ``run`` requests:
             p50/p99 wall latency per request (the interactive case)
  coalesce   N same-config sessions per request wave, batched through the
             vmapped ``run_batch`` path vs run sequentially: aggregate
             sessions/sec both ways

Rows land in the schema-versioned ledger (``BENCH_serve.json``, same
``repro.bench_rtf/v2`` family as ``BENCH_rtf.json``; every entry carries
``rtf`` so ``compare_ledgers`` gates regressions unchanged)::

    PYTHONPATH=src python benchmarks/serve_throughput.py \
        --out BENCH_serve.json
    PYTHONPATH=src python benchmarks/serve_throughput.py \
        --compare BENCH_serve.json      # exit 3 on regression
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks import common
from benchmarks.common import fmt_row
from repro.launch.runtime import setup_jax

SCALE = 0.02
RUN_MS = 20.0         # per-request horizon
N_CHURN = 6
N_LATENCY = 30
N_COALESCE = 4


def _experiment():
    from repro.api.experiment import Experiment
    from repro.configs.microcircuit import MicrocircuitConfig
    model = MicrocircuitConfig(n_scaling=SCALE, k_scaling=SCALE,
                               t_presim=10.0, seed=7)
    return Experiment(model=model, probes=("pop_counts",),
                      name="serve-throughput")


def _entry(name: str, *, rtf: float, wall_s: float, t_model_ms: float,
           connectome, **extra) -> dict:
    out = {
        "name": name, "strategy": "event", "scale": SCALE,
        "rtf": float(rtf), "wall_s": float(wall_s),
        "t_model_ms": float(t_model_ms),
        "n_steps": int(round(t_model_ms / 0.1)),
        "n_neurons": int(connectome.n_total),
        "n_synapses": int(connectome.n_synapses),
        "overflow": 0,
    }
    out.update(extra)
    return out


def bench_churn(mgr, exp, connectome) -> dict:
    """Short-lived users: create/run/destroy against warm caches."""
    t0 = time.perf_counter()
    rtfs = []
    for _ in range(N_CHURN):
        s = mgr.create(exp)
        rtfs.append(s.run(RUN_MS).rtf)
        mgr.destroy(s.id)
    wall = time.perf_counter() - t0
    sessions_per_s = N_CHURN / wall
    print(fmt_row("serve/churn", wall / N_CHURN * 1e6,
                  f"{sessions_per_s:.2f}_sessions_per_s"))
    return _entry(f"serve/churn/scale{SCALE}",
                  rtf=float(np.mean(rtfs)), wall_s=wall,
                  t_model_ms=N_CHURN * RUN_MS, connectome=connectome,
                  n_sessions=N_CHURN, sessions_per_s=sessions_per_s)


def bench_latency(mgr, exp, connectome) -> dict:
    """One interactive session, many small requests: p50/p99 wall."""
    s = mgr.create(exp)
    s.run(RUN_MS)                    # warm + presim, untimed
    lat = []
    for _ in range(N_LATENCY):
        t0 = time.perf_counter()
        s.run(RUN_MS)
        lat.append(time.perf_counter() - t0)
    mgr.destroy(s.id)
    p50, p99 = np.percentile(lat, [50, 99])
    total = float(np.sum(lat))
    print(fmt_row("serve/latency", p50 * 1e6,
                  f"p50={p50 * 1e3:.1f}ms_p99={p99 * 1e3:.1f}ms"))
    return _entry(f"serve/latency/scale{SCALE}",
                  rtf=total / (N_LATENCY * RUN_MS * 1e-3), wall_s=total,
                  t_model_ms=N_LATENCY * RUN_MS, connectome=connectome,
                  n_requests=N_LATENCY,
                  p50_ms=float(p50 * 1e3), p99_ms=float(p99 * 1e3))


def bench_coalesce(mgr, exp, connectome) -> list:
    """A wave of same-config requests, batched vs sequential."""
    sessions = [mgr.create(exp, seed=100 + i) for i in range(N_COALESCE)]
    reqs = {s.id: RUN_MS for s in sessions}
    mgr.run_many(reqs)               # warm the batched executable, untimed
    rows = []
    for mode, coalesce in (("coalesced", True), ("sequential", False)):
        t0 = time.perf_counter()
        results = mgr.run_many(reqs, coalesce=coalesce)
        wall = time.perf_counter() - t0
        sessions_per_s = N_COALESCE / wall
        rtf = float(np.mean([r.rtf for r in results.values()]))
        print(fmt_row(f"serve/{mode}{N_COALESCE}", wall * 1e6,
                      f"{sessions_per_s:.2f}_sessions_per_s"))
        rows.append(_entry(
            f"serve/{mode}{N_COALESCE}/scale{SCALE}", rtf=rtf,
            wall_s=wall, t_model_ms=N_COALESCE * RUN_MS,
            connectome=connectome, n_sessions=N_COALESCE,
            sessions_per_s=sessions_per_s, coalesced=coalesce))
    for s in sessions:
        mgr.destroy(s.id)
    return rows


def measure() -> list:
    from repro.serve import SessionManager
    exp = _experiment()
    with SessionManager() as mgr:
        warm = mgr.create(exp)       # pay build + compile outside the clock
        warm.run(RUN_MS)
        connectome = warm.sim.connectome
        mgr.destroy(warm.id)
        entries = [bench_churn(mgr, exp, connectome),
                   bench_latency(mgr, exp, connectome)]
        entries.extend(bench_coalesce(mgr, exp, connectome))
    return entries


def main(argv=None) -> int:
    setup_jax()
    ap = argparse.ArgumentParser(
        description="serve throughput ledger benchmark")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the ledger JSON here")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="exit 3 if any entry regresses vs this ledger")
    ap.add_argument("--rtol", type=float, default=0.5)
    args = ap.parse_args(argv)

    entries = measure()
    doc = {"schema": common.BENCH_SCHEMA,
           "machine": common.machine_metadata(), "entries": entries}
    if args.out:
        doc = common.write_ledger(
            args.out, entries,
            meta={"suite": "serve_throughput", "run_ms": RUN_MS})
        print(f"ledger written: {args.out} ({len(entries)} entries)")
    if args.compare:
        baseline = common.load_ledger(args.compare)
        regressions = common.compare_ledgers(baseline, doc,
                                             rtol=args.rtol)
        if regressions:
            for r in regressions:
                print(f"REGRESSION {r['name']}: rtf {r['baseline_rtf']:.2f}"
                      f" -> {r['current_rtf']:.2f} (x{r['ratio']:.2f})",
                      file=sys.stderr)
            return 3
        print(f"no regressions vs {args.compare}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
