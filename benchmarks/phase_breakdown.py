"""Fig. 1b (bottom) analogue: wall-clock fraction per simulation phase.

The paper instruments update / deliver / communicate with NEST's timers;
the ``instrumented`` Simulator backend reproduces that instrumentation
(each phase a separately jitted, synchronised call).  Communicate is a
no-op on one device — the dry-run's collective term covers it for the
sharded engine.
"""
from __future__ import annotations

from benchmarks.common import fmt_row
from repro.api import Simulator
from repro.configs.microcircuit import MicrocircuitConfig
from repro.launch.runtime import setup_jax


def run(scale: float = 0.05, steps: int = 2000, strategy: str = "event"):
    cfg = MicrocircuitConfig(n_scaling=scale, k_scaling=scale, seed=2,
                             strategy=strategy, spike_budget=256,
                             t_presim=0.0)
    sim = Simulator(cfg, backend="instrumented", probes=())
    t_ms = steps * cfg.dt
    sim.warmup(t_ms)                       # compile outside the timers
    sim.reset()
    res = sim.run(t_ms)
    timers = {k: v for k, v in res.timers.items() if k != "record"}
    total = sum(timers.values())
    rows = []
    for phase, t in sorted(timers.items()):
        rows.append(fmt_row(
            f"phase_breakdown/{strategy}/{phase}", t / steps * 1e6,
            f"fraction={t / total:.2f}"))
    return rows


def main():
    setup_jax()
    for strategy in ("event", "dense"):
        sc = 0.05 if strategy == "event" else 0.02
        for r in run(scale=sc, steps=500, strategy=strategy):
            print(r)


if __name__ == "__main__":
    main()
