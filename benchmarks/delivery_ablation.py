"""Beyond-paper ablation: delivery strategies x network scales.

Sweeps every registered spike-delivery strategy (``event`` gather+scatter,
``dense`` delay-binned GEMM, ``ell`` sparse-ELL) across down-scaled
microcircuits and reports wall time per step, RTF, overflow and the
host-estimated table footprint.  Cells land in the BENCH JSON format under
``artifacts/bench/delivery__{strategy}__{scale}.json`` (same directory
convention as the dry-run cells consumed by ``table1_rtf`` /
``strong_scaling``); the CSV rows keep ``benchmarks.run`` compatible.

Strategies whose footprint cannot reach a scale are reported as skipped
rather than OOM-ing (the dense guard is the mechanism under test there).
The Pallas kernels' HBM-traffic saving is reported analytically since
interpret mode has no bandwidth model.
"""
from __future__ import annotations

import json
import os

from benchmarks.common import fmt_row, time_sim
from repro.api import Simulator
from repro.configs.microcircuit import MicrocircuitConfig
from repro.core import delivery as dlv
from repro.core import connectivity as conn
from repro.launch.runtime import setup_jax

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")

SCALES = (0.01, 0.02, 0.05)
STRATEGIES = ("event", "dense", "ell")
T_MS = 100.0


def bench_cell(strategy: str, scale: float, connectome=None) -> dict:
    sim = Simulator(
        MicrocircuitConfig(scale=scale, seed=4, strategy=strategy,
                           t_presim=0.0),
        connectome=connectome)
    res = time_sim(sim, T_MS)
    c = sim.connectome
    return {
        "name": f"delivery__{strategy}__{scale}",
        "strategy": strategy,
        "scale": scale,
        "n_neurons": int(c.n_total),
        "n_synapses": int(c.n_synapses),
        "spike_budget": sim.sim_config.spike_budget,
        "us_per_step": res.wall_s * 1e6 / res.n_steps,
        "rtf": res.rtf,
        "wall_s": res.wall_s,
        "overflow": int(res.overflow),
        "table_bytes": int(
            dlv.get_strategy(strategy).memory_bytes(c)),
        "_connectome": c,            # stripped before writing
    }


def gated_skip_fraction(spikes_per_step: float, n: int,
                        block: int = 512) -> float:
    """Expected fraction of W tiles the gated dense kernel skips."""
    return (1 - spikes_per_step / n) ** block


def main():
    setup_jax()
    os.makedirs(ART, exist_ok=True)
    rows = []
    for scale in SCALES:
        c = None
        for strategy in STRATEGIES:
            if (strategy == "dense" and c is not None
                    and conn.dense_bytes_estimate(c) > conn.DENSE_MAX_BYTES):
                # the guard under test: report the skip, don't trip it
                rows.append(fmt_row(
                    f"delivery/{strategy}@{scale}", 0.0,
                    f"skipped:dense_guard"
                    f"({conn.dense_bytes_estimate(c) / 1e9:.0f}GB)"))
                continue
            cell = bench_cell(strategy, scale, connectome=c)
            c = cell.pop("_connectome")
            path = os.path.join(ART, cell["name"] + ".json")
            with open(path, "w") as f:
                json.dump(cell, f, indent=1)
            rows.append(fmt_row(
                f"delivery/{strategy}@{scale}", cell["us_per_step"],
                f"rtf={cell['rtf']:.2f};overflow={cell['overflow']};"
                f"table_mb={cell['table_bytes'] / 1e6:.0f}"))
    # full-scale analytic: natural activity ~31 spikes/step over 77k sources
    skip_full = gated_skip_fraction(31.0, 77169)
    rows.append(fmt_row(
        "delivery/gated_kernel_tile_skip", 0.0,
        f"skip_frac_fullscale={skip_full:.2f};"
        f"W_traffic_reduction=x{1 / (1 - skip_full):.1f}"))
    # the ell strategy's full-scale footprint vs the guarded dense one
    rows.append(fmt_row(
        "delivery/fullscale_table_bytes", 0.0,
        "ell=~3.7e9;dense=~1.1e12(guarded);"
        "ell_step_traffic=O(S*K)=~31*3876*12B"))
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
