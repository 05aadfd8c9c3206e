"""Fig. 1b (top) analogue: realtime factor vs problem scale / resources.

The container has one CPU core, so the paper's thread axis is replaced by
two sweeps:
  (a) measured CPU RTF across network scales (event strategy) — shows how
      wall time tracks the synapse count on fixed hardware, and
  (b) projected v5e RTF across chip counts for the FULL-scale model, derived
      from the dry-run roofline terms (event strategy; see EXPERIMENTS.md
      §Roofline for the derivation).
"""
from __future__ import annotations

import json
import os

from benchmarks.common import fmt_row, time_sim
from benchmarks.roofline import DRYRUN_DEVICE_KIND
from repro.api import Simulator
from repro.configs.microcircuit import MicrocircuitConfig
from repro.launch.runtime import setup_jax
from repro.perf.peaks import peaks_for

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

# conservative per-step overheads for the projection (latency-bound regime)
STEP_LATENCY_S = {1: 2e-6, 256: 6e-6, 512: 8e-6}   # dispatch + AG latency


def measured_rows():
    rows = []
    for scale in (0.01, 0.02, 0.05):
        sim = Simulator(MicrocircuitConfig(
            n_scaling=scale, k_scaling=scale, seed=1, spike_budget=256,
            t_presim=0.0))
        res = time_sim(sim, 1000.0)
        c = sim.connectome
        rows.append(fmt_row(
            f"strong_scaling/cpu/scale_{scale}", res.wall_s * 1e6 / 10000,
            f"rtf={res.rtf:.2f};N={c.n_total};syn={c.n_synapses}"))
    return rows


def _event_mem_bytes_per_step(chips: int) -> float:
    """Analytic HBM bytes/device/step for event delivery.

    The HLO-derived ceiling charges each row-gather with its *full table
    operand* (an analyzer artifact); physically a gather touches only the
    ~31 spiking rows: S x k_loc x 9 B plus the local state read-modify-write.
    """
    spikes = 31.0                       # 77k neurons x ~4 Hz x 0.1 ms
    k_loc = 3876.0 / chips + 8 * (3876.0 / chips) ** 0.5  # padded row width
    n_loc = 77312.0 / chips
    return spikes * k_loc * 9 + n_loc * 6 * 4 * 2


def projected_rows():
    """Full-scale v5e projection from the event-strategy dry-run cell."""
    rows = []
    for mesh, chips in (("pod1", 256), ("pod2", 512)):
        path = os.path.join(ART, f"microcircuit__event__{mesh}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            cell = json.load(f)
        steps = 100.0                      # the dry-run lowers a 100-step chunk
        peaks = peaks_for(DRYRUN_DEVICE_KIND)
        comp = cell["flops_per_device"] / steps / peaks.flops_bf16
        mem = _event_mem_bytes_per_step(chips) / peaks.hbm_bw
        coll = (cell["collective_wire_bytes_per_device"] / steps
                / peaks.ici_link_bw)
        lat = STEP_LATENCY_S[chips]
        step_s = max(comp, mem, coll) + lat
        rtf = step_s / 1e-4                # 0.1 ms of model time per step
        rows.append(fmt_row(
            f"strong_scaling/v5e_projected/{chips}chips", step_s * 1e6,
            f"rtf={rtf:.3f};comp={comp:.2e};mem={mem:.2e};coll={coll:.2e}"))
    return rows


def main():
    setup_jax()
    for r in measured_rows() + projected_rows():
        print(r)


if __name__ == "__main__":
    main()
