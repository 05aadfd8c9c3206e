"""Roofline analysis: dry-run artifacts + the live step program.

Both modes take their peaks from ``repro.perf.peaks``, keyed by device
kind:

**Dry-run cells** (default) — three terms per (arch x shape x mesh) cell,
in seconds per step, for the v5e pod ``launch/dryrun.py`` lays the cells
out on:

  compute    = HLO_FLOPs_per_device / bf16 peak
  memory     = HLO_bytes_per_device / HBM bandwidth
  collective = wire_bytes_per_device / ICI per-link bandwidth

plus MODEL_FLOPS = 6·N_active·tokens (train) or 2·N_active·tokens
(prefill/decode) and the usefulness ratio MODEL_FLOPS / total_HLO_FLOPs
(catches remat/redundancy waste).  The dominant term is the hillclimb target.

**Live step** (``--live``, and ``table1_rtf.py --roofline``) — the
*actual* compiled step program of a built :class:`Simulator` is lowered
(``repro.analysis.hlo_contract.fused_step_hlo``), its per-step FLOPs and
HBM bytes extracted (``repro.perf.hlo_analysis.analyze_hlo``), and —
when a measured per-step wall time is folded in — converted to achieved
FLOP/s and bytes/s against the peaks of the device it ran on.  A device
with no published peaks (the CPU among them) raises.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.launch.runtime import setup_jax
from repro.perf.peaks import peaks_for

#: The chip ``launch/dryrun.py`` compiles its production mesh for.
DRYRUN_DEVICE_KIND = "TPU v5 lite"

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

_TOKENS = {"train_4k": (4096, 256), "prefill_32k": (32768, 32),
           "decode_32k": (1, 128), "long_500k": (1, 1)}


def model_flops(cell: dict) -> float:
    seq, batch = _TOKENS.get(cell["shape"], (1, 1))
    tokens = seq * batch
    n = cell.get("active_params") or cell.get("params", 0)
    factor = 6 if cell["shape"].startswith("train") else 2
    return factor * n * tokens


def analyze(cell: dict) -> dict:
    peaks = peaks_for(DRYRUN_DEVICE_KIND)
    comp = cell["flops_per_device"] / peaks.flops_bf16
    # memory traffic bounds: the HLO-derived count assumes every top-level
    # op round-trips HBM (true on the un-fused CPU backend; a *ceiling* for
    # TPU, which fuses elementwise chains); the floor is compulsory traffic:
    # every argument/output byte touched once.
    mem_ceiling = cell["bytes_accessed_per_device"] / peaks.hbm_bw
    compulsory = (cell["memory"]["argument_bytes"]
                  + cell["memory"]["output_bytes"])
    mem_floor = compulsory / peaks.hbm_bw
    coll = cell["collective_wire_bytes_per_device"] / peaks.ici_link_bw
    terms_opt = {"compute": comp, "memory": mem_floor, "collective": coll}
    terms_pes = {"compute": comp, "memory": mem_ceiling, "collective": coll}
    dominant = max(terms_pes, key=terms_pes.get)
    total_hlo = cell["flops_per_device"] * cell["n_devices"]
    mf = model_flops(cell)
    # subtract phantom f32 weight copies inserted by the CPU backend for
    # bf16 dots (hoisted out of scans); absent on TPU's native-bf16 MXU
    promo = cell.get("cpu_bf16_promotion_bytes", 0.0)
    mem_bytes = (cell["memory"]["argument_bytes"]
                 + cell["memory"]["temp_bytes"]
                 + cell["memory"]["output_bytes"]
                 - cell["memory"]["alias_bytes"]
                 - promo)
    lo = max(terms_opt.values())
    hi = max(terms_pes.values())
    return {
        **{k: cell[k] for k in ("arch", "shape", "mesh", "n_devices")},
        "compute_s": comp, "memory_floor_s": mem_floor,
        "memory_ceiling_s": mem_ceiling, "collective_s": coll,
        "dominant": dominant,
        "step_bound_s": (lo, hi),
        "step_lower_bound_s": lo,
        "model_flops": mf,
        "useful_flops_ratio": (mf / total_hlo) if total_hlo else 0.0,
        "mfu_bound": (
            mf / (cell["n_devices"] * peaks.flops_bf16 * hi) if hi else 0,
            mf / (cell["n_devices"] * peaks.flops_bf16 * lo) if lo else 0),
        "bytes_per_device": mem_bytes,
        "fits_hbm": mem_bytes <= peaks.hbm_bytes,
    }


def hint(r: dict) -> str:
    if r["dominant"] == "collective":
        return ("collective-bound: reduce resharding (fuse constraints, "
                "bigger per-device blocks) or overlap collectives with "
                "compute")
    if r["dominant"] == "memory":
        if r["useful_flops_ratio"] < 0.5:
            return ("memory-bound with low useful-FLOP ratio: cut remat "
                    "recompute and intermediate materialisation (fusion)")
        return ("memory-bound: increase arithmetic intensity (larger "
                "per-device tiles, bf16 weights, fewer passes over params)")
    if r["useful_flops_ratio"] < 0.5:
        return "compute-bound but wasteful: remove redundant/padded FLOPs"
    return "compute-bound and useful: near roofline, little headroom"


def load_cells(mesh: Optional[str] = "pod1") -> List[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(ART_DIR, "*.json"))):
        with open(path) as f:
            c = json.load(f)
        if mesh is None or c.get("mesh") == mesh:
            cells.append(c)
    return cells


def report(mesh: str = "pod1") -> List[dict]:
    rows = [analyze(c) for c in load_cells(mesh)]
    return rows


def markdown_table(rows: List[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s (floor..ceil) "
           "| collective s | dominant | useful FLOPs | MFU bound | bytes/dev "
           "| fits |")
    sep = "|" + "---|" * 10
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} "
            f"| {r['memory_floor_s']:.2e}..{r['memory_ceiling_s']:.2e} "
            f"| {r['collective_s']:.2e} "
            f"| **{r['dominant']}** | {r['useful_flops_ratio']:.2f} "
            f"| {r['mfu_bound'][0]:.2f}-{r['mfu_bound'][1]:.2f} "
            f"| {r['bytes_per_device']/2**30:.1f} GiB "
            f"| {'Y' if r['fits_hbm'] else 'N'} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Live step-program roofline
# ---------------------------------------------------------------------------

def live_roofline(sim, *, n_steps: int = 100) -> Dict:
    """HLO-derived per-step cost of a built Simulator's step program.

    Lowers the backend's scan runner for ``n_steps`` (AOT — nothing runs
    on the device), divides the module totals by ``n_steps``, and places
    the step on the roofline of the device it runs on (raises where
    ``repro.perf.peaks`` lists no such device).  FLOPs = dot + elementwise terms (a
    spiking step is dot-free, so the elementwise term carries it).

    The byte count is a *ceiling*: every top-level op is charged a full
    HBM round trip, which overstates traffic wherever buffers stay in
    cache/VMEM.  Under ``kernels="fused"`` off-TPU the overstatement is
    large — interpret mode emulates the Pallas grid as an XLA loop that
    re-touches whole buffers per grid step — so compare fused-vs-split
    bytes only between on-TPU lowerings.
    """
    from repro.analysis.hlo_contract import fused_step_hlo
    from repro.perf.hlo_analysis import analyze_hlo

    import jax

    kind = jax.devices()[0].device_kind
    peaks = peaks_for(kind)
    hlo = fused_step_hlo(sim, n_steps=n_steps)
    a = analyze_hlo(hlo)
    flops = (a["flops_per_device"]
             + a["elementwise_flops_per_device"]) / n_steps
    ceil_b = a["hbm_bytes_per_device"] / n_steps
    # compulsory floor: the scan carry (membrane state + delay ring) is
    # read and written once per step no matter how well XLA fuses
    state = sim.state if sim.state is not None \
        else sim.backend.init(jax.random.PRNGKey(0))
    floor_b = 2.0 * sum(x.size * x.dtype.itemsize
                        for x in jax.tree_util.tree_leaves(state)
                        if hasattr(x, "dtype"))
    compute_s = flops / peaks.flops_bf16
    mem_floor_s = floor_b / peaks.hbm_bw
    mem_ceil_s = ceil_b / peaks.hbm_bw
    dt_s = float(sim.sim_config.dt) * 1e-3
    pol = sim.sim_config.kernels
    return {
        "device_kind": kind,
        "n_steps_analyzed": n_steps,
        "flops_per_step": flops,
        "hbm_bytes_per_step_floor": floor_b,
        "hbm_bytes_per_step_ceiling": ceil_b,
        "arithmetic_intensity_floor": (flops / floor_b) if floor_b else 0.0,
        "compute_s": compute_s,
        "memory_floor_s": mem_floor_s,
        "memory_ceiling_s": mem_ceil_s,
        "dominant": "memory" if mem_floor_s >= compute_s else "compute",
        "step_bound_s": (max(compute_s, mem_floor_s),
                         max(compute_s, mem_ceil_s)),
        "rtf_bound": (max(compute_s, mem_floor_s) / dt_s,
                      max(compute_s, mem_ceil_s) / dt_s),
        "kernels": None if pol is None else pol.describe(),
    }


def with_achieved(roof: Dict, step_s: float) -> Dict:
    """Fold a measured per-step wall time into achieved-vs-peak rates.

    Achieved bandwidth uses the compulsory *floor* bytes — sustained
    traffic the step cannot avoid — so the percentage stays meaningful on
    hosts where the ceiling model overstates (see ``live_roofline``).
    """
    peaks = peaks_for(roof["device_kind"])
    return {
        **roof,
        "measured_step_s": step_s,
        "achieved_flops_per_s": roof["flops_per_step"] / step_s,
        "achieved_hbm_bytes_per_s":
            roof["hbm_bytes_per_step_floor"] / step_s,
        "pct_peak_flops": 100.0 * roof["flops_per_step"] / step_s
                          / peaks.flops_bf16,
        "pct_peak_hbm": 100.0 * roof["hbm_bytes_per_step_floor"] / step_s
                        / peaks.hbm_bw,
    }


def live_report(scale: float = 0.05, kernels: str = "auto",
                t_sim_ms: float = 100.0, seed: int = 3) -> Dict:
    """Build, measure, and roofline one microcircuit cell (the --live CLI)."""
    from benchmarks.common import time_sim
    from repro.api import Simulator
    from repro.configs.microcircuit import MicrocircuitConfig

    sim = Simulator(MicrocircuitConfig(
        scale=scale, strategy="ell", seed=seed, t_presim=0.0,
        kernels=kernels))
    roof = live_roofline(sim)
    res = time_sim(sim, t_sim_ms)
    return with_achieved(roof, res.wall_s / res.n_steps)


def main(argv=None):
    setup_jax()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--live", action="store_true",
                    help="roofline the live simulator step program "
                         "(measured) instead of the dry-run artifacts")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--kernels", default="auto",
                    choices=("auto", "fused", "split", "reference"))
    ap.add_argument("--t-sim", type=float, default=100.0)
    args = ap.parse_args(argv)

    if args.live:
        r = live_report(scale=args.scale, kernels=args.kernels,
                        t_sim_ms=args.t_sim)
        print(f"roofline/live/scale{args.scale:g}/{args.kernels},"
              f"{r['measured_step_s']*1e6:.1f},"
              f"flops={r['flops_per_step']:.3g};"
              f"bytes_floor={r['hbm_bytes_per_step_floor']:.3g};"
              f"dom={r['dominant']};"
              f"rtf_bound={r['rtf_bound'][0]:.2e}"
              f"..{r['rtf_bound'][1]:.2e};"
              f"pct_peak_hbm={r['pct_peak_hbm']:.3f}")
        print(json.dumps(r, indent=2))
        return

    rows = report("pod1")
    for r in rows:
        print(f"roofline/{r['arch']}/{r['shape']},"
              f"{r['step_lower_bound_s']*1e6:.1f},"
              f"dom={r['dominant']};useful={r['useful_flops_ratio']:.2f};"
              f"mfu={r['mfu_bound'][0]:.2f}-{r['mfu_bound'][1]:.2f};"
              f"fits={'Y' if r['fits_hbm'] else 'N'}")
    print()
    print(markdown_table(rows))


if __name__ == "__main__":
    main()
