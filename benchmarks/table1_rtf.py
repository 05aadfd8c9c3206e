"""Table I analogue + the persisted RTF benchmark ledger.

Default mode prints the paper's literature table plus this framework's
rows (measured CPU RTF at a down-scale; roofline-projected full-scale RTF
and energy/synaptic event on TPU v5e).

Ledger modes turn the measurement into a regression gate:

    # measure the strategy x scale sweep, persist the ledger
    python benchmarks/table1_rtf.py --sweep --out artifacts/bench/BENCH_rtf.json

    # ... with per-step roofline numbers (achieved vs the peaks of the
    # device it ran on; raises off the chips repro.perf.peaks lists) and
    # the fused one-kernel-step rows attached to every entry
    python benchmarks/table1_rtf.py --sweep --roofline --out BENCH_rtf.json

    # ... and flag regressions against the committed reference ledger
    python benchmarks/table1_rtf.py --sweep --compare BENCH_rtf.json

    # compare two existing ledgers without re-measuring
    python benchmarks/table1_rtf.py --replay artifacts/bench/BENCH_rtf.json \
        --compare BENCH_rtf.json

``--compare`` exits with status 3 when any matched entry's RTF exceeds
``baseline * (1 + rtol)`` — the exit code CI (and the tier-2 test) keys
off.  Energy model: TDP ~200 W/chip wall power (v5e), E = P x chips x
T_wall; synaptic events = N_syn x mean_rate x T_model (paper definition).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from benchmarks import common
from benchmarks.common import fmt_row, time_sim
from benchmarks.roofline import DRYRUN_DEVICE_KIND
from repro.api import Simulator
from repro.configs.microcircuit import MicrocircuitConfig
from repro.core.params import FULL_MEAN_RATES, N_FULL, POPULATIONS
from repro.launch.runtime import setup_jax
from repro.perf.peaks import peaks_for

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

LITERATURE = [
    ("2018 NEST (energy-opt)", 6.29, 4.39),
    ("2018 NEST (fastest)", 2.47, 9.35),
    ("2018 GeNN (energy-opt)", 26.08, 0.30),
    ("2018 GeNN (fastest)", 1.84, 0.47),
    ("2019 SpiNNaker", 1.00, 0.60),
    ("2021 NeuronGPU", 1.06, None),
    ("2021 GeNN", 0.70, None),
    ("paper NEST EPYC 1-node", 0.67, 0.33),
    ("paper NEST EPYC 2-node", 0.53, 0.48),
]

CHIP_POWER_W = 200.0
FULL_SYNAPSES = 299e6


def full_scale_event_rate() -> float:
    n = np.array([N_FULL[p] for p in POPULATIONS], dtype=float)
    # synaptic events/s = sum over sources of out_degree x rate; the mean
    # rate weighted by (out-degree ~ in-degree balance) ~ weighted mean rate
    mean_rate = float((n * FULL_MEAN_RATES).sum() / n.sum())
    return FULL_SYNAPSES * mean_rate      # events per second of model time


def projected(mesh: str, chips: int):
    from benchmarks.strong_scaling import _event_mem_bytes_per_step
    path = os.path.join(ART, f"microcircuit__event__{mesh}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        cell = json.load(f)
    steps = 100.0
    peaks = peaks_for(DRYRUN_DEVICE_KIND)
    comp = cell["flops_per_device"] / steps / peaks.flops_bf16
    mem = _event_mem_bytes_per_step(chips) / peaks.hbm_bw
    coll = (cell["collective_wire_bytes_per_device"] / steps
            / peaks.ici_link_bw)
    lat = {256: 6e-6, 512: 8e-6}[chips]
    rtf = (max(comp, mem, coll) + lat) / 1e-4
    # energy per synaptic event at that RTF
    e_per_event = (CHIP_POWER_W * chips * rtf) / full_scale_event_rate()
    return rtf, e_per_event * 1e6         # uJ


def single_chip_projection():
    """One v5e chip: memory-term bound (tables stream from HBM)."""
    # per step: ~31 spikes x 3876 targets x 9 B (ELL row touch) + state rw
    spikes = 77169 * float((np.array([N_FULL[p] for p in POPULATIONS])
                            * FULL_MEAN_RATES).sum()
                           / sum(N_FULL.values())) * 1e-4
    deliver_bytes = spikes * 3876 * 9
    state_bytes = 77169 * 6 * 4 * 2
    step_s = ((deliver_bytes + state_bytes)
              / peaks_for(DRYRUN_DEVICE_KIND).hbm_bw + 2e-6)
    rtf = step_s / 1e-4
    e = CHIP_POWER_W * rtf / full_scale_event_rate()
    return rtf, e * 1e6


def print_table():
    rows = []
    for name, rtf, e in LITERATURE:
        rows.append(fmt_row(f"table1/{name.replace(' ', '_')}", rtf * 1e6,
                            f"rtf={rtf};uJ_per_event={e}"))
    # measured CPU (down-scaled), through the unified Simulator session
    sim = Simulator(MicrocircuitConfig(
        n_scaling=0.05, k_scaling=0.05, seed=3, spike_budget=256,
        t_presim=0.0))
    res = time_sim(sim, 1000.0)
    rows.append(fmt_row("table1/this_work_cpu_5pct_scale", res.rtf * 1e6,
                        f"rtf={res.rtf:.2f};"
                        f"synapses={sim.connectome.n_synapses}"))
    r1 = single_chip_projection()
    rows.append(fmt_row("table1/this_work_v5e_1chip_projected", r1[0] * 1e6,
                        f"rtf={r1[0]:.3f};uJ_per_event={r1[1]:.3f}"))
    for mesh, chips in (("pod1", 256), ("pod2", 512)):
        pr = projected(mesh, chips)
        if pr:
            rows.append(fmt_row(
                f"table1/this_work_v5e_{chips}chips_projected", pr[0] * 1e6,
                f"rtf={pr[0]:.4f};uJ_per_event={pr[1]:.3f}"))
    for r in rows:
        print(r)


def run_sweep(scales, strategies, t_sim_ms: float, seed: int = 3,
              trials: int = 1, plastic: bool = False,
              roofline: bool = False):
    """Measure RTF for every strategy x scale cell; returns ledger entries.

    The connectome is built once per scale and shared across strategies so
    the sweep measures delivery mechanisms, not instantiation noise.
    ``trials > 1`` runs each cell through ``Simulator.run_batch`` (one
    vmapped device program on the fused backend) and records the
    per-trial RTF mean/std in the v2 ledger fields.

    ``plastic`` additionally measures each cell with pair-STDP composed
    into the fused scan (``rtf/<strategy>+pair_stdp/...`` rows) — the
    static-vs-plastic overhead is the paper-relevant number behind its
    closing argument (learning runs extend over hours and days of
    biological time, so the plastic RTF is what bounds them).  Strategies
    without a live-weight path (``dense``) skip the plastic cell.

    ``roofline`` attaches a per-step roofline to every measured entry
    (``benchmarks/roofline.live_roofline`` folded with the measured step
    time — achieved vs v5e-peak FLOP/s and HBM bytes/s) and adds
    ``rtf/ell+fused/...`` rows measuring the one-kernel step
    (``kernels="fused"``; interpret mode off-TPU) next to the split
    ``ell`` cells, so the fused-vs-split RTF ratio lives in the ledger.
    """
    from benchmarks import roofline as RL
    from repro.core.connectivity import build_connectome
    from repro.core.delivery import get_strategy
    entries = []

    def measure(name, cfg, c, strategy, scale, plasticity=None):
        sim = Simulator(cfg, connectome=c, plasticity=plasticity)
        if trials > 1:
            res = common.time_sim_batch(sim, t_sim_ms, trials)
            derived = (f"rtf={res.rtf_mean:.3f};"
                       f"rtf_std={res.rtf_std:.3f};"
                       f"trials={trials};wall_s={res.wall_s:.2f}")
            rtf = res.rtf_mean
        else:
            res = time_sim(sim, t_sim_ms)
            derived = f"rtf={res.rtf:.3f};wall_s={res.wall_s:.2f}"
            rtf = res.rtf
        entry = common.make_entry(name, strategy=strategy, scale=scale,
                                  result=res, connectome=c)
        if plasticity is not None:
            entry["plasticity"] = plasticity
        pol = sim.sim_config.kernels
        if pol is not None:
            entry["kernels"] = pol.describe()
        if roofline:
            roof = RL.live_roofline(sim)
            entry["roofline"] = RL.with_achieved(
                roof, entry["wall_s"] / entry["n_steps"])
        entries.append(entry)
        print(fmt_row(name, rtf * 1e6, derived))
        return rtf

    for scale in scales:
        c = build_connectome(scale=scale, seed=seed)
        for strategy in strategies:
            cfg = MicrocircuitConfig(scale=scale, strategy=strategy,
                                     seed=seed, t_presim=0.0)
            rtf_static = measure(f"rtf/{strategy}/scale{scale:g}", cfg, c,
                                 strategy, scale)
            fcfg = MicrocircuitConfig(scale=scale, strategy="ell",
                                      seed=seed, t_presim=0.0,
                                      kernels="fused")
            if roofline and strategy == "ell":
                rtf_f = measure(f"rtf/ell+fused/scale{scale:g}", fcfg, c,
                                "ell", scale)
                print(f"# fused step ell/scale{scale:g}: "
                      f"{rtf_f / rtf_static:.2f}x vs split")
            if plastic:
                if not get_strategy(strategy).supports_live_weights:
                    print(f"# rtf/{strategy}+pair_stdp/scale{scale:g}: "
                          f"skipped ({strategy!r} has no live-weight path)")
                    continue
                rtf_p = measure(
                    f"rtf/{strategy}+pair_stdp/scale{scale:g}", cfg, c,
                    strategy, scale, plasticity="pair_stdp")
                print(f"# plastic overhead {strategy}/scale{scale:g}: "
                      f"{rtf_p / rtf_static:.2f}x")
                if roofline and strategy == "ell":
                    measure(f"rtf/ell+fused+pair_stdp/scale{scale:g}",
                            fcfg, c, "ell", scale, plasticity="pair_stdp")
    return entries


def main(argv=None) -> int:
    setup_jax()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sweep", action="store_true",
                    help="measure the strategy x scale RTF sweep")
    ap.add_argument("--scales", default="0.02,0.05",
                    help="comma-separated scales for --sweep")
    ap.add_argument("--strategies", default="event,ell",
                    help="comma-separated delivery strategies for --sweep")
    ap.add_argument("--t-sim", type=float, default=200.0,
                    help="model time per sweep cell (ms)")
    ap.add_argument("--trials", type=int, default=1,
                    help="trials per sweep cell via Simulator.run_batch "
                         "(vmapped on the fused backend); ledger entries "
                         "gain rtf_mean/rtf_std")
    ap.add_argument("--plastic", action="store_true",
                    help="also measure each sweep cell with pair-STDP "
                         "composed in (rtf/<strategy>+pair_stdp/... "
                         "entries) so the ledger records the "
                         "static-vs-plastic RTF overhead; implies --sweep")
    ap.add_argument("--roofline", action="store_true",
                    help="attach per-step roofline numbers (HLO FLOPs/"
                         "bytes, achieved vs v5e peak) to every sweep "
                         "entry and measure the fused one-kernel step "
                         "(rtf/ell+fused/... rows); implies --sweep")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the measured sweep as a ledger JSON")
    ap.add_argument("--replay", default=None, metavar="PATH",
                    help="take entries from an existing ledger instead of "
                         "measuring (compare-only mode)")
    ap.add_argument("--compare", default=None, metavar="PATH", nargs="?",
                    const="BENCH_rtf.json",
                    help="baseline ledger to compare against (default: "
                         "the committed BENCH_rtf.json); exit 3 on "
                         "regression")
    ap.add_argument("--rtol", type=float, default=0.5,
                    help="allowed relative RTF slowdown before a compare "
                         "regression fires (default 0.5 = 50%%)")
    args = ap.parse_args(argv)

    if args.plastic or args.roofline:
        args.sweep = True
    if not (args.sweep or args.replay or args.compare):
        print_table()
        return 0

    if args.replay is not None:
        current = common.load_ledger(args.replay)
    else:
        scales = [float(s) for s in args.scales.split(",") if s]
        strategies = [s for s in args.strategies.split(",") if s]
        entries = run_sweep(scales, strategies, args.t_sim, seed=args.seed,
                            trials=args.trials, plastic=args.plastic,
                            roofline=args.roofline)
        meta = {"t_sim_ms": args.t_sim, "seed": args.seed,
                "trials": args.trials, "plastic": bool(args.plastic),
                "roofline": bool(args.roofline)}
        if args.out:
            current = common.write_ledger(args.out, entries, meta=meta)
            print(f"ledger written: {args.out} ({len(entries)} entries)")
        else:
            current = {"schema": common.BENCH_SCHEMA,
                       "machine": common.machine_metadata(),
                       "entries": entries, "meta": meta}

    if args.compare is not None:
        base_path = args.compare
        if not os.path.exists(base_path):
            print(f"--compare: baseline ledger {base_path!r} not found",
                  file=sys.stderr)
            return 2
        baseline = common.load_ledger(base_path)
        regressions = common.compare_ledgers(baseline, current,
                                             rtol=args.rtol)
        matched = {e["name"] for e in current.get("entries", [])} \
            & {e["name"] for e in baseline.get("entries", [])}
        print(f"compare vs {base_path}: {len(matched)} matched entries, "
              f"{len(regressions)} regression(s) at rtol={args.rtol}")
        for r in regressions:
            note = " [baseline from different machine]" \
                if r["machine_differs"] else ""
            print(f"  REGRESSION {r['name']}: rtf "
                  f"{r['baseline_rtf']:.3f} -> {r['current_rtf']:.3f} "
                  f"({r['ratio']:.2f}x, limit {r['limit']:.3f}){note}",
                  file=sys.stderr)
        if regressions:
            return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
