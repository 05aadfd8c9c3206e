"""End-to-end driver for the paper's experiment: simulate the microcircuit
for a span of biological time and report the realtime factor + activity
statistics (paper's Fig. 1 protocol: 0.1 s discarded transient, then the
timed simulation phase) — declared through the ``Experiment`` API.

    PYTHONPATH=src python examples/microcircuit_sim.py --scale 0.05 \
        --t-sim 1000 --strategy event

Scenario files run verbatim (and CLI flags can be skipped entirely):

    ... --scenario examples/scenarios/thalamic_pulses.json

Stimulation protocols and multi-trial statistics:

    ... --thalamic --trials 4          # pulsed L4/L6 drive, vmapped trials
    ... --dc                           # equivalent-mean DC instead of Poisson

Long runs can be chunked and checkpointed:

    ... --t-sim 60000 --chunk 10000 --checkpoint-dir ckpt
"""
import argparse
import dataclasses
import time

import numpy as np

from repro.api import Experiment
from repro.configs.microcircuit import MicrocircuitConfig
from repro.launch.runtime import setup_jax


def build_experiment(args) -> Experiment:
    if args.scenario:
        exp = Experiment.from_json(args.scenario)
        overrides = {}
        if args.trials > 1:
            overrides["trials"] = args.trials
        if args.validate or args.validate_json:
            overrides["validate"] = True
        return dataclasses.replace(exp, **overrides) if overrides else exp

    stimulus = []
    if args.dc:
        stimulus.append({"kind": "dc"})
    else:
        stimulus.append("poisson_background")
    if args.thalamic:
        stimulus.append({"kind": "thalamic_pulses",
                         "start_ms": args.thalamic_start,
                         "interval_ms": args.thalamic_interval})
    return Experiment(
        model=MicrocircuitConfig(
            n_scaling=args.scale, k_scaling=args.scale, t_sim=args.t_sim,
            t_presim=args.t_presim, strategy=args.strategy, seed=args.seed),
        stimulus=stimulus,
        plasticity="pair_stdp" if args.stdp else None,
        duration_ms=args.t_sim,
        trials=args.trials,
        validate=bool(args.validate or args.validate_json),
        sample_per_pop=args.sample_per_pop,
        backend=args.backend,
        name="microcircuit-cli")


def main():
    setup_jax()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default=None, metavar="PATH",
                    help="run a repro.experiment/v1 scenario JSON (CLI "
                         "model/stimulus flags are ignored)")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--t-sim", type=float, default=1000.0,
                    help="model time (ms); the paper uses 10000")
    ap.add_argument("--t-presim", type=float, default=100.0)
    ap.add_argument("--strategy", default="event",
                    choices=["event", "dense", "ell"])
    ap.add_argument("--backend", default="fused",
                    choices=["fused", "instrumented", "sharded"])
    ap.add_argument("--trials", type=int, default=1,
                    help="independent trials via run_batch (vmapped on "
                         "the fused backend); statistics pool across "
                         "trials")
    ap.add_argument("--dc", action="store_true",
                    help="replace the Poisson background with its "
                         "equivalent-mean DC current")
    ap.add_argument("--thalamic", action="store_true",
                    help="add the PD-2014 thalamic pulse protocol")
    ap.add_argument("--thalamic-start", type=float, default=700.0)
    ap.add_argument("--thalamic-interval", type=float, default=1000.0)
    ap.add_argument("--chunk", type=float, default=0.0,
                    help="chunk size (ms); 0 = single fused run "
                         "(single-trial only)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist the session every chunk")
    ap.add_argument("--kernels", default=None,
                    choices=["auto", "fused", "split", "reference"],
                    help="KernelPolicy mode (default: auto — fused "
                         "one-kernel step on TPU, phase-split elsewhere; "
                         "Pallas runs in interpret mode on CPU: slow, "
                         "bit-exact)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="deprecated: same as --kernels split")
    ap.add_argument("--stdp", action="store_true",
                    help="compose the pair_stdp plasticity rule (E->E "
                         "pair STDP) into the loop")
    ap.add_argument("--validate", action="store_true",
                    help="stream spike statistics (CV-ISI, pairwise "
                         "correlation) during the run and judge them "
                         "against the published microcircuit bands")
    ap.add_argument("--validate-json", default=None, metavar="PATH",
                    help="write the ValidationReport JSON here")
    ap.add_argument("--sample-per-pop", type=int, default=100,
                    help="neurons sampled per population for --validate")
    ap.add_argument("--seed", type=int, default=55)
    args = ap.parse_args()

    exp = build_experiment(args)
    sim_kwargs = {}
    if args.kernels is not None:
        sim_kwargs.update(kernels=args.kernels)
    elif args.use_kernels:
        sim_kwargs.update(kernels="split")

    t0 = time.perf_counter()
    if args.chunk > 0:
        # chunked long-run path: drive the Simulator session the
        # experiment declares directly (run_chunked + checkpointing are
        # session-level features)
        if exp.trials > 1:
            raise SystemExit("--chunk runs a single chunked session; "
                             "drop --trials")
        sim = exp.make_simulator(**sim_kwargs)
        c = sim.connectome
        print(f"instantiation: {time.perf_counter() - t0:.1f}s "
              f"({c.n_total} neurons, {c.n_synapses:,} synapses)")
        sim.warmup(args.chunk)
        res = sim.run_chunked(exp.duration_ms, chunk_ms=args.chunk,
                              checkpoint_dir=args.checkpoint_dir)
        report = res.validate() if exp.validate else None
    else:
        result = exp.run(warmup=True, **sim_kwargs)
        c = result.connectome
        print(f"instantiation+run: {time.perf_counter() - t0:.1f}s "
              f"({c.n_total} neurons, {c.n_synapses:,} synapses, "
              f"{len(result.trials)} trial(s), "
              f"vmapped={result.batch.vmapped})")
        res = (result.trials[0] if exp.trials == 1
               else result.batch.pooled())
        report = result.report
        if exp.trials > 1:
            print(f"per-trial RTF: mean={result.batch.rtf_mean:.2f} "
                  f"std={result.batch.rtf_std:.2f}")

    summ = res.summary()
    print(f"T_model={res.t_model_ms / 1e3:.1f}s  T_wall={res.wall_s:.1f}s  "
          f"RTF={res.rtf:.2f}  ({'sub' if res.rtf < 1 else 'super'}-realtime)")
    print("rates (Hz):", np.round(summ["rates_hz"], 2))
    print("synchrony:", round(summ["synchrony"], 2),
          " overflow:", res.overflow)

    if report is not None:
        print(report.table())
        if args.validate_json:
            report.to_json(args.validate_json)
            print("report written:", args.validate_json)
        if not report.passed:
            raise SystemExit(4)


if __name__ == "__main__":
    main()
