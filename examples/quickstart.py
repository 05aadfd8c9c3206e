"""Quickstart: declare and run a microcircuit experiment in 20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.api import Experiment
from repro.configs.microcircuit import MicrocircuitConfig
from repro.launch.runtime import setup_jax


def main():
    setup_jax()
    # 5 % of the full network (77k neurons / 300M synapses at scale 1.0),
    # with van-Albada DC compensation so firing rates stay realistic.
    exp = Experiment(
        model=MicrocircuitConfig(scale=0.05,        # n & k scaling in one knob
                                 seed=55,
                                 strategy="event",  # delivery: event|dense|ell
                                 t_presim=100.0),   # discarded transient
        stimulus=("poisson_background",),           # the paper's default drive
        probes=("pop_counts",),
        duration_ms=500.0,                          # 0.5 s of model time
        name="quickstart")

    result = exp.run()                              # -> ExperimentResult
    res = result.trials[0]
    c = result.connectome
    print(f"network: {c.n_total} neurons, {c.n_synapses} synapses")

    summary = res.summary()
    print(f"RTF = {res.rtf:.2f} (wall {res.wall_s:.1f}s incl. compile)")
    print("population rates (Hz):")
    for pop, rate, target in zip(
            ("L23E", "L4E", "L5E", "L6E", "L23I", "L4I", "L5I", "L6I"),
            summary["rates_hz"], summary["target_rates_hz"]):
        print(f"  {pop:5s} {rate:6.2f}  (full-scale reference {target:.2f})")
    print(f"spike-budget overflows: {res.overflow} (must be 0)")

    # the same experiment serializes to a shareable scenario file:
    #   exp.to_json("my_scenario.json")
    #   PYTHONPATH=src python -m repro.api my_scenario.json


if __name__ == "__main__":
    main()
