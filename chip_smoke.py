#!/usr/bin/env python3
"""Bring-up smoke test: the microcircuit on a TPU, through ``Simulator``.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

* **Phase A** — the paper's full width: ``scale=1.0``, ``strategy="ell"``,
  ``kernels="auto"``; 100 ms presim, then 200 ms recorded.  Rates must be
  finite, non-zero and inside the validation bands
  (``repro.validate.reference``); no spike may be dropped.
* **Phase B** — the kernels ``auto`` picks where the ring fits VMEM:
  ``scale=0.25`` resolves to the fused ``lif_deliver`` step with the Pallas
  ELL delivery.  Run static and with ``pair_stdp``, each against
  ``kernels="reference"`` at the same seed over 100 ms of ``pop_counts``.
  The code promises bitwise equality; a break is reported with its first
  diverging step, and the run fails unless the rates agree within the
  validation rate band.
* **Sharded** (``--chips 4``) — the ``sharded`` backend at ``scale=1.0``
  over four devices against the single-chip backend on ``devices[0]``,
  under the deterministic ``dc`` drive, compared the same way.

Everything runs in this one process (a chip belongs to one process).  It
exits non-zero, printing no result, without a TPU or when any check
fails; on success the last line is one JSON object naming the device.
RTFs printed here are smoke readings, not benchmarks.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.launch.runtime import setup_jax  # noqa: E402

SEED = 55


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def host_rss_gib() -> float:
    """Peak resident set of this process so far (Linux reports KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20


def build(tag: str, scale: float):
    from repro.core.connectivity import build_connectome
    t0 = time.perf_counter()
    c = build_connectome(scale=scale, seed=SEED)
    log(f"[{tag}] connectome scale={scale}: n={c.n_total} "
        f"synapses={c.n_synapses} K={c.targets.shape[1]} "
        f"build_s={time.perf_counter() - t0:.3f} "
        f"host_rss_peak_gib={host_rss_gib():.3f}")
    return c


def session(tag: str, c, cfg, **kw):
    """A Simulator on the chip's compiled kernels, warmed for ``t_ms``."""
    from repro.api import Simulator
    t_ms = kw.pop("t_ms")
    t0 = time.perf_counter()
    sim = Simulator(cfg, connectome=c, probes=("pop_counts",), **kw)
    pol = sim.sim_config.kernels
    log(f"[{tag}] backend={sim.backend.name} kernels={pol.describe()} "
        f"spike_budget={sim.sim_config.spike_budget} "
        f"setup_s={time.perf_counter() - t0:.3f}")
    check(not pol.interpret, f"[{tag}] Pallas interpret mode on the chip")
    t0 = time.perf_counter()
    sim.warmup(t_ms)
    log(f"[{tag}] compile_s={time.perf_counter() - t0:.3f}")
    return sim


def simulate(tag: str, sim, c, t_ms: float, every_pop_fires: bool = True):
    """Run, check overflow and rates; return (pop_counts, rates).  Rates
    must be finite, and non-zero in every population (``every_pop_fires``)
    or at least in one."""
    from repro.core.recording import population_rates
    res = sim.run(t_ms)
    counts = np.asarray(res["pop_counts"])
    rates = population_rates(counts, c, sim.sim_config.dt)
    log(f"[{tag}] overflow={res.overflow} wall_s={res.wall_s:.6f} "
        f"rtf={res.rtf:.6f} (smoke reading, not a benchmark)")
    log(f"[{tag}] rates_hz=" + " ".join(
        f"{p}:{r:.4f}" for p, r in zip(pop_names(), rates)))
    check(res.overflow == 0, f"[{tag}] {res.overflow} spikes dropped")
    firing = np.all(rates > 0) if every_pop_fires else np.any(rates > 0)
    check(bool(np.all(np.isfinite(rates)) and firing),
          f"[{tag}] non-finite or silent rates: {rates}")
    return counts, rates


def pop_names():
    from repro.core.params import POPULATIONS
    return POPULATIONS


def compare(tag: str, want, got, want_rates, got_rates) -> bool:
    """Exact ``pop_counts`` equality, else the first diverging step and the
    rate differences; fails unless every rate lies in the validation rate
    band around the reference's.  Returns whether equality held."""
    from repro.validate.reference import rate_band
    diverged = np.flatnonzero(np.any(want != got, axis=1))
    if diverged.size == 0:
        log(f"[{tag}] pop_counts bitwise equal over {want.shape[0]} steps")
    else:
        log(f"[{tag}] pop_counts differ: first diverging step "
            f"{int(diverged[0])} of {want.shape[0]}, {diverged.size} "
            f"steps differ; rate diff (Hz) " + " ".join(
                f"{p}:{g - w:+.4f}"
                for p, w, g in zip(pop_names(), want_rates, got_rates)))
    outside = [p for p, w, g in zip(pop_names(), want_rates, got_rates)
               if not rate_band(w).contains(g)]
    check(not outside, f"[{tag}] rates outside the band around the "
          f"reference's: {outside}")
    return diverged.size == 0


def phase_a(scale: float = 1.0, presim_ms: float = 100.0,
            t_ms: float = 200.0) -> None:
    """The paper's full width through ``kernels='auto'``."""
    from repro.configs.microcircuit import MicrocircuitConfig
    from repro.validate.reference import microcircuit_reference
    c = build("A", scale)
    cfg = MicrocircuitConfig(scale=scale, strategy="ell",
                             t_presim=presim_ms, seed=SEED)
    sim = session("A", c, cfg, kernels="auto", t_ms=t_ms)
    _, rates = simulate("A", sim, c, t_ms)
    bands = microcircuit_reference().rate_hz
    outside = [f"{p}:{r:.3f} not in {b.as_tuple()}"
               for p, r, b in zip(pop_names(), rates, bands)
               if not b.contains(r)]
    check(not outside, f"[A] rates outside the validation bands: {outside}")


def phase_b(scale: float = 0.25, t_ms: float = 100.0) -> None:
    """The fused kernels ``auto`` picks, static and plastic, against the
    XLA reference path."""
    from repro.configs.microcircuit import MicrocircuitConfig
    c = build("B", scale)
    cfg = MicrocircuitConfig(scale=scale, strategy="ell", t_presim=0.0,
                             seed=SEED)
    for plasticity in (None, "pair_stdp"):
        runs = {}
        for mode in ("auto", "reference"):
            tag = f"B/{plasticity or 'static'}/{mode}"
            sim = session(tag, c, cfg, kernels=mode, plasticity=plasticity,
                          t_ms=t_ms)
            if mode == "auto":
                pol = sim.sim_config.kernels
                check((pol.step, pol.deliver) == ("fused", "pallas"),
                      f"[{tag}] auto resolved to {pol.describe()}, not the "
                      f"fused step with Pallas delivery")
            runs[mode] = simulate(tag, sim, c, t_ms)
            del sim
        compare(f"B/{plasticity or 'static'}", runs["reference"][0],
                runs["auto"][0], runs["reference"][1], runs["auto"][1])


def phase_sharded(n_dev: int, scale: float = 1.0,
                  t_ms: float = 100.0) -> None:
    """The ``sharded`` backend over ``n_dev`` devices against the
    single-chip backend on ``devices[0]``, under a drive with no RNG."""
    import jax

    from repro.configs.microcircuit import MicrocircuitConfig
    check(len(jax.devices()) >= n_dev,
          f"[S] {n_dev} devices needed, JAX reports {len(jax.devices())}")
    c = build("S", scale)
    cfg = MicrocircuitConfig(scale=scale, strategy="ell", t_presim=0.0,
                             seed=SEED)
    runs = {}
    for tag, kw in (("S/sharded", dict(backend="sharded", n_devices=n_dev)),
                    ("S/single", dict(backend="fused"))):
        sim = session(tag, c, cfg, stimulus=("dc",), t_ms=t_ms, **kw)
        if kw["backend"] == "sharded":
            per_dev = {}
            for x in sim.backend.tables:
                for sh in x.addressable_shards:
                    per_dev[sh.device.id] = (per_dev.get(sh.device.id, 0)
                                             + sh.data.nbytes)
            total = sum(per_dev.values())
            log(f"[{tag}] table bytes per device: "
                + " ".join(f"dev{d}:{b}" for d, b in sorted(per_dev.items()))
                + f" (total {total})")
            check(len(per_dev) == n_dev
                  and max(per_dev.values()) <= -(-total // n_dev),
                  f"[{tag}] tables not split evenly over {n_dev} devices")
        # the dc drive starts from rest: a layer may not fire this early
        runs[tag] = simulate(tag, sim, c, t_ms, every_pop_fires=False)
        del sim
        gc.collect()
    compare("S", runs["S/single"][0], runs["S/sharded"][0],
            runs["S/single"][1], runs["S/sharded"][1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase over four chips")
    args = ap.parse_args(argv)
    log(f"compile cache: {setup_jax()}")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX reports {dev.platform!r}",
              file=sys.stderr)
        return 2
    log(f"jax {jax.__version__} device {dev.device_kind} "
        f"x{len(jax.devices())} host_rss_peak_gib={host_rss_gib():.3f}")
    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_a()
        gc.collect()
        jax.clear_caches()
        phase_b()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
