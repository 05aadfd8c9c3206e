#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload pd14_full.scan20 \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds 10 --out f.json

In one process, at the cell's own size and call length (or, with
``--cpu-scale s``, on the CPU at ``s`` of the neurons and the in-degree,
with as many virtual devices as the cell has chips): for each seed,
the program's session (built once, reset to the seed's key, past its
presim) runs a short window like a benchmark run, and the sampled calls
are rerun by the plain reference, giving the sound readings.  For each
control seed, the control then runs from where that window ended: the
reference itself in the program's place, computed in bfloat16 (the
nearest precision below the configuration's float32), its calls compared
with the float32 reference from the same start.  The program's own
bfloat16 state path is tried once as well and its outcome recorded,
except on a configuration that names a backend: the sharded backend
keeps float32 state whatever ``state_dtype`` says.  A cell on
several chips draws the reference's drive per chip, as its program does.
Nothing here runs in a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import run  # noqa: E402


def control_readings(tb, net, cfg: dict, state, steps: int, calls: int,
                     dtype: str = "bfloat16", shards: int = 1) -> list:
    """``calls`` consecutive calls of the reference in ``dtype`` from the
    program's ``state``, each compared with the float32 reference from
    the same start (both drawing the drive over ``shards`` chips)."""
    import jax.numpy as jnp

    from chipbench import reference
    n, k = net.targets.shape
    c32 = reference.consts(net, cfg, shards=shards)
    low = reference.consts(net, cfg, dtype, shards)
    before = run.export_state(state, n, k)
    out = []
    for _ in range(calls):
        after, counts = reference.chunk(tb, before, steps, low)
        after = {name: (v.astype(before[name].dtype)
                        if name in before else v)
                 for name, v in after.items()}
        out.append(run.rerun(tb, c32, before, after, counts))
        before = {name: after[name] for name in before}
        before["t"] = jnp.asarray(before["t"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu-scale", type=float, default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    cfg, mix = cell.cfg, cell.mix
    if args.cpu_scale is None:
        devs = run.require_chips(cell.chips)
        run.setup_jax()
    else:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_"
                                   f"count={cell.chips}")
        import jax
        devs = jax.devices()[:cell.chips]
        cfg = dict(cfg, n_scaling=args.cpu_scale, k_scaling=args.cpu_scale)
    from chipbench import compare, reference
    counter = run.CompileCounter()
    net = run.make_network(cfg)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sim, ovf = run.session(cfg, mix, net, seeds[0], counter, devices=devs)
    tb = reference.tables(net, cfg)
    report = {"cell": cell.name, "scale": cfg["n_scaling"],
              "device": devs[0].device_kind, "sound": [], "control": [],
              "program_bf16": None}
    steps = int(round(mix["chunk_ms"] / cfg["dt_ms"]))
    for i, seed in enumerate(seeds):
        if i:
            sim.reset(run.dynamics_key(seed))
            ovf = run.warm(sim, cfg, mix, counter)
        sampler = run.Sampler(int(mix["check_chunks"]), seed)
        calls, attempted, failed, span = run.window(
            sim, mix, args.seconds, sampler, counter, ovf)
        v = run.check(sampler.kept, net, cfg, cell.limits, tb, cell.chips)
        row = {"seed": seed, "calls": attempted, "failed": failed,
               "rtf": (span[1] - span[0]) / (sum(c.steps for c in calls) * cfg["dt_ms"]
                              * 1e-3) if calls else None,
               "numbers": v["numbers"], "readings": v["readings"]}
        report["sound"].append(row)
        print(json.dumps({"sound": row}), flush=True)
        if seed in controls:
            t = time.perf_counter()
            r = control_readings(tb, net, cfg, sim.state, steps,
                                 int(mix["check_chunks"]),
                                 shards=cell.chips)
            crow = {"seed": seed, "numbers": compare.combine(r),
                    "readings": r, "s": time.perf_counter() - t}
            report["control"].append(crow)
            print(json.dumps({"control": crow}), flush=True)
    del sim
    if "backend" in cfg:
        report["program_bf16"] = {"error": f"not tried: the {cfg['backend']}"
                                  " backend has no bfloat16 state path"}
    else:
        try:
            bf, ovf = run.session(cfg, mix, net, seeds[0], counter,
                                  program_dtype="bfloat16", devices=devs)
            sampler = run.Sampler(int(mix["check_chunks"]), seeds[0])
            run.window(bf, mix, args.seconds, sampler, counter, ovf)
            v = run.check(sampler.kept, net, cfg, cell.limits, tb)
            report["program_bf16"] = {"numbers": v["numbers"]}
        except Exception as e:      # the program's own path may not run
            report["program_bf16"] = {"error": repr(e)[:2000]}
            traceback.print_exc()
    print(json.dumps({"program_bf16": report["program_bf16"]}), flush=True)
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1))
    print(json.dumps({"device": run.device_info(devs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
