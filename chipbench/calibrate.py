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

    python3 chipbench/calibrate.py --workload pd14_full_x4.scan20 \
        --reference-only pd14_full_stdp_x4 --traffic scan5 \
        --seeds 1,2,3 --out f.json

``--reference-only`` takes the readings of a sharded plastic
configuration that has no program yet, on the host's CPU
(``reference_device: "host"``), from the state of the cell's program after
its presim.  For each seed: that state, with the plastic weights at the
network's and zero traces, goes through the configuration's presim in the
float32 reference, then through the mix's calls (``check_chunks`` of
them) three ways, each against the float32 reference from the same start:
the reference with its delivered sources reversed (a sound program that
sums in another order), handed over in the sharded plastic layout and read
by ``run.check`` as a benchmark run reads it (its seconds and the host's
peak RSS are the check's cost); the reference in bfloat16 (the control);
and, on the first seed, the sound calls' weights with chip 0's and chip
1's column blocks swapped (a fault).
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
    from chipbench import reference
    n, k = net.targets.shape
    return stand_in_readings(
        tb, reference.consts(net, cfg, shards=shards),
        reference.consts(net, cfg, dtype, shards),
        run.export_state(state, n, k), steps, calls)


def stand_in_readings(tb, c32, program, before: dict, steps: int,
                      calls: int) -> list:
    """``calls`` consecutive calls of the reference under the constants
    ``program`` in the program's place, from the reference leaves
    ``before``, each compared with the reference under ``c32`` from the
    same start."""
    import jax.numpy as jnp

    from chipbench import reference
    out = []
    for _ in range(calls):
        after, counts = reference.chunk(tb, before, steps, program)
        after = {name: (v.astype(before[name].dtype)
                        if name in before else v)
                 for name, v in after.items()}
        out.append(run.rerun(tb, c32, before, after, counts))
        before = {name: after[name] for name in before}
        before["t"] = jnp.asarray(before["t"])
    return out


def plastic_start(static: dict, net) -> dict:
    """A static program's state (reference leaves) as a plastic start:
    the weights at the network's, the sentinel row 0, zero traces."""
    import numpy as np
    n, k = net.targets.shape
    return dict(static,
                w=np.concatenate([net.weights, np.zeros((1, k), np.float32)]),
                x_pre=np.zeros(n, np.float32), x_post=np.zeros(n, np.float32))


def laid_out(st: dict, n_loc: int, weights) -> tuple:
    """The reference leaves ``st`` as a sharded plastic program hands its
    state back (``run.py``'s layout contract), over as many chips as
    ``st`` has keys of ``n_loc`` neurons each: neurons padded to
    ``N_pad``, the ring in one block of ``n_loc`` columns and a dump column
    per chip, and ``weights``, ``st``'s weights already in the sharded
    layout."""
    from types import SimpleNamespace

    import numpy as np
    chips = np.asarray(st["key"]).shape[0]
    n = st["V"].shape[0]
    pad = lambda a: np.concatenate(
        [np.asarray(a), np.zeros(chips * n_loc - n, np.asarray(a).dtype)])
    ring = np.asarray(st["ring"])
    d = ring.shape[0]
    blocks = np.zeros((d, 2, chips, n_loc + 1), ring.dtype)
    blocks[..., :n_loc] = np.concatenate(
        [ring[..., :n], np.zeros((d, 2, chips * n_loc - n), ring.dtype)],
        axis=-1).reshape(d, 2, chips, n_loc)
    sim = SimpleNamespace(
        V=pad(st["V"]), I_ex=pad(st["I_ex"]), I_in=pad(st["I_in"]),
        refrac=pad(st["refrac"]),
        ring=blocks.reshape(d, 2, chips * (n_loc + 1)),
        t=np.asarray(st["t"]), key=np.asarray(st["key"]))
    return sim, SimpleNamespace(weights=weights, x_pre=pad(st["x_pre"]),
                                x_post=pad(st["x_post"]))


def swap_blocks(state: tuple, a: int, b: int) -> tuple:
    """A sharded plastic state with chip ``a``'s and chip ``b``'s weight
    column blocks swapped."""
    from types import SimpleNamespace

    import numpy as np
    sim, plastic = state
    w = np.array(plastic.weights)
    k_loc = w.shape[1] // sim.key.shape[0]
    blk = lambda d: slice(d * k_loc, (d + 1) * k_loc)
    w[:, blk(a)], w[:, blk(b)] = w[:, blk(b)].copy(), w[:, blk(a)].copy()
    return sim, SimpleNamespace(weights=w, x_pre=plastic.x_pre,
                                x_post=plastic.x_post)


def chip_memory(devs) -> list:
    """Each chip's allocator counters, to show what the reference left
    there."""
    keys = ("bytes_in_use", "peak_bytes_in_use", "num_allocs")
    return [{k: (d.memory_stats() or {}).get(k) for k in keys}
            for d in devs]


class RssPeak:
    """The host's resident memory at its highest while the block runs,
    read every ``every`` seconds (the process's own peak also holds its
    set-up's)."""

    def __init__(self, every: float = 0.2):
        import threading
        self.every, self.gib = every, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self) -> None:
        page = os.sysconf("SC_PAGE_SIZE")
        while True:
            with open("/proc/self/statm") as f:
                rss = int(f.read().split()[1]) * page / 2 ** 30
            self.gib = max(self.gib, rss)
            if self._stop.wait(self.every):
                return

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=10)


def reference_only(args, cell, devs, net, sim, counter) -> dict:
    """The readings of ``--reference-only`` (module docstring)."""
    import jax
    import numpy as np

    from chipbench import compare, reference
    pcfg = json.loads((cell.bench / "configs"
                       / f"{args.reference_only}.json").read_text())
    mix = json.loads((cell.bench / "mixes" / f"{args.traffic}.json")
                     .read_text())
    if args.cpu_scale is not None:
        pcfg = dict(pcfg, n_scaling=args.cpu_scale,
                    k_scaling=args.cpu_scale, t_presim_ms=mix["chunk_ms"])
    differ = [key for key in NETWORK_KEYS if pcfg[key] != cell.cfg[key]]
    if differ or not pcfg.get("plasticity"):
        raise SystemExit(f"{args.reference_only} is not a plastic "
                         f"configuration of {cell.name}'s network: {differ}")
    chips = cell.chips
    n, k = net.targets.shape
    steps = int(round(mix["chunk_ms"] / pcfg["dt_ms"]))
    calls = int(mix["check_chunks"])
    cpu = run.host_cpu()
    t = time.perf_counter()
    columns = run.ShardedColumns(net, chips)
    k_loc = columns.least_k_loc()
    report = {"cell": cell.name, "config": args.reference_only,
              "traffic": args.traffic, "scale": pcfg["n_scaling"],
              "device": devs[0].device_kind, "chips": chips, "k_loc": k_loc,
              "columns_s": time.perf_counter() - t, "seeds": []}
    c32 = reference.consts(net, pcfg, shards=chips)
    low = reference.consts(net, pcfg, "bfloat16", chips)
    reordered = c32._replace(reverse_delivery=True)
    with jax.default_device(cpu):
        tb = reference.tables(net, pcfg, cpu)
    for i, seed in enumerate(args.seeds):
        if i:
            sim.reset(run.dynamics_key(seed))
            run.warm(sim, cell.cfg, cell.mix, counter)
        start = plastic_start(run.export_state(sim.state, n, k), net)
        row = {"seed": seed, "chip_memory_before": chip_memory(devs)}
        t = time.perf_counter()
        with jax.default_device(cpu):
            st = jax.device_put(start, cpu)
            for _ in range(int(round(pcfg["t_presim_ms"] / mix["chunk_ms"]))):
                st, _ = reference.chunk(tb, st, steps, c32)
                st = {name: st[name] for name in start}
            jax.block_until_ready(st)
            row["presim_s"] = time.perf_counter() - t
            run.log(f"seed {seed}: presim {row['presim_s']:.3f} s")
            # the sound stand-in, reversed delivery, in the sharded layout
            t = time.perf_counter()
            lay = lambda x: laid_out(x, columns.n_loc,
                                     columns.to_sharded(x["w"], k_loc))
            chain, counts, before = [lay(st)], [], st
            for _ in range(calls):
                after, c = reference.chunk(tb, before, steps, reordered)
                before = {name: after[name] for name in start}
                chain.append(lay(before))
                counts.append(np.asarray(c))
            del before, after
            row["stand_in_s"] = time.perf_counter() - t
            run.log(f"seed {seed}: stand-in {row['stand_in_s']:.3f} s")
        samples = list(zip(chain[:-1], chain[1:], counts))
        t = time.perf_counter()
        with RssPeak() as peak:
            v = run.check(samples, net, pcfg, cell.limits, shards=chips,
                          where="host")
        row.update(check_s=time.perf_counter() - t,
                   check_rss_peak_gib=peak.gib,
                   host_rss_peak_gib=run.host_rss_gib(),
                   sound={"numbers": v["numbers"], "correct": v["correct"],
                          "readings": v["readings"]})
        if i == 0:
            fault = [(a, swap_blocks(b, 0, 1), c) for a, b, c in samples]
            v = run.check(fault, net, pcfg, cell.limits, shards=chips,
                          where="host")
            row["swapped_blocks"] = {"numbers": v["numbers"],
                                     "correct": v["correct"]}
        del samples, chain
        t = time.perf_counter()
        with jax.default_device(cpu):
            r = stand_in_readings(tb, c32, low, st, steps, calls)
        numbers = compare.combine(r)
        row["control"] = {"numbers": numbers, "readings": r,
                          "correct": compare.verdict(numbers, cell.limits),
                          "s": time.perf_counter() - t}
        row["chip_memory_after"] = chip_memory(devs)
        report["seeds"].append(row)
        print(json.dumps({"seed": row}), flush=True)
        write(args.out, report)
    report["host_rss_peak_gib"] = run.host_rss_gib()
    return report


#: Keys that make the network: a plastic configuration read from another
#: cell's program state has to share them.
NETWORK_KEYS = ("populations", "excitatory", "n_full", "conn_probs", "k_ext",
                "bg_rate_hz", "neuron", "synapse", "dt_ms", "n_scaling",
                "k_scaling", "network_seed")


def write(out: str, report: dict) -> None:
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(out).write_text(json.dumps(report, indent=1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu-scale", type=float, default=None)
    ap.add_argument("--reference-only", metavar="CONFIG")
    ap.add_argument("--traffic", help="the mix of --reference-only")
    args = ap.parse_args(argv)
    if (args.seconds is None) == (args.reference_only is None) or (
            (args.traffic is None) != (args.reference_only is None)):
        ap.error("give --seconds, or --reference-only with --traffic")
    args.seeds = [int(s) for s in args.seeds.split(",")]
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
    if args.reference_only:
        run.host_cpu()               # fail before the set-up, not after
    from chipbench import compare, reference
    counter = run.CompileCounter()
    net = run.make_network(cfg)
    seeds = args.seeds
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sim, ovf = run.session(cfg, mix, net, seeds[0], counter, devices=devs)
    if args.reference_only:
        cell.cfg = cfg
        write(args.out, reference_only(args, cell, devs, net, sim, counter))
        print(json.dumps({"device": run.device_info(devs)}))
        return 0
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
    write(args.out, report)
    print(json.dumps({"device": run.device_info(devs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
