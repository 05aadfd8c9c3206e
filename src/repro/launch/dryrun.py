import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (strategy x mesh) cell.

For each cell the full-scale sharded microcircuit step is lowered with
ShapeDtypeStruct inputs (nothing is allocated), compiled against the
production mesh, and the compiled artifact is mined for:
  * memory_analysis()  — per-device argument/output/temp bytes (fits-HBM proof)
  * cost_analysis()    — per-device HLO FLOPs and bytes accessed
  * the post-GSPMD HLO — per-collective byte counts (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute)
Results land in artifacts/dryrun/<arch>__<shape>__<mesh>.json; the roofline
benchmark (benchmarks/roofline.py) consumes them.

Shapes are the delivery strategies: ``event`` lowers the NEST ownership
scheme under shard_map (explicit spike all-gather), ``dense`` the delay-
binned W[D, N, N] under pjit (2-D sharded weight matmul).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch microcircuit \
      --shape event --mesh pod1
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS
from repro.launch.mesh import make_production_mesh

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-collective-kind (count, result bytes) from post-GSPMD HLO."""
    out = {}
    for shape_str, kind in _COLL_RE.findall(hlo_text):
        b = _shape_bytes(shape_str)
        c, tot = out.get(kind, (0, 0))
        out[kind] = (c + 1, tot + b)
    return {k: {"count": c, "bytes": b} for k, (c, b) in out.items()}


def wire_bytes(stats: dict) -> float:
    """Approx bytes crossing links per device per step.

    all-reduce counts 2x (reduce-scatter + all-gather phases); gather-like
    collectives count their result size. (DESIGN.md section 7: factors are
    the dominant-term approximation, not per-ring exact counts.)
    """
    total = 0.0
    for kind, s in stats.items():
        f = 2.0 if kind == "all-reduce" else 1.0
        total += f * s["bytes"]
    return total


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_microcircuit(strategy: str, multi_pod: bool):
    """Dry-run the paper's model itself: full-scale microcircuit, sharded.

    event: NEST ownership scheme under shard_map (explicit spike all-gather);
    dense: delay-binned W[D, N, N] under pjit (2-D sharded weight matmul).
    Lowers a 100-step (10 ms biological time) sim chunk.
    """
    from repro.core import distributed as DD
    from repro.core import params as MP
    from repro.core.neuron import NeuronParams, Propagators

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    prop = Propagators.make(NeuronParams(), 0.1)
    n = sum(MP.N_FULL.values())                       # 77169
    n_syn = int(MP.synapse_numbers(
        np.array([MP.N_FULL[p] for p in MP.POPULATIONS]), MP.CONN_PROBS,
        np.array([MP.N_FULL[p] for p in MP.POPULATIONS]), 1.0).sum())
    n_exc = sum(MP.N_FULL[p] for p in MP.POPULATIONS[:MP.N_EXC_POPS])
    d_ring = 46
    w_ext = MP.psc_from_psp(0.15, NeuronParams())
    meta = {"params": n_syn, "active_params": n_syn}

    if strategy == "event":
        n_pad = -(-n // 512) * 512                    # divides 256 and 512
        lam = n_syn / n / n_dev
        k_loc = int(lam + 8 * lam ** 0.5 + 4)
        sim = DD.make_sharded_step(
            mesh, {"n_loc": n_pad // n_dev}, prop, n_exc=n_exc, w_ext=w_ext,
            bg_rate=8.0, dt=0.1, spike_budget=512, n_steps=100)
        state = DD.abstract_state(n_pad, n_dev, d_ring)
        tables = DD.abstract_sharded_tables({}, n_dev, k_loc, n_pad)
        with mesh:
            lowered = jax.jit(sim, donate_argnums=(0,)).lower(state, tables,
                                                              ())
    else:
        n_pad = -(-n // 512) * 512          # silent-neuron padding
        sim = DD.make_dense_step(
            mesh, prop, n=n_pad, n_exc=n_exc, w_ext=w_ext, bg_rate=8.0,
            dt=0.1, n_steps=100)
        state, W, aux = DD.abstract_dense(n_pad, d_ring)
        st_sh, w_sh, aux_sh = DD.dense_shardings(mesh, state, W, aux)
        with mesh:
            jf = jax.jit(sim, in_shardings=(st_sh, w_sh, aux_sh),
                         out_shardings=(st_sh, None), donate_argnums=(0,))
            lowered = jf.lower(state, W, aux)
    return lowered, meta, mesh


def run_cell(arch: str, shape_name: str, mesh_name: str,
             out_dir: str = ART_DIR, force: bool = False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    key = f"{arch}__{shape_name}__{mesh_name}"
    path = os.path.join(out_dir, key + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    multi_pod = mesh_name == "pod2"
    if arch != "microcircuit":
        raise KeyError(f"unknown arch {arch!r}; the LM dry-run cells were "
                       f"excised (see CHANGES.md) — known: {list(ARCH_IDS)}")
    t0 = time.time()
    lowered, meta, mesh = lower_microcircuit(shape_name, multi_pod)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware analysis (cost_analysis counts scan bodies once)
    from repro.perf.hlo_analysis import analyze_hlo
    hc = analyze_hlo(hlo)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_devices": mesh.devices.size,
        "params": meta["params"], "active_params": meta["active_params"],
        "flops_per_device": hc["flops_per_device"],
        "bytes_accessed_per_device": hc["hbm_bytes_per_device"],
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "collectives": hc["collectives"],
        "cpu_bf16_promotion_bytes": hc.get("cpu_bf16_promotion_bytes", 0.0),
        "collective_top_tags": hc.get("collective_top_tags", {}),
        "collective_wire_bytes_per_device":
            hc["collective_wire_bytes_per_device"],
        "xla_cost_analysis": {
            "flops_body_once": float(cost.get("flops", 0.0)),
            "bytes_body_once": float(cost.get("bytes accessed", 0.0)),
        },
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "pod1", "pod2"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    meshes = [args.mesh] if args.mesh else ["pod1", "pod2"]
    n_ok = n_fail = 0
    for arch in archs:
        shapes = [args.shape] if args.shape else ["event", "dense"]
        for shape in shapes:
            for mesh_name in meshes:
                key = f"{arch}__{shape}__{mesh_name}"
                try:
                    r = run_cell(arch, shape, mesh_name, force=args.force)
                    gb = (r["memory"]["argument_bytes"]
                          + r["memory"]["temp_bytes"]) / 2 ** 30
                    print(f"OK   {key:55s} flops/dev={r['flops_per_device']:.3e} "
                          f"mem/dev={gb:.2f}GiB "
                          f"coll={r['collective_wire_bytes_per_device']:.3e}B "
                          f"compile={r.get('compile_s', 0)}s", flush=True)
                    n_ok += 1
                except Exception as e:  # noqa: BLE001
                    print(f"FAIL {key}: {e}", flush=True)
                    traceback.print_exc()
                    n_fail += 1
    print(f"\ndry-run: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
