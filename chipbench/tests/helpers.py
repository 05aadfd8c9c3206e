"""A tiny cell in a temporary checkout: the real BENCHMARK.json and
chipbench data files, plus a small configuration of the same model."""
from __future__ import annotations

import json
import pathlib
import shutil

from chipbench import run  # noqa: F401  (puts the program on sys.path)

REPO = pathlib.Path(__file__).resolve().parents[2]
#: The tests' own mix: short calls, several of them checked.
SHORT = {"chunk_ms": 1.0, "check_chunks": 8}


def tiny_root(tmp: pathlib.Path, config: str = "pd14_full",
              traffic: str = "short1", limits_of: str = "pd14_full.scan20",
              scale: float = 0.02, mix: dict = None,
              chips: int = 1) -> tuple:
    """A checkout in ``tmp`` with cell ``tiny.<traffic>`` on ``chips``:
    ``config`` at ``scale`` of its neurons and in-degree, a 1 ms presim,
    under the real mix ``traffic`` (or a new one written from ``mix``, by
    default the tests' own :data:`SHORT` where no such mix exists) and the
    limits of cell ``limits_of``.  Returns (root, cell name)."""
    bench = tmp / "chipbench"
    for d in ("configs", "mixes", "cells", "metrics"):
        shutil.copytree(REPO / "chipbench" / d, bench / d)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs" / f"{config}.json").read_text())
    cfg.update(name="tiny", n_scaling=scale, k_scaling=scale,
               t_presim_ms=1.0)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    if mix is None and not (bench / "mixes" / f"{traffic}.json").exists():
        mix = SHORT
    if mix is not None:
        (bench / "mixes" / f"{traffic}.json").write_text(json.dumps(mix))
    name = f"tiny.{traffic}"
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "chipbench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": "tiny",
                              "traffic": traffic, "chips": chips,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copy(bench / "cells" / f"{limits_of}.json",
                bench / "cells" / f"{name}.json")
    return tmp, name


def run_tiny(tmp, seed: int = 2 ** 33 + 5, seconds: float = 0.3,
             trace: bool = False, **kw) -> dict:
    import jax

    root, name = tiny_root(tmp, **kw)
    cell = run.load_cell(name, root)
    return run.run_cell(cell, seed, seconds, trace,
                        devices=jax.devices()[:cell.chips])
