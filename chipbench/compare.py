"""The comparison that decides ``correct``.

For each sampled timed call, the program's state before the call is handed
to the plain reference (:mod:`chipbench.reference`), which runs the same
number of steps.  Three numbers are compared, each with a limit of its own
from the cell's file under ``chipbench/cells/``:

``counts_gap``
    The summed absolute difference of the per-step, per-population spike
    counts the program returned, over the reference's total spike count.
``state_gap``
    The worst leaf of the state after the call (membrane potentials,
    synaptic currents, refractory counters, delay ring and, with
    plasticity, weights and traces): the norm of the program's difference
    from the reference, over the norm of the reference's change during the
    call.  A leaf the call leaves unchanged reads 1; one it computes as the
    reference does reads 0.
``clock_gap``
    Entries of the step counter and PRNG key that differ (exact: limit 0).
    Where the program draws its drive per chip the key is ``[chips, 2]``,
    and every entry counts; a key of another shape differs in all of its
    entries.

A reference step with more spikes than its budget cannot vouch for the
call; such a run is not correct either (``ref_overflow``).
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

LEAVES = ("V", "I_ex", "I_in", "refrac", "ring", "w", "x_pre", "x_post")
NUMBERS = ("counts_gap", "state_gap", "clock_gap")


def _f64(x) -> np.ndarray:
    return np.asarray(x).astype(np.float64).ravel()


def _differ(a, b) -> float:
    """Entries of ``a`` and ``b`` that differ (all of them where the shapes
    do)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return float(max(a.size, b.size))
    return float((a != b).sum())


def sample_gaps(before: dict, after: dict, counts, ref_after: dict,
                ref_counts) -> Dict[str, float]:
    """The raw readings of one sampled call."""
    counts = np.asarray(counts, np.int64)
    ref_counts = np.asarray(ref_counts, np.int64)
    out = {"counts_abs": float(np.abs(counts - ref_counts).sum()),
           "counts_ref": float(ref_counts.sum())}
    for leaf in LEAVES:
        if leaf not in before:
            continue
        a, b, r = _f64(before[leaf]), _f64(after[leaf]), _f64(ref_after[leaf])
        change = np.linalg.norm(r - a)
        gap = np.linalg.norm(b - r)
        out[f"gap_{leaf}"] = float(gap / change) if change > 0 else (
            0.0 if gap == 0 else float("inf"))
    out["clock"] = (_differ(after["t"], ref_after["t"])
                    + _differ(after["key"], ref_after["key"]))
    out["ref_over"] = float(np.asarray(ref_after["over"]))
    return out


def combine(samples: Iterable[dict]) -> Dict[str, float]:
    """The compared numbers over every sampled call."""
    samples = list(samples)
    if not samples:
        return {"counts_gap": float("inf"), "state_gap": float("inf"),
                "clock_gap": float("inf"), "ref_overflow": 0.0}
    ref = sum(s["counts_ref"] for s in samples)
    diff = sum(s["counts_abs"] for s in samples)
    gaps = [v for s in samples for k, v in s.items() if k.startswith("gap_")]
    return {
        "counts_gap": diff / ref if ref > 0 else (0.0 if diff == 0
                                                   else float("inf")),
        "state_gap": (float("nan") if any(np.isnan(gaps))
                      else max(gaps)),
        "clock_gap": sum(s["clock"] for s in samples),
        "ref_overflow": sum(s["ref_over"] for s in samples),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is within its limit (``clock_gap`` and
    ``ref_overflow`` exactly 0)."""
    if numbers["ref_overflow"] > 0:
        return False
    return all(numbers[k] <= limits[k] for k in NUMBERS)
