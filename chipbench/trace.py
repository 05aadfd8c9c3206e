"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` trace wrote, with
nothing but JAX, into plain interval lists on one clock (nanoseconds):

* device operations: events on the ``XLA Ops`` line of the
  ``/device:TPU:<i>`` plane of each chip the cell uses (``XLA Modules``
  where that line is empty); planes of chips it does not use are left
  out;
* host spans: events on the host plane's lines (``TraceAnnotation`` spans
  of the harness, such as ``chipbench.call`` around each timed call, and
  the profiler's Python function events).

The functions below take those lists, so the tests can hand them small
synthetic traces.
"""
from __future__ import annotations

import glob
import os
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

CALL_SPAN = "chipbench.call"

Interval = Tuple[float, float]


class Event(NamedTuple):
    name: str
    start: float      # ns
    end: float        # ns


class Trace(NamedTuple):
    device_ops: Tuple[Tuple[Event, ...], ...]   # per device plane
    host: Tuple[Event, ...]                      # every host span
    calls: Tuple[Event, ...]                     # the CALL_SPAN spans


def device_planes(planes, device_ids: Optional[Sequence[int]] = None):
    """The ``/device:TPU:<i>`` planes of the chips ``device_ids`` (all
    where None), in trace order."""
    keep = None if device_ids is None else {str(i) for i in device_ids}
    return [p for p in planes if p.name.startswith("/device:TPU:")
            and (keep is None or p.name.split(":")[2] in keep)]


def device_ops(plane, device_lines=("XLA Ops", "XLA Modules")):
    """A device plane's operations: its first line in ``device_lines``
    that has events, sorted by start."""
    lines = {line.name: line for line in plane.lines}
    ops = next(([Event(e.name, e.start_ns, e.end_ns)
                 for e in lines[name].events]
                for name in device_lines if name in lines
                and any(True for _ in lines[name].events)), [])
    return tuple(sorted(ops, key=lambda e: e.start))


def load(log_dir: str, device_ids: Optional[Sequence[int]] = None) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``, keeping the
    device planes of the chips ``device_ids`` (all where None)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = list(ProfileData.from_file(paths[-1]).planes)
    devices = tuple(device_ops(p) for p in device_planes(planes, device_ids))
    host = sorted((Event(e.name, e.start_ns, e.end_ns)
                   for p in planes if p.name.startswith("/host:CPU")
                   for line in p.lines for e in line.events),
                  key=lambda e: e.start)
    calls = tuple(e for e in host if e.name == CALL_SPAN)
    return Trace(devices, tuple(host), calls)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping cover of ``intervals``."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def window(tr: Trace) -> Optional[Interval]:
    """From the start of the first timed call to the end of the last."""
    if not tr.calls:
        return None
    return tr.calls[0].start, tr.calls[-1].end


def busy(tr: Trace, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which an operation ran, averaged
    over the device planes."""
    if not tr.device_ops:
        return 0.0
    return sum(covered(union((e.start, e.end) for e in ops), lo, hi)
               for ops in tr.device_ops) / len(tr.device_ops)


def op_seconds(tr: Trace, lo: float, hi: float,
               match=lambda name: True) -> dict:
    """Device seconds per operation name inside ``[lo, hi]``, summed over
    events (and planes) whose name ``match``es."""
    out: dict = {}
    for ops in tr.device_ops:
        for e in ops:
            if match(e.name):
                d = max(0.0, min(e.end, hi) - max(e.start, lo))
                if d > 0:
                    out[e.name] = out.get(e.name, 0.0) + d * 1e-9
    return out


def host_label(tr: Trace, at: float) -> str:
    """The innermost host span open at ``at`` (``idle`` when none)."""
    best = None
    for e in tr.host:
        if e.start > at:
            break
        if e.end >= at and (best is None or e.start >= best.start):
            best = e
    return best.name if best is not None else "idle"


def idle_gaps(tr: Trace, lo: float, hi: float, top: int = 10) -> list:
    """The longest stretches of ``[lo, hi]`` with no operation on device
    0, each named by what the host was doing in its middle."""
    if not tr.device_ops:
        return []
    merged = union((e.start, e.end) for e in tr.device_ops[0])
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((max(t, lo), min(a, hi)))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:top]
    return [[host_label(tr, (a + b) / 2), (b - a) * 1e-9] for a, b in gaps]
