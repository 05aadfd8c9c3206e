"""Device time per layer of the step, from a ``--trace 1`` run.

The program runs each layer of its step under a ``jax.named_scope`` and,
when it warms a program, records which of the program's device ops runs
under which layer (``repro.perf.scopes.op_layers()``: HLO instruction
name -> layer).  The trace's op line names each op by its instruction
(``%fusion.64 = f32[...] fusion(...)``), and a loop (``%while``) spans
the ops of its body.  So an op's **self** time is its duration less the
union of the ops nested inside it on its plane, and the self times of a
plane add up to its busy time; each op's self time goes to its layer.

Host spans the program opens around each call (``repro.run``, holding
``repro.dispatch``, ``repro.sync`` and ``repro.overflow``) share the
trace's clock.

The exchange between chips needs no map: a collective is an op of its
own on the device, named after its opcode (``%all-reduce.5 = ...``).

Against a program that records no map, or opens no ``repro.run`` span,
every reader here but the exchange's returns None; against a window with
no collective op, the exchange's does.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as T

#: The program's span around each call of its session.
RUN_SPAN = "repro.run"
#: Opcodes of the exchange between chips; async ones run as a ``-start``
#: and a ``-done`` op.
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "collective-broadcast",
               "reduce-scatter")

_INSTR_RE = re.compile(r"%?([\w.-]+)")


def instruction(event_name: str) -> str:
    """The HLO instruction an op event names: ``%fusion.64 = ...`` ->
    ``fusion.64``."""
    m = _INSTR_RE.match(event_name.strip())
    return m.group(1) if m else event_name


def self_times(ops: Sequence[T.Event], lo: float, hi: float
               ) -> List[Tuple[T.Event, float]]:
    """Each op with its self time inside ``[lo, hi]`` (ns): its duration
    there less the union of the ops nested inside it.  ``ops`` are one
    plane's, sorted by start."""
    children: Dict[int, List[T.Interval]] = {}
    stack: List[int] = []
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start, -ops[i].end))
    for i in order:
        e = ops[i]
        while stack and ops[stack[-1]].end < e.end:
            stack.pop()
        if stack:
            children.setdefault(stack[-1], []).append((e.start, e.end))
        stack.append(i)
    out = []
    for i, e in enumerate(ops):
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        inner = T.covered(T.union(children.get(i, ())), a, b)
        out.append((e, (b - a) - inner))
    return out


_COLLECTIVE_RE = re.compile(
    r"[\s=](?:%s)(?:-start|-done)?\(" % "|".join(COLLECTIVES))


@functools.lru_cache(maxsize=None)
def is_collective(event_name: str) -> bool:
    """Whether an op event is a collective: by the opcode in its text
    (``... all-reduce(...)``), or by its instruction's name, which XLA
    takes from the opcode or from JAX's name for it (``all_gather.14``)."""
    op = instruction(event_name).split(".")[0].replace("_", "-")
    for half in ("-start", "-done"):
        op = op.removesuffix(half)
    return op in COLLECTIVES or bool(_COLLECTIVE_RE.search(event_name))


def layer_seconds(tr: T.Trace, lo: float, hi: float,
                  layers: Dict[str, str]) -> Dict[Optional[str], float]:
    """Device self seconds per layer inside ``[lo, hi]``, averaged over
    the device planes; key None holds the ops in no layer (the loops'
    own time, ops the map lacks or gives no layer)."""
    out: Dict[Optional[str], float] = {}
    for ops in tr.device_ops:
        for e, ns in self_times(ops, lo, hi):
            key = layers.get(instruction(e.name))
            out[key] = out.get(key, 0.0) + ns * 1e-9 / len(tr.device_ops)
    return out


def program_layers() -> Optional[Dict[str, str]]:
    """The program's op -> layer map, or None where it keeps none."""
    try:
        from repro.perf import scopes
    except ImportError:
        return None
    return scopes.op_layers() or None


def _window(run):
    tr = run.trace
    win = T.window(tr) if tr is not None else None
    if win is None or not tr.device_ops or not run.calls:
        return None
    return win


def plane_self_times(run) -> List[List[Tuple[T.Event, float]]]:
    """Each device plane's ops with their self times in the window (ns),
    worked out once a run."""
    cached = getattr(run, "_self_times", None)
    if cached is None:
        lo, hi = _window(run)
        cached = run._self_times = [self_times(ops, lo, hi)
                                    for ops in run.trace.device_ops]
    return cached


def op_self_seconds(run) -> Dict[str, float]:
    """Device self seconds per op name in the window, averaged over the
    device planes (the ``breakdown``'s ranking: a loop keeps only its own
    time, not its body's)."""
    if _window(run) is None:
        return {}
    planes = plane_self_times(run)
    out: Dict[str, float] = {}
    for plane in planes:
        for e, ns in plane:
            out[e.name] = out.get(e.name, 0.0) + ns * 1e-9 / len(planes)
    return out


def exchange_ms(run) -> Optional[float]:
    """Device self time of the collective ops per simulated step (ms),
    averaged over the device planes; None where the window holds none."""
    if _window(run) is None:
        return None
    planes = plane_self_times(run)
    found = [ns for plane in planes for e, ns in plane
             if is_collective(e.name)]
    if not found:
        return None
    return 1e-6 * sum(found) / len(planes) / sum(c.steps for c in run.calls)


def per_step_ms(run, layer: str) -> Optional[float]:
    """Device self time under ``layer`` per simulated step (ms)."""
    layers, win = program_layers(), _window(run)
    if layers is None or win is None or layer not in layers.values():
        return None
    secs = layer_seconds(run.trace, *win, layers)
    return 1e3 * secs.get(layer, 0.0) / sum(c.steps for c in run.calls)


def unscoped_share(run) -> Optional[float]:
    """Device self time in no layer over busy time (%)."""
    layers, win = program_layers(), _window(run)
    if layers is None or win is None:
        return None
    busy = T.busy(run.trace, *win)
    if busy <= 0:
        return None
    return 100.0 * layer_seconds(run.trace, *win, layers).get(
        None, 0.0) * 1e9 / busy


def call_idle_ms(run) -> Optional[float]:
    """Device-idle time inside the program's ``repro.run`` spans of the
    window, per span (ms), averaged over the device planes."""
    win = _window(run)
    if win is None:
        return None
    lo, hi = win
    spans = [e for e in run.trace.host
             if e.name == RUN_SPAN and e.start >= lo and e.end <= hi]
    if not spans:
        return None
    idle = 0.0
    for ops in run.trace.device_ops:
        merged = T.union((e.start, e.end) for e in ops)
        idle += sum((s.end - s.start) - T.covered(merged, s.start, s.end)
                    for s in spans)
    return idle / len(run.trace.device_ops) / len(spans) * 1e-6
