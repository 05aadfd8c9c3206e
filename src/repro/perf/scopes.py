"""Named layer scopes of the simulation step, and the map from compiled
device ops to them.

Every piece of the step's work runs under one of :data:`LAYERS`
(``jax.named_scope``), so the compiled program's ``op_name`` metadata
names its layer: ``.../while/body/deliver/scatter-add``.  Scopes are
metadata only; they change no op of the compiled program.

A profiler trace names device ops by their HLO instruction (``%fusion.64
= f32[...] fusion(...)``), not by their scope.  :func:`record` reads the
optimized HLO of a compiled program once, when the backend warms it, and
keeps ``instruction name -> layer`` for the ops the device timeline
shows: the top-level instructions of the entry computation and of the
computations it runs as loop bodies, loop conditions, branches and
calls.  :func:`op_layers` hands that map to a trace reader.
"""
from __future__ import annotations

import re
import threading
from typing import Dict, Optional

import jax

from repro.perf.hlo_analysis import parse_module

#: The step's layers, each the name of a ``jax.named_scope``.
LAYERS = ("drive", "lif_update", "deliver", "fused_step", "plasticity",
          "probes")

#: An ``op_name`` that is JAX's name stack (XLA passes name some ops they
#: make after themselves, e.g. ``reduce_window_sum``).
_JAX_OP_NAME_RE = re.compile(r'op_name="(jit\([^"]*)"')
_NAME_RE = re.compile(r"%([\w.-]+)")
_ENTRY_RE = re.compile(r"^ENTRY %?([\w.-]+)", re.M)
_SUBCOMP_RE = re.compile(
    r"(?:body|condition|to_apply|calls)=%?([\w.-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
#: Instructions whose called computations run as ops of their own (a
#: fusion, reduce or scatter runs its computation inside one op).
_RUNS_COMPS = {"while", "conditional", "call"}
#: Instructions that never run on the device.
_NOT_RUN = {"parameter", "constant", "tuple", "get-tuple-element",
            "bitcast", "after-all", "opt-barrier"}

# The process-wide map, merged over every program recorded so far.  A
# name two programs give different layers maps to None (no layer).
_LOCK = threading.Lock()
_OP_LAYERS: Dict[str, Optional[str]] = {}


def scope(layer: str):
    """``jax.named_scope`` for one of :data:`LAYERS`."""
    if layer not in LAYERS:
        raise ValueError(f"unknown layer {layer!r}; layers: {LAYERS}")
    return jax.named_scope(layer)


def layer_of_op_name(op_name: str) -> Optional[str]:
    """The innermost component of an ``op_name`` path that is a layer."""
    for part in reversed(op_name.split("/")):
        if part in LAYERS:
            return part
    return None


def _one(layers) -> Optional[str]:
    """The layer a set of candidates agrees on, if it agrees on one."""
    found = {x for x in layers if x is not None}
    return found.pop() if len(found) == 1 else None


def _operands(ins):
    """Names of an instruction's operands: the ``%`` names inside its
    ``opcode(...)`` (a TPU module's types hold parentheses too)."""
    line = ins.line
    start = line.index(f" {ins.op}(") + len(ins.op) + 2
    depth, end = 1, len(line)
    for j in range(start, len(line)):
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        if depth == 0:
            end = j
            break
    return _NAME_RE.findall(line, start, end)


def _layer(ins, comps, by_name, memo) -> Optional[str]:
    """An instruction's layer from its own ``op_name``.  XLA gives some
    instructions it makes none: a fusion then takes the layer the ops of
    its body agree on (constants aside: XLA shares one constant between
    scopes), else, as any other op (a layout copy, say), the layer its
    operands agree on."""
    if ins.name in memo:
        return memo[ins.name]
    memo[ins.name] = None                       # cycle guard
    m = _JAX_OP_NAME_RE.search(ins.line)
    layer = layer_of_op_name(m.group(1)) if m else None
    if m is None and ins.op == "fusion":
        layer = _one(layer_of_op_name(m.group(1))
                     for callee in _SUBCOMP_RE.findall(ins.line)
                     for sub in comps.get(callee, ())
                     if sub.op not in _NOT_RUN
                     for m in [_JAX_OP_NAME_RE.search(sub.line)] if m)
    if m is None and layer is None:
        layer = _one(_layer(by_name[a], comps, by_name, memo)
                     for a in _operands(ins) if a in by_name)
    memo[ins.name] = layer
    return layer


def program_op_layers(hlo: str) -> Dict[str, Optional[str]]:
    """``instruction name -> layer`` (None: in no layer) for the ops the
    device timeline shows of one compiled program's optimized HLO text:
    the top-level instructions of the entry computation and of every
    computation it runs as a loop body, loop condition, branch or call
    (not fusion bodies: a fusion is one op on the device)."""
    comps = parse_module(hlo)
    m = _ENTRY_RE.search(hlo)
    todo = [m.group(1)] if m else []
    seen = set(todo)
    out: Dict[str, Optional[str]] = {}
    while todo:
        instrs = comps.get(todo.pop(), ())
        by_name = {ins.name: ins for ins in instrs}
        memo: Dict[str, Optional[str]] = {}
        for ins in instrs:
            subs = [] if ins.op not in _RUNS_COMPS else (
                _SUBCOMP_RE.findall(ins.line)
                + [b.strip().lstrip("%")
                   for mb in _BRANCHES_RE.findall(ins.line)
                   for b in mb.split(",")])
            for sub in subs:
                if sub in comps and sub not in seen:
                    seen.add(sub)
                    todo.append(sub)
            if ins.op not in _NOT_RUN:
                out[ins.name] = _layer(ins, comps, by_name, memo)
    return out


def record(compiled) -> None:
    """Merge the op map of a compiled program (``jax.stages.Compiled``)
    into the process-wide map.  Called once per warmed program, never on
    a timed call; reads the executable's text and compiles nothing.  A
    backend that gives no text records nothing."""
    text = compiled.as_text()
    if text is None:
        return
    found = program_op_layers(text)
    with _LOCK:
        for name, layer in found.items():
            if _OP_LAYERS.get(name, layer) != layer:
                layer = None
            _OP_LAYERS[name] = layer


def op_layers() -> Dict[str, str]:
    """``instruction name -> layer`` over every program recorded since
    the last :func:`reset`; names in no layer, or given different layers
    by two programs, are left out."""
    with _LOCK:
        return {k: v for k, v in _OP_LAYERS.items() if v is not None}


def reset() -> None:
    """Forget every recorded program."""
    with _LOCK:
        _OP_LAYERS.clear()
