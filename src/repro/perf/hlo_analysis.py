"""Trip-count-aware cost analysis of post-GSPMD HLO text.

``compiled.cost_analysis()`` counts every computation ONCE — a scan over 32
layer groups contributes 1/32 of its true FLOPs (and a grad-accumulation
loop another 1/8).  This analyzer walks the call graph instead:

  * while ops carry ``known_trip_count`` in backend_config; a computation's
    execution count = sum over call sites of caller_count x trips,
  * dot FLOPs  = 2 x |result| x |contracting dims|, scaled by count;
    elementwise FLOPs (reported separately) = 1 x |result| for the
    arithmetic op set, counted inside fusion bodies too,
  * HBM bytes  = (result + operand bytes) of *top-level* ops (entry, while
    bodies, conditionals), scaled by count.  Ops inside fusion computations
    are excluded — the fusion op itself accounts for the HBM traffic, which
    is exactly the fusion contract,
  * collective bytes = result bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, scaled by count
    (all-reduce counted 2x: RS + AG phases).

All numbers are per device (the module is the SPMD-partitioned one).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\) -> .* \{\s*$")
# The result type runs to the first " opcode(": a TPU module's tuple
# types hold parentheses of their own (``(s32[]{:T(128)}, ...)``).
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT )?%?([\w.-]+) = (\(.*?\)|[\w]+\[[^\]]*\]"
    r"(?:\{[^}]*\})?)\s+([\w-]+)\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:calls|body|to_apply)=%?([\w.-]+)")
_COND_RE = re.compile(r"condition=%?([\w.-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SKIP_BYTES = {"parameter", "tuple", "get-tuple-element", "constant",
               "bitcast", "after-all", "opt-barrier", "partition-id"}

#: elementwise arithmetic ops counted as 1 FLOP per result element (a
#: roofline-grade estimate; transcendentals cost more on real hardware,
#: but within an order of magnitude).  Matters for dot-free programs —
#: a spiking-network step is elementwise + scatter, so the ``dot``-only
#: count reads zero and the compute term vanishes from the roofline.
_EW_FLOP_OPS = {
    "add", "subtract", "multiply", "divide", "remainder", "power",
    "maximum", "minimum", "clamp", "compare", "select",
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "sqrt", "rsqrt", "cbrt",
    "negate", "abs", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "cosine", "sine", "atan2",
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _shape_elems(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _operand_names(line: str):
    """Operand instruction names of an HLO line.

    Handles both operand syntaxes XLA emits: bare (``dot(%a, %b)``) and
    typed (``dot(f32[32,64]{1,0} %a, ...)``) — operand references are the
    ``%``-prefixed tokens (shape strings contain commas, so a plain
    comma-split is wrong).
    """
    ops = re.findall(r"\(([^)]*)\)", line)
    if not ops:
        return []
    names = re.findall(r"%([\w.-]+)", ops[0])
    if names:
        return names
    # bare un-prefixed names (plain comma-separated list)
    return [a.strip() for a in ops[0].split(",") if a.strip()]


class Instr:
    __slots__ = ("name", "type_str", "op", "line")

    def __init__(self, name, type_str, op, line):
        self.name, self.type_str, self.op, self.line = name, type_str, op, line


def parse_module(hlo: str):
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo.splitlines():
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            comps[cur].append(Instr(mi.group(1), mi.group(2), mi.group(3),
                                    line))
    return comps


def analyze_hlo(hlo: str) -> dict:
    comps = parse_module(hlo)
    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line)
            if m:
                entry = m.group(1)
    if entry is None:                                   # fall back: last comp
        entry = list(comps)[-1]

    # call graph: comp -> [(callee, multiplier, via_fusion)]
    edges = defaultdict(list)
    fused = set()
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.op == "while":
                trips = 1
                mt = _TRIP_RE.search(ins.line)
                if mt:
                    trips = int(mt.group(1))
                body = _CALL_RE.search(ins.line)
                cond = _COND_RE.search(ins.line)
                if body:
                    edges[cname].append((body.group(1), trips))
                if cond:
                    edges[cname].append((cond.group(1), trips + 1))
            elif ins.op == "conditional":
                mb = _BRANCH_RE.search(ins.line)
                if mb:
                    for b in mb.group(1).split(","):
                        edges[cname].append((b.strip().lstrip("%"), 1))
            elif ins.op in ("fusion", "call", "reduce", "scatter", "sort",
                            "map", "reduce-window", "select-and-scatter",
                            "all-reduce", "reduce-scatter", "custom-call"):
                for callee in _CALL_RE.findall(ins.line):
                    edges[cname].append((callee, 1))
                    if ins.op == "fusion":
                        fused.add(callee)

    # propagate execution counts from ENTRY
    count: Dict[str, float] = defaultdict(float)
    count[entry] = 1.0
    order = [entry]
    seen = {entry}
    i = 0
    while i < len(order):
        c = order[i]
        i += 1
        for callee, mult in edges.get(c, ()):
            if callee not in comps:
                continue
            count[callee] += count[c] * mult
            if callee not in seen:
                seen.add(callee)
                order.append(callee)
    # NOTE: simple accumulation over a DAG visited in BFS order can under-
    # count if a callee is reached before all its callers are final; iterate
    # to a fixed point instead (call graphs are acyclic, so this converges).
    for _ in range(len(comps)):
        changed = False
        new = defaultdict(float)
        new[entry] = 1.0
        for c in order:
            for callee, mult in edges.get(c, ()):
                if callee in comps:
                    new[callee] += new.get(c, 0.0) * mult
        for k in set(new) | set(count):
            if abs(new.get(k, 0) - count.get(k, 0)) > 0.5:
                changed = True
        count = new
        if not changed:
            break

    flops = 0.0
    ew_flops = 0.0
    hbm = 0.0
    coll = defaultdict(lambda: {"count": 0.0, "bytes": 0.0})
    coll_tags = defaultdict(float)
    tag_re = re.compile(r'op_name="([^"]*)"')
    # XLA *CPU* has no native bf16 dot: it inserts f32 converts of the
    # operands, and hoists loop-invariant (weight) converts out of scans —
    # phantom f32 weight copies that do not exist on TPU (native bf16 MXU).
    # Quantified here so memory reports can be TPU-adjusted.
    bf16_promo = 0.0
    # entry-level hoisted dtype-conversion fusions of loop-invariant tensors
    # (params or casts thereof); >64 MB only so activation casts don't count
    promo_re = re.compile(
        r"= (?:f32|bf16)\[[\d,]*\][^=]*fusion\(%[\w.-]+\),"
        r" kind=kLoop, calls=%wrapped_convert")
    for cname, instrs in comps.items():
        mult = count.get(cname, 0.0)
        if mult == 0.0:
            continue
        shapes = {i.name: i.type_str for i in instrs}
        for ins in instrs:
            if ins.op == "dot":
                res = 1
                for d in _shape_dims(ins.type_str):
                    res *= d
                contract = 1
                mc = _CONTRACT_RE.search(ins.line)
                args = _operand_names(ins.line)
                lhs_name = args[0] if args else None
                if mc and lhs_name and lhs_name in shapes:
                    lhs_dims = _shape_dims(shapes[lhs_name])
                    for d in mc.group(1).split(","):
                        if d and int(d) < len(lhs_dims):
                            contract *= lhs_dims[int(d)]
                flops += mult * 2.0 * res * contract
            # elementwise FLOPs are counted *everywhere* (fusion bodies
            # included) — fusion reduces memory traffic, not arithmetic
            if ins.op in _EW_FLOP_OPS:
                ew_flops += mult * _shape_elems(ins.type_str)
            base_op = ins.op.replace("-start", "")
            if base_op in _COLLECTIVES:
                b = _shape_bytes(ins.type_str)
                factor = 2.0 if base_op == "all-reduce" else 1.0
                coll[base_op]["count"] += mult
                coll[base_op]["bytes"] += mult * b * factor
                mtag = tag_re.search(ins.line)
                if mtag:
                    # keep a coarse tag: last two path components
                    parts = mtag.group(1).split("/")
                    tag = "/".join(parts[-2:])[:80]
                else:
                    tag = "untagged"
                coll_tags[f"{base_op}|{tag}"] += mult * b * factor
            if (ins.op == "fusion" and cname == entry
                    and promo_re.search(ins.line)):
                b = _shape_bytes(ins.type_str)
                if b > 64 << 20:
                    bf16_promo += b
            if cname not in fused and ins.op not in _SKIP_BYTES \
                    and not ins.op.endswith("-done"):
                b = _shape_bytes(ins.type_str)
                for a in _operand_names(ins.line):
                    if a in shapes:
                        b += _shape_bytes(shapes[a])
                hbm += mult * b

    top_tags = dict(sorted(coll_tags.items(), key=lambda kv: -kv[1])[:12])
    return {
        "flops_per_device": flops,
        "elementwise_flops_per_device": ew_flops,
        "hbm_bytes_per_device": hbm,
        "collectives": {k: dict(v) for k, v in coll.items()},
        "collective_wire_bytes_per_device": sum(
            v["bytes"] for v in coll.values()),
        "collective_top_tags": top_tags,
        "cpu_bf16_promotion_bytes": bf16_promo,
    }


# ---------------------------------------------------------------------------
# Structural op census (the repro.analysis HLO contract checks)
# ---------------------------------------------------------------------------

_CUSTOM_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')


def op_census(hlo: str) -> dict:
    """Structural facts of an HLO module, for contract assertions.

    Unlike :func:`analyze_hlo` (a trip-count-weighted *cost* model) this
    is a plain census of what the module is made of:

    * ``entry_whiles`` — while ops in the ENTRY computation.  A fused
      step that lowered correctly has exactly one (the ``lax.scan``);
      more means the step body escaped fusion or a second loop crept in,
    * ``custom_call_targets`` — target -> count over the whole module.
      Host callbacks (``xla_python_*_callback``-style targets) must not
      appear in the hot program: each one is a device->host sync per
      invocation,
    * ``converts`` — dtype-conversion ops module-wide (fusion-internal
      included).  A bounded count pins the mixed-precision surface: a
      jump means something started promoting per step,
    * ``f64_tensors`` — instructions whose result type mentions ``f64``
      (the dtype-discipline contract at the HLO level, where nothing can
      hide behind an allowlist),
    * ``ops`` — total op histogram, for reports.
    """
    comps = parse_module(hlo)
    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line)
            if m:
                entry = m.group(1)
    if entry is None and comps:
        entry = list(comps)[-1]

    ops: Dict[str, int] = defaultdict(int)
    custom_targets: Dict[str, int] = defaultdict(int)
    converts = 0
    f64 = 0
    for instrs in comps.values():
        for ins in instrs:
            ops[ins.op] += 1
            if ins.op == "convert":
                converts += 1
            if "f64[" in ins.type_str:
                f64 += 1
            if ins.op == "custom-call":
                mt = _CUSTOM_TARGET_RE.search(ins.line)
                custom_targets[mt.group(1) if mt else "<unknown>"] += 1
    entry_whiles = sum(1 for ins in comps.get(entry, ())
                       if ins.op == "while")
    return {
        "entry": entry,
        "entry_whiles": entry_whiles,
        "custom_call_targets": dict(sorted(custom_targets.items())),
        "converts": converts,
        "f64_tensors": f64,
        "ops": dict(sorted(ops.items())),
    }
