"""Trip-count-aware HLO analyzer: synthetic-module unit tests + a live one."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.perf.hlo_analysis import analyze_hlo

SYNTH = """\
HloModule test

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} constant(0)
  %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot.1), to_apply=%add
  ROOT %t = (s32[], f32[8,16]) tuple(%i, %ar)
}

%cond (p2: (s32[], f32[8,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %i0 = s32[] constant(0)
  %t0 = (s32[], f32[8,16]) tuple(%i0, %a)
  %w2 = f32[16,4]{1,0} constant(0)
  %loop = (s32[], f32[8,16]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  %out = f32[8,16]{1,0} get-tuple-element(%loop), index=1
  %dot.2 = f32[8,4]{1,0} dot(%out, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %pad = f32[8,16]{1,0} parameter(0)
}
"""


def test_while_trip_count_multiplies_flops():
    r = analyze_hlo(SYNTH)
    # body dot: 2*8*16*16 = 4096 flops x 10 trips; entry dot: 2*8*4*16 = 1024
    assert r["flops_per_device"] == 10 * 4096 + 1024, r["flops_per_device"]


def test_collectives_counted_with_trips():
    r = analyze_hlo(SYNTH)
    ar = r["collectives"]["all-reduce"]
    assert ar["count"] == 10
    # 8*16*4 bytes x 2 (RS+AG) x 10 trips
    assert ar["bytes"] == 8 * 16 * 4 * 2 * 10


def test_elementwise_flops_counted_with_trips():
    """tanh in the live scan body below is elementwise; on the synthetic
    module the only _EW_FLOP_OPS instruction is... none — assert 0 there,
    then pin trip-weighted counting on a module with an add in the body."""
    assert analyze_hlo(SYNTH)["elementwise_flops_per_device"] == 0
    synth_ew = SYNTH.replace(
        "%ar = f32[8,16]{1,0} all-reduce(%dot.1), to_apply=%add",
        "%s = f32[8,16]{1,0} add(%dot.1, %x)\n"
        "  %ar = f32[8,16]{1,0} all-reduce(%s), to_apply=%add")
    r = analyze_hlo(synth_ew)
    # one add of 8x16 elements x 10 trips
    assert r["elementwise_flops_per_device"] == 10 * 8 * 16


def test_live_module_flops_match_manual():
    """Analyzer on a real compiled scan: flops ~= trips x per-iter matmul."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jnp.ones((32, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    r = analyze_hlo(hlo)
    expect = 7 * 2 * 32 * 64 * 64
    assert 0.9 * expect <= r["flops_per_device"] <= 1.2 * expect, \
        (r["flops_per_device"], expect)


def test_parse_module_reads_tpu_tuple_types():
    """A TPU module's tuple types carry layouts with parentheses."""
    from repro.perf.hlo_analysis import parse_module
    text = (
        "ENTRY %main (a: f32[8]) -> f32[8] {\n"
        "  %a = f32[8]{0:T(1024)} parameter(0)\n"
        "  %w.1 = (s32[]{:T(128)}, f32[8]{0:T(1024)S(1)}) while(%t), "
        "condition=%c, body=%b\n"
        "  ROOT %o = f32[8]{0:T(1024)} get-tuple-element(%w.1), index=1\n"
        "}\n")
    ops = [(i.name, i.op) for i in parse_module(text)["main"]]
    assert ops == [("a", "parameter"), ("w.1", "while"),
                   ("o", "get-tuple-element")]
