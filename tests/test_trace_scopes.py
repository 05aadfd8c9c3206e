"""Layer scopes of the step, the op -> layer map, and the session spans.

On the CPU at a small scale, for the split static, fused static and fused
plastic runners of ``FusedBackend``:

* every layer the path runs names ops in the compiled program's
  ``op_name`` metadata;
* the map ``repro.perf.scopes.op_layers()`` is built when the program is
  warmed, covers its top-level instructions and gives the scan's own
  ``while`` no layer;
* scopes are metadata only: the program without them has the same op
  census and gives bitwise the same results, and a warm call compiles
  nothing and leaves the map as the warmup built it.

``Simulator.run`` under ``jax.profiler`` writes its ``repro.*`` spans on
the host plane.
"""
import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.analysis import RecompileGuard
from repro.api.simulator import Simulator
from repro.configs.microcircuit import MicrocircuitConfig
from repro.perf import scopes
from repro.perf.hlo_analysis import op_census

#: runner -> (kernels, plasticity, the layers its step runs)
RUNNERS = {
    "split_static": ("split", None,
                     {"drive", "lif_update", "deliver", "probes"}),
    "fused_static": ("fused", None,
                     {"drive", "fused_step", "deliver", "probes"}),
    "fused_plastic": ("fused", "pair_stdp",
                      {"drive", "fused_step", "plasticity", "deliver",
                       "probes"}),
}
T_MS = 5.0          # 50 steps


def _sim(kernels, plasticity, **kw):
    mc = MicrocircuitConfig(n_scaling=0.01, k_scaling=0.01, t_presim=0.0,
                            strategy="ell", kernels=kernels)
    return Simulator(mc, plasticity=plasticity,
                     key=jax.random.PRNGKey(11), **kw)


def _compiled(sim):
    return sim.backend._aot.peek((sim._steps(T_MS), tuple(sim.probes)))


@pytest.fixture(scope="module", params=sorted(RUNNERS))
def warmed(request):
    """A session per runner, warmed for T_MS with the map reset first;
    and the same session built with every scope a no-op."""
    kernels, plasticity, want = RUNNERS[request.param]
    scopes.reset()
    sim = _sim(kernels, plasticity)
    sim.warmup(T_MS)
    layers = scopes.op_layers()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _sim(kernels, plasticity)
        bare.warmup(T_MS)
    scopes.reset()
    return request.param, sim, bare, layers, want


def test_every_layer_the_path_runs_is_in_the_metadata(warmed):
    name, sim, bare, _, want = warmed
    text = _compiled(sim).as_text()
    found = {scopes.layer_of_op_name(m)
             for m in re.findall(r'op_name="([^"]*)"', text)}
    assert want <= found, name
    # no layer the path does not run
    assert found - {None} == want, name
    # without scopes only a kernel named like its layer names one
    bare_text = _compiled(bare).as_text()
    assert {scopes.layer_of_op_name(m)
            for m in re.findall(r'op_name="([^"]*)"', bare_text)} \
        - {None} <= {"lif_update"}


def test_scopes_are_metadata_only(warmed):
    _, sim, bare, _, _ = warmed
    assert op_census(_compiled(sim).as_text()) == \
        op_census(_compiled(bare).as_text())


def test_warmup_maps_the_top_level_ops(warmed):
    name, sim, _, layers, want = warmed
    text = _compiled(sim).as_text()
    assert set(layers.values()) == want, name
    program = scopes.program_op_layers(text)
    assert layers == {k: v for k, v in program.items() if v is not None}
    # the scan's own loop is in the map's domain but in no layer
    entry = op_census(text)["entry"]
    scan = re.search(rf"ENTRY %?{re.escape(entry)} .*?^\s+(?:ROOT )?"
                     rf"%?(while[\w.-]*) = ", text, re.M | re.S).group(1)
    assert scan in program and program[scan] is None
    assert scan not in layers


def test_run_is_bitwise_unchanged_and_compiles_nothing(warmed):
    _, sim, bare, layers, _ = warmed
    scopes.reset()
    scopes.record(_compiled(sim))
    with RecompileGuard(0, caches=sim.backend.caches(),
                        what="a warm call"):
        got = sim.run(T_MS)
        got2 = sim.run(T_MS)
    assert scopes.op_layers() == layers     # built at warmup only
    ref = bare.run(T_MS)
    ref2 = bare.run(T_MS)
    for a, b in ((got, ref), (got2, ref2)):
        np.testing.assert_array_equal(np.asarray(a.data["pop_counts"]),
                                      np.asarray(b.data["pop_counts"]))
    for x, y in zip(jax.tree.leaves(sim.state), jax.tree.leaves(bare.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(np.asarray(got2.data["pop_counts"]).sum()) > 0
    scopes.reset()


def test_batch_warmup_records_the_vmapped_program():
    sim = _sim("reference", None)
    scopes.reset()
    try:
        sim.warmup_batch(1.0, 2, include_presim=False)
        assert {"drive", "lif_update", "deliver", "probes"} <= set(
            scopes.op_layers().values())
        with RecompileGuard(0, caches=sim.backend.caches(),
                            what="a warm batch"):
            sim.run_batch(1.0, 2)
    finally:
        scopes.reset()


def test_a_name_two_programs_disagree_on_maps_to_no_layer():
    def program(layer):
        class Compiled:
            def as_text(self):
                return (
                    "ENTRY %main (p: f32[4]) -> f32[4] {\n"
                    "  %p = f32[4] parameter(0)\n"
                    "  %fusion.3 = f32[4] fusion(f32[4] %p), kind=kLoop, "
                    "calls=%fused, metadata={op_name=\"jit(run)/while/"
                    f"body/{layer}/add\"}}\n"
                    "  ROOT %copy.4 = f32[4] copy(f32[4] %fusion.3)\n"
                    "}\n")
        return Compiled()
    scopes.reset()
    try:
        scopes.record(program("deliver"))
        # the copy has no op_name and takes its operand's layer
        assert scopes.op_layers() == {"fusion.3": "deliver",
                                      "copy.4": "deliver"}
        scopes.record(program("deliver"))
        assert scopes.op_layers() == {"fusion.3": "deliver",
                                      "copy.4": "deliver"}
        scopes.record(program("probes"))
        assert scopes.op_layers() == {}
        # a backend that gives no text records nothing
        scopes.reset()
        scopes.record(type("NoText", (), {"as_text": lambda self: None})())
        assert scopes.op_layers() == {}
    finally:
        scopes.reset()


def test_unknown_layer_is_refused():
    with pytest.raises(ValueError, match="layer"):
        scopes.scope("record")


def test_session_spans_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    mc = MicrocircuitConfig(n_scaling=0.01, k_scaling=0.01, t_presim=1.0,
                            strategy="ell", kernels="reference")
    sim = Simulator(mc, key=jax.random.PRNGKey(3))
    sim.warmup(1.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim.run(1.0)            # runs the presim first
        sim.run(1.0)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted(((e.name, e.start_ns, e.end_ns)
                    for p in ProfileData.from_file(path[-1]).planes
                    if p.name.startswith("/host:CPU")
                    for line in p.lines for e in line.events
                    if e.name.startswith("repro.")), key=lambda s: s[1])
    runs = [s for s in spans if s[0] == "repro.run"]
    assert len(runs) == 2
    names = lambda run: [s[0] for s in spans
                         if run[1] <= s[1] and s[2] <= run[2]
                         and s is not run]
    # the presim holds its own overflow read
    assert names(runs[0]) == ["repro.presim", "repro.overflow",
                              "repro.dispatch", "repro.sync",
                              "repro.overflow"]
    assert names(runs[1]) == ["repro.dispatch", "repro.sync",
                              "repro.overflow"]


#: The shape of a TPU module: layouts with parentheses inside tuple types,
#: fusions XLA made late without ``op_name``, a constant shared between
#: scopes, an op named by an XLA pass, and a reduction's computation.
TPU_MODULE = """\
HloModule jit_run

%fused_rem (param_0: s32[8], param_1: s32[]) -> s32[8] {
  %param_0 = s32[8]{0:T(1024)} parameter(0)
  %constant.46 = s32[]{:T(128)} constant(46), metadata={op_name="jit(run)/while/body/closed_call/lif_update/rem"}
  %broadcast.1 = s32[8]{0:T(1024)} broadcast(%constant.46), dimensions={}, metadata={op_name="jit(run)/while/body/closed_call/deliver/rem"}
  ROOT %rem.1 = s32[8]{0:T(1024)} remainder(%param_0, %broadcast.1), metadata={op_name="jit(run)/while/body/closed_call/deliver/rem"}
}

%add_comp (x: s32[], y: s32[]) -> s32[] {
  %x = s32[]{:T(128)} parameter(0)
  %y = s32[]{:T(128)} parameter(1)
  ROOT %sum.9 = s32[]{:T(128)} add(%x, %y), metadata={op_name="jit(run)/while/body/closed_call/deliver/cumsum"}
}

%body.1 (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p = (s32[]{:T(128)}, s32[8]{0:T(1024)S(1)}) parameter(0)
  %gte.0 = s32[]{:T(128)} get-tuple-element(%p), index=0
  %gte.1 = s32[8]{0:T(1024)S(1)} get-tuple-element(%p), index=1
  %fusion.64 = s32[8]{0:T(1024)S(1)} fusion(s32[8]{0:T(1024)} %gte.1), kind=kCustom, calls=%fused_rem, metadata={op_name="jit(run)/while/body/closed_call/deliver/gather"}
  %remainder_fusion.2 = s32[8]{0:T(1024)S(1)} fusion(%fusion.64, %gte.0), kind=kLoop, calls=%fused_rem
  %copy.14 = s32[8]{0:T(8,128)S(1)} copy(%remainder_fusion.2)
  %reduce-window.31 = s32[8]{0:T(1024)} reduce-window(%copy.14, %gte.0), window={size=8}, to_apply=%add_comp
  %sum_fusion.5 = s32[8]{0:T(1024)} fusion(%reduce-window.31), kind=kLoop, calls=%fused_rem, metadata={op_name="reduce_window_sum"}
  %add.1 = s32[]{:T(128)} add(%gte.0, %gte.0), metadata={op_name="jit(run)/while/body/add"}
  ROOT %t.1 = (s32[]{:T(128)}, s32[8]{0:T(1024)S(1)}) tuple(%add.1, %sum_fusion.5)
}

%cond.1 (p.2: (s32[], s32[8])) -> pred[] {
  %p.2 = (s32[]{:T(128)}, s32[8]{0:T(1024)S(1)}) parameter(0)
  %gte.2 = s32[]{:T(128)} get-tuple-element(%p.2), index=0
  %constant.9 = s32[]{:T(128)} constant(20)
  ROOT %lt.1 = pred[]{:T(512)} compare(%gte.2, %constant.9), direction=LT, metadata={op_name="jit(run)/while/cond/lt"}
}

ENTRY %main.2 (a: s32[8]) -> s32[8] {
  %a = s32[8]{0:T(1024)} parameter(0)
  %constant.0 = s32[]{:T(128)} constant(0)
  %t.0 = (s32[]{:T(128)}, s32[8]{0:T(1024)S(1)}) tuple(%constant.0, %a)
  %while.53 = (s32[]{:T(128)}, s32[8]{0:T(1024)S(1)}) while(%t.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(run)/while"}
  ROOT %out.1 = s32[8]{0:T(1024)} get-tuple-element(%while.53), index=1
}
"""


def test_the_map_reads_a_tpu_module():
    layers = scopes.program_op_layers(TPU_MODULE)
    assert layers == {
        "while.53": None,                   # the scan itself
        "fusion.64": "deliver",
        # no op_name: the ops of its body agree, the shared constant aside
        "remainder_fusion.2": "deliver",
        "copy.14": "deliver",               # a layout copy: its operand's
        "reduce-window.31": "deliver",
        "sum_fusion.5": "deliver",          # named by an XLA pass
        "add.1": None,                      # the loop counter
        "lt.1": None,                       # the loop condition
    }
