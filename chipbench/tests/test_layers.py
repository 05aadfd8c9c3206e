"""Device time per layer on small synthetic traces (CPU only)."""
from types import SimpleNamespace

import pytest

from chipbench import layers as L
from chipbench import trace as T
from chipbench.run import metric_reader
from repro.perf import scopes

from .helpers import REPO

E = T.Event

#: One call: a scan (``%while.5``) enclosing two steps of three body ops,
#: then a copy out of the loop; a gap while the host reads back.
OPS = (E("%while.5 = (s32[]) while(...)", 10, 90),
       E("%fusion.1 = f32[8] fusion(...)", 12, 22),
       E("%sort.2 = s32[8] sort(...)", 22, 30),
       E("%rng.3 = u32[8] fusion(...)", 30, 34),
       E("%fusion.1 = f32[8] fusion(...)", 50, 60),
       E("%sort.2 = s32[8] sort(...)", 60, 68),
       E("%rng.3 = u32[8] fusion(...)", 68, 72),
       E("%copy.4 = f32[8] copy(...)", 90, 95))
LAYERS = {"fusion.1": "deliver", "sort.2": "deliver", "rng.3": "drive"}


def trace(ops=OPS):
    calls = (E(T.CALL_SPAN, 0, 100),)
    host = calls + (E(L.RUN_SPAN, 2, 99), E("repro.dispatch", 3, 9),
                    E("repro.sync", 9, 96), E("repro.overflow", 96, 99))
    return T.Trace(device_ops=(ops,),
                   host=tuple(sorted(host, key=lambda e: e.start)),
                   calls=calls)


def cell_run(tr=None, steps=2):
    return SimpleNamespace(trace=trace() if tr is None else tr,
                           calls=[SimpleNamespace(steps=steps)])


def compiled(layers):
    """A compiled program (as ``scopes.record`` reads it) whose entry
    holds one instruction per ``name -> layer`` of ``layers``."""
    body = "".join(
        f"  %{name} = s32[8] sort(s32[8] %p), metadata={{op_name="
        f"\"jit(run)/while/body/{layer}/sort\"}}\n"
        for name, layer in layers.items())
    text = (f"ENTRY %main (p: s32[8]) -> s32[8] {{\n"
            f"  %p = s32[8] parameter(0)\n{body}}}\n")
    return SimpleNamespace(as_text=lambda: text)


@pytest.fixture
def program_map():
    """The program's op map, as a warmup would leave it."""
    scopes.reset()
    scopes.record(compiled(LAYERS))
    assert scopes.op_layers() == LAYERS
    yield
    scopes.reset()


def test_instruction_name_from_the_op_line():
    assert L.instruction("%fusion.64 = f32[7099640]{0} fusion(%a)") == \
        "fusion.64"
    assert L.instruction("lif_deliver_plastic.12") == \
        "lif_deliver_plastic.12"


def test_self_time_of_a_while_excludes_its_body():
    got = {L.instruction(e.name) + f"@{e.start}": ns
           for e, ns in L.self_times(OPS, 0, 100)}
    # the loop's own time: 80 ns less the 44 ns of its body ops
    assert got["while.5@10"] == 80 - 44
    assert got["fusion.1@12"] == 10 and got["copy.4@90"] == 5
    # self times add up to the busy time, not to twice it
    assert sum(got.values()) == T.busy(trace(), 0, 100) == 85


def test_self_time_is_clipped_to_the_window():
    got = dict((L.instruction(e.name) + f"@{e.start}", ns)
               for e, ns in L.self_times(OPS, 0, 55))
    assert got["while.5@10"] == 45 - 22 - 5    # body ops inside [10, 55]
    assert got["fusion.1@50"] == 5
    assert "copy.4@90" not in got


def test_attribution_through_a_given_map():
    secs = L.layer_seconds(trace(), 0, 100, LAYERS)
    assert secs == pytest.approx({"deliver": 36e-9, "drive": 8e-9,
                                  None: 41e-9})


def test_readers_per_step_and_unscoped(program_map):
    run = cell_run()
    read = lambda name: metric_reader(REPO / "chipbench", name)(run)
    assert read("deliver_ms") == pytest.approx(36e-9 * 1e3 / 2)
    assert read("drive_ms") == pytest.approx(8e-9 * 1e3 / 2)
    assert read("unscoped_share") == pytest.approx(100 * 41 / 85)
    # a layer with no op in the map has nothing to read
    assert read("plasticity_ms") is None
    # layers and unscoped time add up to the busy time
    total = (sum(read(n) for n in ("deliver_ms", "drive_ms")) * 2 * 1e-3
             + read("unscoped_share") / 100 * 85e-9)
    assert total == pytest.approx(85e-9)


def test_an_ambiguous_name_counts_as_unscoped():
    """Two warmed programs that give ``sort.2`` different layers: the
    name maps to no layer, and its time is unscoped."""
    scopes.reset()
    try:
        scopes.record(compiled({"fusion.1": "deliver", "sort.2": "deliver"}))
        scopes.record(compiled({"fusion.1": "deliver", "sort.2": "probes"}))
        assert scopes.op_layers() == {"fusion.1": "deliver"}
        secs = L.layer_seconds(trace(), 0, 100, scopes.op_layers())
        assert secs[None] == pytest.approx((36 + 16 + 8 + 5) * 1e-9)
    finally:
        scopes.reset()


def test_call_idle_inside_the_programs_run_spans():
    # repro.run spans [2, 99]; the device is busy over [10, 95]
    assert L.call_idle_ms(cell_run()) == pytest.approx((97 - 85) * 1e-6)
    two = trace()._replace(host=trace().host + (E(L.RUN_SPAN, 200, 210),))
    two = two._replace(calls=(E(T.CALL_SPAN, 0, 100),
                              E(T.CALL_SPAN, 150, 250)))
    assert L.call_idle_ms(cell_run(two)) == pytest.approx(
        (12 + 10) / 2 * 1e-6)


def test_readers_return_nothing_against_a_program_without_scopes():
    scopes.reset()
    no_spans = trace()._replace(host=trace().calls)
    for name in ("deliver_ms", "lif_update_ms", "fused_step_ms",
                 "plasticity_ms", "drive_ms", "probes_ms",
                 "unscoped_share"):
        assert metric_reader(REPO / "chipbench", name)(cell_run()) is None
    assert metric_reader(REPO / "chipbench", "call_idle_ms")(
        cell_run(no_spans)) is None
    for name in ("deliver_ms", "unscoped_share", "call_idle_ms"):
        assert metric_reader(REPO / "chipbench", name)(
            SimpleNamespace(trace=None, calls=[])) is None


def test_breakdown_ranks_ops_by_self_time():
    """A loop's body ops are not counted again in the loop: ``%while.5``
    spans 80 ns but keeps 36 of its own; two planes average."""
    secs = L.op_self_seconds(cell_run())
    top = sorted(secs, key=lambda k: -secs[k])
    assert secs[OPS[0].name] == pytest.approx(36e-9)
    assert secs[OPS[1].name] == pytest.approx(20e-9)
    assert [L.instruction(n) for n in top] == [
        "while.5", "fusion.1", "sort.2", "rng.3", "copy.4"]
    two = trace()._replace(device_ops=(OPS, OPS[1:2]))
    assert L.op_self_seconds(cell_run(two))[OPS[1].name] == \
        pytest.approx((20 + 10) / 2 * 1e-9)
    assert L.op_self_seconds(SimpleNamespace(trace=None, calls=[])) == {}


def test_exchange_reads_the_collectives_per_step():
    """Two steps, each with a collective (its async start and done on
    chip 0, one op on chip 1), read per step and averaged over chips."""
    gather = (E("%all-reduce-start.5 = u32[8]{0} all-reduce-start(%p)",
                34, 36),
              E("%all-reduce-done.5 = u32[8]{0} all-reduce-done(%s)",
                36, 40),
              E("%all-gather.6 = pred[16]{0} all-gather(%q)", 72, 76))
    chip0 = tuple(sorted(OPS + gather, key=lambda e: e.start))
    chip1 = tuple(sorted(OPS + (E("%all-reduce.7 = u32[8]{0} "
                                  "all-reduce(%p)", 34, 44),),
                         key=lambda e: e.start))
    run = cell_run(trace()._replace(device_ops=(chip0, chip1)))
    got = metric_reader(REPO / "chipbench", "exchange_ms")(run)
    assert got == pytest.approx(((2 + 4 + 4) + 10) / 2 / 2 * 1e-6)
    # no collective in the window: nothing to read
    assert metric_reader(REPO / "chipbench", "exchange_ms")(
        cell_run()) is None
