"""The control does not pass the comparison: the plain reference put in
the program's place and computed in bfloat16, where the configuration
states float32 (the program's own bfloat16 state path does not run, see
PERF.md).  Plasticity's weights left as they were do not pass either.  A
tiny network on the CPU."""
import pytest

from .helpers import run_tiny, tiny_root

from chipbench import calibrate, compare, reference, run  # noqa: E402
from repro.api import backends  # noqa: E402

_run = backends.FusedBackend.run


@pytest.mark.parametrize("config,traffic,cell", [
    ("pd14_full", "short1", "pd14_full.scan20"),
    ("pd14_n25_stdp", "scan5", "pd14_n25_stdp.scan5")])
def test_bfloat16_reference_in_place_is_not_correct(tmp_path, config,
                                                    traffic, cell):
    root, name = tiny_root(tmp_path, config=config, traffic=traffic,
                           limits_of=cell)
    cell = run.load_cell(name, root)
    net = run.make_network(cell.cfg)
    sim, _ = run.session(cell.cfg, cell.mix, net, 2 ** 32 + 3,
                      run.CompileCounter())
    steps = int(round(cell.mix["chunk_ms"] / cell.cfg["dt_ms"]))
    tb = reference.tables(net, cell.cfg)
    numbers = compare.combine(calibrate.control_readings(
        tb, net, cell.cfg, sim.state, steps, calls=3))
    assert not compare.verdict(numbers, cell.limits), numbers
    # the same calls in float32 are the reference itself
    same = compare.combine(calibrate.control_readings(
        tb, net, cell.cfg, sim.state, steps, calls=3, dtype="float32"))
    assert same["counts_gap"] == 0 and same["state_gap"] == 0


def test_plastic_cell_is_correct(tmp_path):
    res = run_tiny(tmp_path, config="pd14_n25_stdp", traffic="scan5",
                   limits_of="pd14_n25_stdp.scan5")
    assert res["correct"] is True, res["checks"]


def test_plastic_weights_left_unchanged_is_not_correct(tmp_path,
                                                       monkeypatch):
    def frozen(self, state, n_steps, probes, stream=None):
        (sim, ps), data = _run(self, state, n_steps, probes, stream)
        return (sim, state[1]), data
    monkeypatch.setattr(backends.FusedBackend, "run", frozen)
    res = run_tiny(tmp_path, config="pd14_n25_stdp", traffic="scan5",
                   limits_of="pd14_n25_stdp.scan5")
    assert res["correct"] is False, res["checks"]
