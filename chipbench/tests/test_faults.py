"""The timed path broken underneath: each fault this model's cells can
have turns ``correct`` false, where the sound program reads true.  A tiny
network on the CPU, the rest of a run as on the chip."""
import jax.numpy as jnp
import pytest

from .helpers import run_tiny

from repro.api import backends  # noqa: E402

_run = backends.FusedBackend.run


def _unchanged(self, state, n_steps, probes, stream=None):
    """A call that hands back the state it was given."""
    _, data = _run(self, state, n_steps, probes, stream)
    return state, data


def _half(self, state, n_steps, probes, stream=None):
    """Half of the neurons left out: they keep their state."""
    new, data = _run(self, state, n_steps, probes, stream)
    h = new.neuron.V.shape[0] // 2
    neuron = type(new.neuron)(*(b.at[h:].set(a[h:]) for a, b in
                                zip(state.neuron, new.neuron)))
    return new._replace(neuron=neuron), data


_update = backends.update_phase


def _flip(state, net, prop, cfg, w_ext, n, drive=None):
    """One neuron's spike altered where the step produces it."""
    state, spiked = _update(state, net, prop, cfg, w_ext, n, drive)
    return state, spiked.at[0].set(jnp.logical_not(spiked[0]))


def test_sound_program_is_correct(tmp_path):
    res = run_tiny(tmp_path, trace=True)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 1
    assert res["checks"]["counts_gap"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["unchanged", "half", "spike_altered"])
def test_fault_is_not_correct(tmp_path, monkeypatch, fault):
    if fault == "unchanged":
        monkeypatch.setattr(backends.FusedBackend, "run", _unchanged)
    elif fault == "half":
        monkeypatch.setattr(backends.FusedBackend, "run", _half)
    else:
        monkeypatch.setattr(backends, "update_phase", _flip)
    res = run_tiny(tmp_path)
    assert res["correct"] is False, res["checks"]
