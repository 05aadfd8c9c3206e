"""Process-level setup (compile-cache placement) and the peak table."""
import pathlib

import jax
import pytest

from repro.launch import runtime
from repro.perf.peaks import PEAKS, peaks_for


@pytest.fixture
def restore_cache_dir(monkeypatch):
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env_dir", [None, "placed-from-outside"])
def test_setup_jax_places_the_compile_cache(monkeypatch, tmp_path,
                                            restore_cache_dir, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(runtime.DEFAULT_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert runtime.setup_jax() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_default_cache_dir_is_in_the_checkout_and_ignored():
    root = pathlib.Path(__file__).resolve().parents[1]
    assert runtime.DEFAULT_CACHE_DIR == root / ".jax_cache"
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e.flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
    assert all(p.source for p in PEAKS.values())


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for(kind)
