"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode on CPU checks what the kernels compute; it cannot check
that Mosaic, the TPU kernel compiler, accepts them (tile-aligned blocks,
no scalar stores into VMEM, the scoped-VMEM budget).  These tests
AOT-compile each kernel ``auto`` can pick on TPU, with ``interpret=False``,
for one chip of a described ``v5e:2x2`` topology — no chip is needed —
and check that each reaches the device as one custom call under its own
``name=``:

* ``lif_update`` at the full-scale width, N = 77,169;
* ``ell_deliver``, ``lif_deliver`` and ``lif_deliver_plastic`` at the
  scale-0.25 width and at the widest N whose delay ring fits
  ``FUSED_MAX_RING_BYTES``, with K at the full-scale padded out-degree.

The topology is described inside a fixture (never at import), because
only one process at a time may load the TPU compiler's library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import kernel_policy as kpol
from repro.core.neuron import NeuronParams, Propagators
from repro.kernels.ell_deliver import TILE, ell_deliver_pallas
from repro.kernels.lif_deliver import (lif_deliver_pallas,
                                       lif_deliver_plastic_pallas)
from repro.kernels.lif_update import lif_update_pallas

N_FULL = 77169            # neurons at scale 1.0
N_QUARTER = 19292         # neurons at scale 0.25
#: Full-scale max out-degree (6,786 for the multinomial out-degree of
#: scale 1.0) padded to the ELL row tile ``block_k = 128``.
K_FULL = 6912
D_BINS = 46               # ring slots at dt = 0.1 ms (core/params.py)
SPIKE_BUDGET = 256        # auto_spike_budget at scale 1.0


#: The widest N the ring cap admits: whole (8, 128) ring tiles, minus the
#: dump column.
N_CAP = kpol.FUSED_MAX_RING_BYTES // (2 * D_BINS * 4) // TILE * TILE - 1
WIDTHS = {"scale0.25": N_QUARTER, "ring_cap": N_CAP}
PROP = Propagators.make(NeuronParams(), 0.1)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, name, args, **static):
    """Compile ``fn`` and check that its kernel reaches the device as one
    custom call named ``name`` (the name the trace's op line shows)."""
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"^\s*(ROOT )?%{name}(\.\d+)? = .* custom-call\(",
                     text, re.M), f"no custom call named {name!r}"


def _shapes(one_chip, n, k=K_FULL):
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    rows = n + 1
    return dict(
        ids=sd((SPIKE_BUDGET,), jnp.int32),
        lens=sd((SPIKE_BUDGET,), jnp.int32),
        targets=sd((rows, k), jnp.int32),
        weights=sd((rows, k), jnp.float32),
        dbins=sd((rows, k), jnp.int32),
        pmask=sd((rows, k), jnp.int32),
        ring=sd((D_BINS, 2, n + 1), jnp.float32),
        vf=sd((n,), jnp.float32),
        vi=sd((n,), jnp.int32),
        t=sd((), jnp.int32),
    )


def test_lif_update_compiles_at_full_scale(one_chip):
    s = _shapes(one_chip, N_FULL, k=128)
    _compile(lif_update_pallas, "lif_update",
             (s["vf"], s["vf"], s["vf"], s["vi"], s["vf"], s["vf"],
              s["vf"]), prop=PROP)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_ell_deliver_compiles(one_chip, width):
    n = WIDTHS[width]
    s = _shapes(one_chip, n)
    _compile(ell_deliver_pallas, "ell_deliver",
             (s["ids"], s["lens"], s["targets"], s["weights"], s["dbins"],
              s["t"]),
             d_bins=D_BINS, n_cols=n + 1, n_exc=n * 4 // 5)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_lif_deliver_compiles(one_chip, width):
    n = WIDTHS[width]
    s = _shapes(one_chip, n)
    _compile(lif_deliver_pallas, "lif_deliver_static",
             (s["ids"], s["lens"], s["targets"], s["weights"], s["dbins"],
              s["ring"], s["vf"], s["vf"], s["vf"], s["vi"], s["vf"],
              s["vf"], s["t"]),
             d_bins=D_BINS, n_cols=n + 1, n=n, n_exc=n * 4 // 5, prop=PROP)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_lif_deliver_plastic_compiles(one_chip, width):
    n = WIDTHS[width]
    s = _shapes(one_chip, n)
    _compile(lif_deliver_plastic_pallas, "lif_deliver_plastic",
             (s["ids"], s["lens"], s["targets"], s["weights"], s["dbins"],
              s["pmask"], s["ring"], s["vf"], s["vf"], s["vf"], s["vi"],
              s["vf"], s["vf"], s["vf"], s["vf"], s["vf"], s["t"]),
             d_bins=D_BINS, n_cols=n + 1, n=n, n_exc=n * 4 // 5, prop=PROP,
             dep_coef=0.01, decay_p=0.99, decay_m=0.99)


def test_widest_n_sits_on_the_ring_cap():
    assert kpol._ring_bytes(N_CAP, D_BINS) <= kpol.FUSED_MAX_RING_BYTES
    assert kpol._ring_bytes(N_CAP + 1, D_BINS) > kpol.FUSED_MAX_RING_BYTES
