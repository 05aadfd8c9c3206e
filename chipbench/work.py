"""Work a step requires by the model, not by the implementation.

Counts come from the configuration's synapse totals and the spikes the
timed calls returned: each neuron's state is read once per step, each
synapse of each neuron that fired is read once (its weight, and its
target and delay packed as tightly as the network's size allows) and its
target accumulated once.  No padded table slot counts, so removing
padding shows as a gain, and a kernel cannot need less than this.  With
pair STDP, each plastic synapse of a source that fired has its weight
written once (depression), each plastic synapse onto a target that fired
is read and written once (potentiation), and both traces of every neuron
are read once.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

STATE_BYTES = 16       # V, I_ex, I_in (f32) and the refractory count (int32)
TRACE_BYTES = 8        # x_pre, x_post (f32)
NEURON_FLOPS = 15      # exact integration, refractory hold, threshold
WEIGHT_BYTES = 4       # float32 weights


class Work(NamedTuple):
    flops: float
    bytes: float


def synapse_bytes(n_total: int, d_bins: int) -> int:
    """Weight plus target id and delay bin packed into whole bytes."""
    bits = math.ceil(math.log2(n_total + 1)) + math.ceil(math.log2(d_bins))
    return WEIGHT_BYTES + math.ceil(bits / 8)


def per_source(m) -> dict:
    """Mean synapses per neuron of each population (from the projection
    totals): out-degree, plastic (E->E) out- and in-degree."""
    n_exc_pops = sum(1 for i in range(len(m.pops)) if m.offsets[i] < m.n_exc)
    exc = np.arange(len(m.pops)) < n_exc_pops
    ee = m.n_syn * exc[:, None] * exc[None, :]
    return {"out": m.n_syn.sum(axis=0) / m.n_pop,
            "plastic_out": ee.sum(axis=0) / m.n_pop,
            "plastic_in": ee.sum(axis=1) / m.n_pop}


def step_work(m, counts: np.ndarray, plastic: bool) -> Work:
    """Required FLOPs and bytes of the steps whose spike counts per
    population are ``counts [T, P]``."""
    counts = np.asarray(counts, np.float64).reshape(-1, len(m.pops))
    steps = counts.shape[0]
    deg = per_source(m)
    spikes = counts.sum(axis=0)                    # per population
    syn = float(spikes @ deg["out"])
    flops = steps * m.n_total * NEURON_FLOPS + syn
    nbytes = (steps * m.n_total * STATE_BYTES
              + syn * synapse_bytes(m.n_total, m.d_max_bins))
    if plastic:
        dep = float(spikes @ deg["plastic_out"])
        flops += 2 * dep + 2 * steps * m.n_total
        nbytes += dep * WEIGHT_BYTES + steps * m.n_total * TRACE_BYTES
        pot = float(spikes @ deg["plastic_in"])
        flops += 2 * pot
        nbytes += 2 * pot * WEIGHT_BYTES
    return Work(flops, nbytes)


def least_seconds(w: Work, peaks) -> float:
    """The chip's least time for ``w``: the larger of its two bounds
    (bytes bind for this model)."""
    return max(w.flops / peaks.flops_bf16, w.bytes / peaks.hbm_bw)
