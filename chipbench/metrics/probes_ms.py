"""probes_ms (ms/step): device self time under the program's ``probes``
scope (the per-step probe reductions and stream-probe updates), over the
window's simulated steps (:mod:`chipbench.layers`).  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.per_step_ms(run, "probes")
