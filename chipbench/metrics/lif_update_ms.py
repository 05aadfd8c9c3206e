"""lif_update_ms (ms/step): device self time under the program's
``lif_update`` scope (the ring-slot read and clear, and the LIF step),
over the window's simulated steps (:mod:`chipbench.layers`).  Moves
``rtf``."""
from chipbench import layers


def read(run):
    return layers.per_step_ms(run, "lif_update")
