"""fused_step_ms (ms/step): device self time under the program's
``fused_step`` scope (the one-kernel delivery + LIF step,
``lif_deliver_static`` or ``lif_deliver_plastic``), over the window's
simulated steps (:mod:`chipbench.layers`).  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.per_step_ms(run, "fused_step")
