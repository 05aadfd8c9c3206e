"""deliver_ms (ms/step): device self time of the ops the program runs
under its ``deliver`` scope (spike delivery into the delay ring), over
the window's simulated steps (:mod:`chipbench.layers`).  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.per_step_ms(run, "deliver")
