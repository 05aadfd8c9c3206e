"""drive_ms (ms/step): device self time under the program's ``drive``
scope (the step key and the Poisson external drive), over the window's
simulated steps (:mod:`chipbench.layers`).  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.per_step_ms(run, "drive")
