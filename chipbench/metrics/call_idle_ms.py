"""call_idle_ms (ms/call): device-idle time inside the program's
``repro.run`` spans (dispatch, the wait for the result, the overflow
read), per timed call (:mod:`chipbench.layers`).  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.call_idle_ms(run)
