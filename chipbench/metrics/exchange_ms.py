"""exchange_ms (ms/step): device self time of the collective ops (the
spike registry's exchange between chips, ``all-gather`` in the program,
which XLA may run as an ``all-reduce``), averaged over the chips, over
the window's simulated steps (:mod:`chipbench.layers`).  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.exchange_ms(run)
