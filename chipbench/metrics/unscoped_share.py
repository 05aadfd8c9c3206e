"""unscoped_share (%): device self time in none of the program's layer
scopes (the loops' own time, ops the program's map lacks) over the
traced busy time (:mod:`chipbench.layers`).  How much of the device time
the per-layer metrics leave unnamed.  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.unscoped_share(run)
