"""plasticity_ms (ms/step): device self time under the program's
``plasticity`` scope (the potentiation scatter and clip, the weight views
and copies around the fused kernel), over the window's simulated steps
(:mod:`chipbench.layers`).  Moves ``rtf``."""
from chipbench import layers


def read(run):
    return layers.per_step_ms(run, "plasticity")
