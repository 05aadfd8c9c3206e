"""step_mfu (%): the whole step's share of the chips' peak: the least time
the cell's chips need for the required work of every timed step
(:mod:`chipbench.work`, counted from the model and the spikes the calls
returned; the peak is one chip's times the chips) over the host-clock time
of the whole window.  Bytes bind.  Moves ``rtf``."""
import numpy as np

from chipbench import work


def read(run):
    if not run.calls or run.peaks is None:
        return None
    counts = np.concatenate([c.counts for c in run.calls])
    need = work.least_seconds(
        work.step_work(run.net.model, counts, bool(run.cfg.get("plasticity"))),
        run.peaks) / run.chips
    return 100.0 * need / (run.span[1] - run.span[0])
