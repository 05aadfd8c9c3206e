"""rtf (s/s): the wall time of the whole window, from the first timed
call's start to the last one's end with everything the host did between
calls, over all the biological time the calls simulated.  The paper's
figure of merit (below 1 is faster than realtime)."""


def read(run):
    if not run.calls:
        return None
    model_s = sum(c.steps for c in run.calls) * run.cfg["dt_ms"] * 1e-3
    return (run.span[1] - run.span[0]) / model_s
