"""device_idle_share (%): the traced window's share in which no operation
ran on the device (1 - union of device-op intervals / window), averaged
over the chips used.  Moves ``rtf``."""
from chipbench import trace as T


def read(run):
    tr = run.trace
    win = T.window(tr) if tr is not None else None
    if win is None or not tr.device_ops:
        return None
    lo, hi = win
    return 100.0 * (1.0 - T.busy(tr, lo, hi) / (hi - lo))
