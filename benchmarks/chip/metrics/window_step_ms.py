"""Window step: device busy time from one ``sched.step`` host span to the
next, mean over the traced steps that ran anything on the device."""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    busy = [b for b in run.trace.step_busy_s if b > 0]
    return float(np.mean(busy)) * 1e3 if busy else None
