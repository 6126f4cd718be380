"""Window step (digital backend, XLA's dots): the least time the traced
steps could take, each the larger of its FLOPs over the bf16 peak and its
least bytes over HBM bandwidth (``work.least_step_seconds``), over the
device busy time of those steps, in %."""
from work import least_step_seconds


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    busy = sum(run.trace.step_busy_s)
    if busy <= 0:
        return None
    least = least_step_seconds(run.conf, run.traced_steps, run.peaks)
    return 100.0 * least / busy
