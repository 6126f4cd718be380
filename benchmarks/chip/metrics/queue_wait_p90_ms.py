"""Scheduler admission: p90 of (start of the first step that fed the
request - due), host clock, read from ``Request.fed`` after each step,
over the requests due in the window before the profiler started."""
from endtoend import p90


def read(run):
    return p90([(tr.fed - tr.due) * 1e3 for tr in run.untraced_tracks
                if tr.fed is not None])
