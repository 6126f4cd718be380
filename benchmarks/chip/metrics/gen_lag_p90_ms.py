"""Load generator: p90 of (sent - due), host clock, over the requests due
in the window before the profiler started.  The client sends between
scheduler steps, so a request due while a step runs waits for it; this
says how late the generator ran."""
from endtoend import p90


def read(run):
    return p90([(tr.submit - tr.due) * 1e3 for tr in run.untraced_tracks])
