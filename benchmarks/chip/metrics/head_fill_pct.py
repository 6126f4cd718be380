"""Head: emitted tokens over the rows the head computed, in %, from the
program's counter ``serve_head_rows_total`` (the head runs on every
window position: slots x chunk rows per step).  Read after the window,
over every step the scheduler ran, as ``window_fill_pct``."""


def read(run):
    reg = run.drv.sched.metrics
    computed = reg.total("serve_head_rows_total", kind="computed")
    if computed <= 0:
        return None
    return 100.0 * reg.total("serve_head_rows_total",
                             kind="emitted") / computed
