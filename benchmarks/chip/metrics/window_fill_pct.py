"""Window step: useful over computed window positions, in %, from the
program's counter ``serve_window_positions_total`` (useful: prompt
tokens fed plus decoding rows; computed: slots x chunk per step).  The
counter is read once, after the window, so it covers every step the
scheduler ran: the warm-up's few steps, the window and the drain."""


def read(run):
    reg = run.drv.sched.metrics
    computed = reg.total("serve_window_positions_total", kind="computed")
    if computed <= 0:
        return None
    return 100.0 * reg.total("serve_window_positions_total",
                             kind="useful") / computed
