"""Window step, whole step against the chip's peak: model FLOPs of the
useful tokens of every step in the window (``work.step_flops``) over the
steps' summed host wall time times the bf16 peak, in %."""
from work import step_flops


def read(run):
    steps = run.window_steps
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    flops = sum(step_flops(run.conf, s.positions, s.context, s.tokens)
                for s in steps)
    return 100.0 * flops / (wall * run.peaks["bf16_flops"])
