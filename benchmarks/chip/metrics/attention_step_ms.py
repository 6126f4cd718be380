"""Attention (scope ``attention``: the K/V page writes and gathers,
scores, softmax and PV, not the projections): device self time of the
ops under the scope, per traced step that ran anything (``scopes.py``)."""
from scopes import step_ms


def read(run):
    return step_ms(run, "attention")
