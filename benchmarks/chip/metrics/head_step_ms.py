"""Head (final norm and logits, scope ``head``): device self time of the
ops under the scope, per traced step that ran anything (``scopes.py``).
On the crossbar backend this is the head's ``crossbar_mac`` read."""
from scopes import step_ms


def read(run):
    return step_ms(run, "head")
