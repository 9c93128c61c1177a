"""Device milliseconds per simulated cell step in the CC rate update: the
own time of the ops in the ``step.cc`` scope inside the traced unit's
device calls, over the denominator of ``scan_step_ms``
(``bench.scopes``)."""
from bench import scopes


def read(run):
    return scopes.phase_ms(run, "cc")
