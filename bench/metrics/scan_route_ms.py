"""Device milliseconds per simulated cell step in arrival routing
(``decide``) and re-decision: the own time of the ops in the
``step.route`` and ``step.redecide`` scopes inside the traced unit's
device calls, over the denominator of ``scan_step_ms``
(``bench.scopes``)."""
from bench import scopes


def read(run):
    return scopes.phase_ms(run, "route")
