"""Device milliseconds per simulated cell step in link sharing and
completion: the own time of the ops in the fluid engine's ``step.links``
and ``step.drain`` scopes, or the packet engine's ``step.pfc``,
``step.inject``, ``step.hops`` and ``step.drain``, inside the traced
unit's device calls, over the denominator of ``scan_step_ms``
(``bench.scopes``)."""
from bench import scopes


def read(run):
    return scopes.phase_ms(run, "links")
