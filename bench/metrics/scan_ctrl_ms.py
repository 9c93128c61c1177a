"""Device milliseconds per simulated cell step in the per-step fixed
work: the own time of the ops in the ``step.failover``,
``step.monitor``, ``step.ctrl``, ``step.redte``, ``step.rings`` and
``step.counters`` scopes inside the traced unit's device calls, over the
denominator of ``scan_step_ms`` (``bench.scopes``)."""
from bench import scopes


def read(run):
    return scopes.phase_ms(run, "ctrl")
