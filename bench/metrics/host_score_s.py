"""Host seconds per unit copying the answers back and scoring them: the
program's ``sweep.fetch`` and ``sweep.score`` spans, the mean over the
units that ran without the profiler."""
from bench import scopes


def read(run):
    return scopes.mean_stage_s(run, ("fetch", "score"))
