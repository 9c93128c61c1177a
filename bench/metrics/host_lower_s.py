"""Host seconds per unit tracing, lowering and compiling (or loading from
the cache) the unit's group programs: the program's ``sweep.lower`` span,
the mean over the units that ran without the profiler."""
from bench import scopes


def read(run):
    return scopes.mean_stage_s(run, ("lower",))
