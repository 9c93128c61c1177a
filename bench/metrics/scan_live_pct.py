"""Share of the scan's flow slots that hold a flow in flight: the
program's ``live_flow_steps`` over its ``flow_slots`` (padded flows x
steps x cells) in the last unit, in percent."""
from bench import scopes


def read(run):
    recs = scopes.unit_records(run.units[-1]) if run.units else []
    slots = sum(r.counters.get("flow_slots", 0) for r in recs)
    if not slots:
        return None
    live = sum(r.counters.get("live_flow_steps", 0) for r in recs)
    return 100.0 * live / slots
