"""Run one reference cell: the cell's world and flows built from its
parameters and seed, stepped by the NumPy reference (``npsim``), and
scored.

``precision="float64"`` is the reference that decides ``correct``;
``precision="bfloat16"`` is the control, the same model with its state
held in the next precision below the program's float32.

A cell's parameters are its configuration's and its traffic's keys. The
reference takes only the keys it models (``MODELED``) and refuses any
other by name, so that a key that reaches the program is never silently
absent here.
"""
from __future__ import annotations

from types import SimpleNamespace

from . import metrics
from .npsim import Reference
from .world import build_world, make_flows, sim_config


# the keys of a cell that this reference models; the harness takes the
# grid's keys out and passes each cell's ``policy`` and ``seed`` apart
MODELED = frozenset({"topology", "cap_scale", "duration_us", "dt_us",
                     "engine", "workload", "pairs", "load", "bg_load"})


def run_cell(cell: dict, policy: str, seed: int,
             precision: str = "float64") -> SimpleNamespace:
    """One policy cell at ``seed``: flows, raw outputs and scores."""
    unknown = sorted(set(cell) - MODELED)
    if unknown:
        raise ValueError(f"the reference does not model {unknown}")
    scen, table = build_world(cell["topology"])
    flows = make_flows(cell, seed, scen, table)
    cfg = sim_config(cell, policy, scen)
    ref = Reference(table, flows, cfg, precision)
    view = ref.run()
    stats = metrics.fct_stats(view, table, flows, cfg)
    fg, _ = metrics.fg_bg_stats(view, table, flows, cfg, overall=stats)
    util = metrics.link_utilization(view, ref.link_arrays(), cfg)
    return SimpleNamespace(policy=policy, seed=seed, flows=flows,
                           final=view, stats=stats, stats_fg=fg, util=util,
                           table=table, cfg=cfg)
