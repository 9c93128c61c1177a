"""World, flow tables and configuration of one reference cell.

The same steps as the program's `netsim.experiment` module, taken
from a cell's parameters (a dict read from the benchmark's config and
traffic files) instead of the program's ``ExpSpec``. Load schedules and
the training co-simulation overlay are not part of the copy: ``sim``
refuses a cell that asks for them.
"""
from __future__ import annotations

import functools

import numpy as np

from . import cdf as cdfmod
from . import paths, scenarios
from .npsim import Config
from .gen import generate


@functools.lru_cache(maxsize=8)
def build_world(topology: str):
    """Scenario and path table of a scenario string."""
    scen = scenarios.get(topology)
    t = scen.topology
    pair_list = (list(scen.traffic_pairs) if scen.traffic_pairs is not None
                 else paths.all_pairs(t))
    table = paths.build_path_table(t, pair_list, max_hops=scen.max_hops,
                                   detour_delay=scen.detour_delay,
                                   detour_hops=scen.detour_hops)
    _, _, cap, dly = t.arrays()
    object.__setattr__(table, "_link_caps", cap.astype(np.float32))
    object.__setattr__(table, "_link_delays", dly.astype(np.int64))
    return scen, table


def traffic_pair_ids(pairs: str, scen, table) -> list:
    pidx = table.pair_index()
    if pairs == "main":
        main = pidx[scen.main_pair]
        if table.pair_ncand[main] == 0:
            raise ValueError(f"main pair {scen.main_pair} has no candidates")
        return [main]
    if pairs == "all":
        return [pidx[p] for p in pidx if table.pair_ncand[pidx[p]] > 0]
    s, d = pairs.split("-")
    return [pidx[(int(s), int(d))]]


def background_pair_ids(table, fg_ids) -> list:
    fg = set(int(i) for i in fg_ids)
    return [i for i in range(len(table.pair_src))
            if table.pair_ncand[i] > 0 and i not in fg]


def make_flows(cell: dict, seed: int, scen, table):
    """The cell's flow table at ``seed``."""
    fg_ids = traffic_pair_ids(cell["pairs"], scen, table)
    bg_load = cell.get("bg_load", 0.0)
    bg_ids = background_pair_ids(table, fg_ids) if bg_load > 0 else None
    return generate(table, cdfmod.WORKLOADS[cell["workload"]], cell["load"],
                    cell["duration_us"], pair_ids=fg_ids, seed=seed,
                    cap_scale=cell["cap_scale"], bg_pair_ids=bg_ids,
                    bg_load=bg_load)


def sim_config(cell: dict, policy: str, scen) -> Config:
    """The simulator configuration of one policy cell: the horizon is
    twice the traffic window so that tail flows can finish."""
    return Config(engine=cell["engine"], policy=policy,
                  horizon_us=cell["duration_us"] * 2,
                  cap_scale=cell["cap_scale"],
                  fail_sched=scen.fail_sched,
                  degrade_sched=scen.degrade_sched)
