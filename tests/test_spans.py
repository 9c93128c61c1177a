"""Host spans and scan counters of the sweep: the stages cover a call's
wall time, the counters count what a plain Python step loop counts and
padding adds nothing, and the lowered scan names each step phase."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.netsim import engine, sanitize, sweep
from repro.netsim.experiment import ExpSpec, build_experiment

ENGINES = ("fluid", "packet")


def _specs(eng, loads=(0.5,), policies=("ecmp", "lcmp")):
    return [ExpSpec(topology="testbed8", load=ld, policy=p, engine=eng,
                    duration_us=20_000, seed=3)
            for ld in loads for p in policies]


def test_stages_cover_the_call():
    rep = sweep.run_sweep(_specs("fluid"))
    assert set(rep.stages) == set(sweep.STAGES)
    assert all(v > 0 for v in rep.stages.values()), rep.stages
    assert abs(sum(rep.stages.values()) / rep.wall_s - 1) < 0.05
    assert rep.build_s == (rep.stages["world"] + rep.stages["flows"]
                           + rep.stages["pad"])
    rec = sweep.RECENT[-1]
    assert rec.stages == rep.stages and rec.counters == rep.counters
    assert len(rec.programs) == rep.counters["lowerings"] == 1
    assert rec.start < rec.end


def test_only_the_newest_call_keeps_its_programs():
    specs = _specs("fluid", policies=("ecmp",))
    sweep.run_sweep(specs)
    sweep.run_sweep(specs)
    assert len(sweep.RECENT[-1].programs) == 1
    assert sweep.RECENT[-2].programs == ()


@pytest.mark.parametrize("eng", ENGINES)
def test_counters_match_a_python_step_loop(eng):
    spec = _specs(eng, policies=("lcmp",))[0]
    _, table, flows, cfg = build_experiment(spec)
    arrs, st = engine.get_engine(eng).build(table, flows, cfg)
    step = jax.jit(engine.get_engine(eng).make_step(arrs, cfg))
    # the flows a step moves: active at its end, or completed in it
    live, peak = 0, 0
    for t in range(cfg.num_steps):
        done = np.asarray(st.done)
        st, _ = step(st, jnp.int32(t))
        n = int(np.asarray(st.active).sum()
                + (np.asarray(st.done) & ~done).sum())
        live, peak = live + n, max(peak, n)
    assert live > 0 and peak > 1
    rep = sweep.run_sweep([spec])
    assert rep.results[0].counters == {"live_flow_steps": live,
                                       "live_flow_peak": peak}
    assert rep.counters["flow_slots"] == flows.num_flows * cfg.num_steps
    assert rep.counters["arrivals"] == flows.num_flows
    assert rep.counters["arrival_slots"] == (
        cfg.num_steps * int(arrs.arrivals.shape[1]))


@pytest.mark.parametrize("eng", ENGINES)
def test_padding_adds_nothing_to_the_counters(eng):
    """Two loads in one chunk: the smaller cell's flow table and arrival
    buckets are padded, and its counters equal its own sequential run."""
    specs = _specs(eng, loads=(0.45, 0.5), policies=("lcmp",))
    bat = sweep.run_sweep(specs, batch_mode="vmap")
    seq = sweep.run_sweep(specs, sequential=True)
    assert bat.group_cells == [2]
    assert len({r.flows.num_flows for r in bat.results}) == 2
    assert [r.counters for r in bat.results] == [r.counters
                                                 for r in seq.results]
    for key in ("live_flow_steps", "live_flow_peak"):
        assert bat.counters[key] == seq.counters[key]
    steps = build_experiment(specs[0])[3].num_steps
    fmax = max(r.flows.num_flows for r in bat.results)
    assert bat.counters["flow_slots"] == 2 * fmax * steps
    assert seq.counters["flow_slots"] == sum(r.flows.num_flows
                                             for r in seq.results) * steps


def _phases(eng):
    own = {"fluid": ("step.links",),
           "packet": ("step.pfc", "step.inject", "step.hops")}[eng]
    return ("step.monitor", "step.route", "step.cc", "step.drain",
            "step.rings", "step.counters") + own


def _lowered_text(eng, topology, policy, **knobs):
    spec = ExpSpec(topology=topology, load=0.3, policy=policy, engine=eng,
                   duration_us=2_000, seed=1)
    _, table, flows, cfg = build_experiment(spec)
    arrs, st = engine.get_engine(eng).build(table, flows, cfg)
    cfg = dataclasses.replace(cfg, **knobs)
    run_impl = engine.get_engine(eng).run_impl
    if sanitize.enabled(cfg):
        low = sanitize._checked_runner(run_impl, cfg).lower(arrs, st)
    else:
        low = jax.jit(run_impl, static_argnames="cfg").lower(arrs, st,
                                                             cfg=cfg)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("eng", ENGINES)
def test_lowered_scan_names_each_phase_its_cfg_runs(eng):
    plain = _lowered_text(eng, "testbed8", "ecmp")
    for name in _phases(eng):
        assert f"{name}/" in plain, name
    for name in ("step.failover", "step.ctrl", "step.redecide",
                 "step.redte", "step.checks"):
        assert f"{name}/" not in plain, name

    every = _lowered_text(
        eng, "testbed8_failover:fail_ms=1", "sweep",
        sweep_policies=("ecmp", "redte", "fatpaths", "lcmp"),
        redecide_period_us=1000, flowlet_gap_us=1000, checks=1)
    for name in _phases(eng) + ("step.failover", "step.ctrl",
                                "step.redecide", "step.redte",
                                "step.checks"):
        assert f"{name}/" in every, name
