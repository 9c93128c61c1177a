"""The active-flow window of both engines' scans: answers and counters
do not depend on the window's width, a window the flows outgrow is
regrown and run again, and the sanitizer holds over the window."""
import dataclasses

import numpy as np
import pytest

from repro.netsim import engine, sweep
from repro.netsim.experiment import ExpSpec

ENGINES = ("fluid", "packet")
MODES = ("vmap", "map", "sequential")


def _specs(eng):
    """Two cells of different flow counts (so a batch pads one)."""
    return [ExpSpec(topology="testbed8", load=ld, policy=p, engine=eng,
                    duration_us=20_000, seed=5)
            for ld, p in ((0.6, "lcmp"), (0.7, "ecmp"))]


def _fit(rep):
    """The narrowest power-of-two window the run's flows fit."""
    return 1 << (rep.counters["live_flow_peak"] - 1).bit_length()


def _sweep(specs, mode):
    if mode == "sequential":
        return sweep.run_sweep(specs, sequential=True)
    return sweep.run_sweep(specs, batch_mode=mode)


def _answers(rep):
    return [(r.final.done, r.final.fct_us, r.final.flow_path,
             np.asarray(r.util)) for r in rep.results]


def _assert_same(a, b):
    for x, y in zip(_answers(a), _answers(b)):
        for u, v in zip(x, y):
            assert u.dtype == v.dtype and np.array_equal(u, v)


@pytest.fixture
def force_window(monkeypatch):
    """Make every window ``width`` slots wide (at most F), from a clean
    record of fitted windows."""
    monkeypatch.setattr(engine, "_WINDOW_FIT", {})

    def force(width):
        monkeypatch.setattr(engine, "window_size",
                            lambda num_flows, _: max(1, min(num_flows, width)))
    return force


def _full_window_run(specs, mode, force_window):
    force_window(1 << 30)
    full = _sweep(specs, mode)
    assert full.counters["window_regrows"] == 0
    return full


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("eng", ENGINES)
def test_answers_do_not_depend_on_the_window(eng, mode, force_window):
    specs = _specs(eng)
    full = _full_window_run(specs, mode, force_window)
    fit = _fit(full)
    steps = 2 * specs[0].duration_us // 200
    assert 2 * fit < min(r.flows.num_flows for r in full.results)
    for width in (fit, 2 * fit):
        force_window(width)
        rep = _sweep(specs, mode)
        assert rep.counters["window_regrows"] == 0
        assert rep.counters["window_slots"] == width * steps * len(specs)
        _assert_same(rep, full)


@pytest.mark.parametrize("eng", ENGINES)
def test_live_counters_equal_the_full_window(eng, force_window):
    specs = _specs(eng)
    full = _full_window_run(specs, "map", force_window)
    force_window(_fit(full))
    rep = _sweep(specs, "map")
    assert [r.counters for r in rep.results] == [r.counters
                                                 for r in full.results]
    for key in ("live_flow_steps", "live_flow_peak", "flow_slots"):
        assert rep.counters[key] == full.counters[key]
    assert rep.counters["window_slots"] < full.counters["window_slots"]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("eng", ENGINES)
def test_a_window_below_the_peak_regrows(eng, mode, force_window):
    """A window narrower than the flows in flight: the run overflows, is
    regrown and run again, and answers as the full window does; the width
    that fit is remembered, so the next call regrows nothing."""
    specs = _specs(eng)
    full = _full_window_run(specs, mode, force_window)
    force_window(_fit(full) // 2)
    rep = _sweep(specs, mode)
    assert rep.counters["window_regrows"] >= 1
    _assert_same(rep, full)
    again = _sweep(specs, mode)
    assert again.counters["window_regrows"] == 0
    assert again.counters["window_slots"] == rep.counters["window_slots"]
    if mode != "sequential":
        assert again.counters["lowerings"] == again.num_groups
    _assert_same(again, full)


def test_window_size_follows_the_arrivals():
    assert engine.window_size(41_146, 100) == 1024
    assert engine.window_size(41_146, 129) == 2048
    assert engine.window_size(3_737, 20) == 256
    assert engine.window_size(100, 20) == 100
    assert engine.window_size(0, 1) == 1


@pytest.mark.parametrize("eng", ENGINES)
def test_checked_run_over_the_window(eng, force_window):
    """The sanitizer's invariants hold over a window as narrow as the
    flows fit (so it compacts), with failover, silent degradation and
    re-decision armed, and the checked run answers as the plain one."""
    knob = ({"flowlet_gap_us": 1000} if eng == "packet"
            else {"redecide_period_us": 2000})
    specs = [ExpSpec(topology=topo, load=0.6, policy=p, engine=eng,
                     duration_us=20_000, seed=2, **knob)
             for topo, p in (("testbed8_failover:fail_ms=8", "lcmp_r"),
                             ("staleness:deg_ms=10", "fatpaths"))]
    plain = _full_window_run(specs, "vmap", force_window)
    force_window(_fit(plain))
    rep = sweep.run_sweep([dataclasses.replace(s, checks=1)
                           for s in specs])
    assert rep.counters["window_regrows"] == 0
    assert all(r.stats.completed > 0 for r in rep.results)
    _assert_same(rep, plain)
