"""The readers of the program's own spans, scopes and counters: own time
per scope on hand traces and against a brute-force count, the phase and
host-stage readers, and nothing read where the program records nothing.
"""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, scopes
from bench import trace as tracemod
from bench.tests.conftest import tiny

DATA = Path(__file__).parent / "data"

NEW = ("host_lower_s", "host_score_s", "scan_route_ms", "scan_links_ms",
       "scan_cc_ms", "scan_ctrl_ms", "scan_live_pct")


def _brute(iv, labels, lo, hi):
    """Own time per label on a 1 ns grid: each ns to the covering
    interval that comes last in (start, -end) order."""
    order = sorted(range(len(iv)), key=lambda i: (iv[i][0], -iv[i][1]))
    rank = {i: r for r, i in enumerate(order)}
    out = {}
    for x in range(lo, hi):
        cover = [i for i, (a, b) in enumerate(iv) if a <= x < b]
        if cover:
            lab = labels[max(cover, key=rank.get)]
            out[lab] = out.get(lab, 0) + 1
    return out


def test_own_time_against_brute_force():
    rng = np.random.default_rng(3)
    a = rng.integers(0, 400, 60)
    iv = np.stack([a, a + rng.integers(1, 80, 60)], 1)
    labels = [["x", "y", "", None][i % 4] for i in range(60)]
    for lo, hi in ((0, 500), (50, 300)):
        got = scopes.own_by_label(iv, labels, lo, hi)
        assert got == _brute(iv.tolist(), labels, lo, hi)
        assert sum(got.values()) == tracemod.busy_ns(iv, lo, hi)


def _hand_run(cell_name="wan24.fluid"):
    """Two device calls; a loop op holds its body's ops, and one op runs
    past the end of the op it started in."""
    ops = np.array([[10, 50],      # while: the scan loop
                    [12, 20],      # route
                    [20, 30],      # links
                    [28, 34],      # cc, overlaps links' end
                    [40, 44],      # rings
                    [60, 90],      # while
                    [61, 70],      # an op no map names
                    [70, 80],      # counters
                    [95, 99]],     # outside every call
                   np.int64)
    names = ["%while.1 while", "%fusion.1 fusion", "%fusion.2 fusion",
             "%fusion.3 fusion", "%fusion.4 fusion", "%while.1 while",
             "%copy.9 copy", "%fusion.5 fusion", "%fusion.6 fusion"]
    dev = tracemod.Device("/device:TPU:0", ops, names,
                          np.array([[8, 52], [58, 92], [94, 97]], np.int64))
    tr = tracemod.Trace([dev], np.zeros((0, 2), np.int64), [], (0, 100))
    cell = harness.load_cell(cell_name)
    return harness.RunData(cell=cell, units=[], trace=tr, devices=1)


SCOPES = {"%while.1": "", "%fusion.1": "step.route", "%fusion.2": "step.links",
          "%fusion.3": "step.cc", "%fusion.4": "step.rings",
          "%fusion.5": "step.counters", "%fusion.6": "step.cc",
          "%copy.9": ""}


def test_hand_trace_phases(monkeypatch):
    run = _hand_run()
    monkeypatch.setattr(scopes, "program_scopes", lambda: SCOPES)
    dev = run.trace.devices[0]
    lo, hi = run.trace.window
    own = scopes.own_by_scope(dev, lo, hi, SCOPES)
    # the third module run is under a tenth of the longest: not a call;
    # the loops' own time: 2 + 6 + 6 and 1 + 10, and the copy's 9
    assert own == {"": 14 + 11 + 9, "step.route": 8,
                   "step.links": 8, "step.cc": 6, "step.rings": 4,
                   "step.counters": 10}
    assert sum(own.values()) == tracemod.busy_in_calls_ns(dev, lo, hi)
    per_step = run.cell.num_cells * run.cell.num_steps
    for phase, ns in (("route", 8), ("links", 8), ("cc", 6),
                      ("ctrl", 14)):
        assert scopes.phase_ms(run, phase) == pytest.approx(
            ns * 1e-6 / per_step)
    # the phases and the unscoped time make up scan_step_ms
    from bench.metrics import scan_step_ms
    ms = scopes.scope_ms(run)
    assert sum(ms.values()) == pytest.approx(scan_step_ms.read(run))


def test_a_map_of_another_program_is_not_used(monkeypatch):
    run = _hand_run()
    foreign = {k: v for k, v in SCOPES.items() if k != "%copy.9"}
    monkeypatch.setattr(scopes, "program_scopes", lambda: foreign)
    assert scopes.phase_ms(run, "cc") is None


def test_nothing_read_where_the_program_records_nothing(monkeypatch):
    """The parent program has no spans, scopes or counters: every new
    reader returns None and raises nothing."""
    monkeypatch.setattr(scopes, "_records", lambda: [])
    run = _hand_run()
    run.units = [harness.Unit(start=0.0, end=1.0, build_s=0.1, prints=[])]
    bench = harness.load_benchmark()
    got = harness.read_per_layer(bench, run)
    assert not set(got) & set(NEW)


def test_host_readers_on_units_run_here():
    """Two tiny units of a cell through the harness's unit function: the
    host readers give each recorded stage's mean and the counter share."""
    cell = tiny(harness.load_cell("wan24.fluid"))
    specs = harness.cell_specs(cell, 2**31 + 99)
    units = [harness.run_unit(cell, specs)[0] for _ in range(2)]
    run = harness.RunData(cell=cell, units=units, traced=units[0])
    recs = scopes.unit_records(units[1])
    assert len(recs) == 1
    st, ctr = recs[0].stages, recs[0].counters
    from bench.metrics import host_lower_s, host_score_s, scan_live_pct
    assert host_lower_s.read(run) == st["lower"]
    assert host_score_s.read(run) == st["fetch"] + st["score"]
    assert scan_live_pct.read(run) == pytest.approx(
        100 * ctr["live_flow_steps"] / ctr["flow_slots"])
    assert 0 < scan_live_pct.read(run) < 100
    # the stages hold the unit's wall time
    assert sum(st.values()) <= units[1].wall_s
    assert sum(st.values()) > 0.95 * (recs[0].end - recs[0].start)


def _scoped_cut():
    path = DATA / "tb8.fluid.5pol.scoped.json.gz"
    tr = tracemod.Trace.read(str(path))
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return tr, d["scopes"], d["meta"]


def test_scoped_cut_calls_lie_inside_the_run_span():
    """A v5e traced unit with this program's spans: the device's calls
    lie inside the host's ``sweep.run`` span, so host and device share
    one clock, and the idle time before the call lies under a stage."""
    tr, _, _ = _scoped_cut()
    dev = tr.devices[0]
    lo, hi = tr.window
    calls = tracemod.device_calls(dev, lo, hi)
    runs = [r for r, n in zip(tr.host.tolist(), tr.host_names)
            if n == "sweep.run"]
    assert len(calls) == 1 and runs
    assert all(any(a <= c0 and c1 <= b for a, b in runs)
               for c0, c1 in calls.tolist())
    lower = [r for r, n in zip(tr.host.tolist(), tr.host_names)
             if n == "sweep.lower"]
    assert lower and max(b for _, b in lower) <= calls[0][0]


def test_phase_readers_on_the_scoped_cut(monkeypatch):
    tr, smap, meta = _scoped_cut()
    monkeypatch.setattr(scopes, "program_scopes", lambda: smap)
    cell = harness.load_cell(meta["cell"])
    run = harness.RunData(cell=cell, units=[], trace=tr, devices=1)
    dev = tr.devices[0]
    lo, hi = tr.window
    own = scopes.own_by_scope(dev, lo, hi, smap)
    assert None not in own                 # the map names every op
    assert sum(own.values()) == tracemod.busy_in_calls_ns(dev, lo, hi)
    # per step of the cut, each phase reads as in the whole unit
    steps = dev.op_names.count(meta["step_marker"])
    assert steps > 20
    full = meta["full_unit"]["scope_ms"]
    for phase, names in scopes.PHASES.items():
        got = sum(own.get(s, 0) for s in names) * 1e-6 / steps
        want = sum(full.get(s, 0.0) for s in names)
        assert got == pytest.approx(want, rel=0.15), phase
        assert scopes.phase_ms(run, phase) > 0


def test_all_seven_readers_on_the_scoped_cut(monkeypatch):
    """The seven readers of the program's spans, scopes and counters on
    the scoped cut: the phases from its trace and scope map, the host
    stages from a call record made of the cut's ``sweep.*`` host spans
    (the cut holds no counters, so the record's are made by hand)."""
    tr, smap, meta = _scoped_cut()
    lo, hi = tr.window
    stages = {}
    for (a, b), n in zip(tr.host.tolist(), tr.host_names):
        if n.startswith("sweep."):
            key = n[len("sweep."):]
            stages[key] = stages.get(key, 0.0) + (min(b, hi) - max(a, lo)) \
                * 1e-9
    assert stages["lower"] > 0 and stages["run"] > 0
    counters = {"live_flow_steps": 27113, "flow_slots": 18_000_000}
    rec = SimpleNamespace(start=2.0, end=3.0, stages=stages,
                          counters=counters, programs=())
    monkeypatch.setattr(scopes, "_records", lambda: [rec])
    monkeypatch.setattr(scopes, "program_scopes", lambda: smap)
    bench = harness.load_benchmark()
    cell = harness.load_cell(meta["cell"])
    traced = harness.Unit(start=0.0, end=(hi - lo) * 1e-9, build_s=0.03,
                          prints=[])
    other = harness.Unit(start=1.9, end=3.1, build_s=0.03, prints=[])
    run = harness.RunData(cell=cell, units=[traced, other], traced=traced,
                          trace=tr, devices=1, unit_bytes=1e6,
                          peaks=harness.device_peaks("TPU v5 lite"),
                          mem_peak_bytes=49_188_352)
    names = harness.per_layer_names(bench, cell.name)
    assert set(NEW) <= set(names)
    got = harness.read_per_layer(bench, run)
    assert set(got) == set(names)
    assert got["host_lower_s"]["value"] == stages["lower"]
    assert got["host_score_s"]["value"] == (stages.get("fetch", 0.0)
                                            + stages.get("score", 0.0))
    assert got["scan_live_pct"]["value"] == pytest.approx(
        100 * 27113 / 18_000_000)
    phases = sum(got[f"scan_{p}_ms"]["value"] for p in scopes.PHASES)
    assert 0 < phases <= got["scan_step_ms"]["value"]


HLO = """\
%fused_computation.7 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %add.3 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(run_cells)/while/body/step.hops/add"}
  ROOT %mul.4 = f32[8]{0} multiply(%add.3, %add.3), metadata={op_name="jit(run_cells)/while/body/step.hops/mul"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="cells"}
  %fusion.5 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(run_cells)/while/body/step.monitor/step.rings/scatter"}
  %add_select_fusion.6 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.7
  ROOT %copy.8 = f32[8]{0} copy(%add_select_fusion.6)
}
"""


def test_hlo_scopes():
    got = scopes.hlo_scopes(HLO)
    assert got["%fusion.5"] == "step.rings"       # the innermost scope
    assert got["%add_select_fusion.6"] == "step.hops"  # its fused ops'
    assert got["%copy.8"] == "" and got["%Arg_0.1"] == ""
    assert scopes.op_key("%fusion.5 fusion") == "%fusion.5"
