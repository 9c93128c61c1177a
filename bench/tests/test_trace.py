"""The reduction from a profiler trace to busy time, idle share and step
time: on a trace made by hand, and on a unit of ``tb8.fluid.5pol``
recorded on a TPU v5e (cut to its first milliseconds and committed)."""
import gzip
import json
from pathlib import Path

import numpy as np
from bench import trace as tracemod

DATA = Path(__file__).parent / "data"


def _dense_busy(iv, lo, hi):
    """Busy nanoseconds by brute force on a 1 ns grid."""
    on = np.zeros(hi - lo, bool)
    for a, b in iv:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            on[a - lo:b - lo] = True
    return int(on.sum())


def _hand_trace():
    ops = np.array([[10, 20], [15, 30], [40, 45], [44, 50], [70, 90],
                    [95, 200]], np.int64)
    dev = tracemod.Device("/device:TPU:0", ops,
                          ["a", "b", "a", "c", "scan", "tail"],
                          np.array([[8, 52], [60, 92], [95, 96]], np.int64))
    host = np.array([[0, 100], [50, 69], [52, 60]], np.int64)
    return tracemod.Trace([dev], host, ["bench.unit", "build", "gen"],
                          (0, 100))


def test_union_and_busy_against_brute_force():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 900, 200)
    iv = np.stack([a, a + rng.integers(0, 60, 200)], 1)
    for lo, hi in ((0, 1000), (100, 400), (950, 960)):
        assert tracemod.busy_ns(iv, lo, hi) == _dense_busy(iv, lo, hi)
    u = tracemod.union(iv, 0, 1000)
    assert (u[1:, 0] > u[:-1, 1]).all()


def test_hand_trace_reduction():
    tr = _hand_trace()
    dev = tr.devices[0]
    lo, hi = tr.window
    assert tracemod.busy_ns(dev.ops, lo, hi) == 20 + 10 + 20 + 5
    # device calls are the long module runs: [8,52) and [60,92)
    calls = tracemod.device_calls(dev, lo, hi)
    assert calls.tolist() == [[8, 52], [60, 92]]
    assert tracemod.busy_in_calls_ns(dev, lo, hi) == 20 + 10 + 20
    # own time: "b" overlaps the end of the first "a" and takes 5 ns from
    # it, "c" the end of the second and takes 1 ns
    top = tracemod.top_ops(dev, lo, hi, 3)
    assert [n for n, _ in top] == ["scan", "b", "a"]
    assert np.allclose([s for _, s in top], [20e-9, 15e-9, 9e-9],
                       rtol=1e-9, atol=0)
    gaps = tracemod.idle_gaps(tr, dev, 3)
    # the longest gap, [50,70), is named by the innermost host event over
    # its middle (60): "build"; "gen" ends at 60
    assert gaps[0][0] == "build"
    assert [g[0] for g in gaps[1:]] == ["bench.unit", "bench.unit"]
    assert [round(g[1] * 1e9) for g in gaps] == [20, 10, 10]


def test_round_trip(tmp_path):
    tr = _hand_trace()
    tr.save(str(tmp_path / "t.json.gz"))
    back = tracemod.Trace.read(str(tmp_path / "t.json.gz"))
    assert back.window == tr.window
    assert back.devices[0].op_names == tr.devices[0].op_names
    assert np.array_equal(back.devices[0].ops, tr.devices[0].ops)
    assert np.array_equal(back.host, tr.host)


def _sweep_busy(iv, lo, hi):
    """Busy nanoseconds by a plain sweep over start and end points."""
    pts = []
    for a, b in iv:
        a, b = max(int(a), lo), min(int(b), hi)
        if b > a:
            pts += [(a, 1), (b, -1)]
    pts.sort()
    busy, depth, since = 0, 0, None
    for t, d in pts:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_unit():
    tr = tracemod.Trace.read(str(DATA / "tb8.fluid.5pol.cut.json.gz"))
    with gzip.open(DATA / "tb8.fluid.5pol.cut.json.gz", "rt") as f:
        meta = json.load(f)["meta"]
    dev = tr.devices[0]
    lo, hi = tr.window
    busy = tracemod.busy_ns(dev.ops, lo, hi)
    assert busy == _sweep_busy(dev.ops, lo, hi)
    idle = 1.0 - busy / (hi - lo)
    # 60 ms of host work before the device call, 40 ms of the call
    assert 0.55 < idle < 0.65
    calls = tracemod.device_calls(dev, lo, hi)
    assert len(calls) == 1 and calls[0][0] == lo + 60_000_000
    # one marker op per cell step: the step time read from the cut agrees
    # with the whole unit's scan_step_ms measured on the chip
    steps = dev.op_names.count(meta["step_marker"] + " copy-start")
    assert steps > 50
    step_ms = tracemod.busy_in_calls_ns(dev, lo, hi) * 1e-6 / steps
    assert abs(step_ms / meta["full_unit"]["scan_step_ms"] - 1) < 0.05
    top = tracemod.top_ops(dev, lo, hi)
    assert len(top) == 10 and all(s > 0 for _, s in top)
    assert sum(s for _, s in top) <= busy * 1e-9


# the per-layer metrics that read a trace with no program spans, scopes
# or counters, as the recorded cut is; test_scopes.py reads the others
# on a scoped cut of the same cell
TRACE_ONLY = ("host_build_s", "host_other_s", "scan_step_ms",
              "scan_roofline", "device_idle_pct", "peak_mem_gb")


def test_per_layer_readers_on_the_recorded_unit():
    from bench import harness
    bench = harness.load_benchmark()
    cell = harness.load_cell("tb8.fluid.5pol")
    tr = tracemod.Trace.read(str(DATA / "tb8.fluid.5pol.cut.json.gz"))
    lo, hi = tr.window
    traced = harness.Unit(start=0.0, end=(hi - lo) * 1e-9, build_s=0.03,
                          prints=[])
    other = harness.Unit(start=1.0, end=1.09, build_s=0.03, prints=[])
    peaks = harness.device_peaks("TPU v5 lite")
    run = harness.RunData(cell=cell, units=[traced, other], traced=traced,
                          trace=tr, devices=1, unit_bytes=1e6, peaks=peaks,
                          mem_peak_bytes=49_188_352)
    got = harness.read_per_layer(bench, run)
    assert set(TRACE_ONLY) <= set(harness.per_layer_names(bench, cell.name))
    assert set(got) == set(TRACE_ONLY)
    busy_s = tracemod.busy_ns(tr.devices[0].ops, lo, hi) * 1e-9
    assert got["host_build_s"]["value"] == 0.03
    assert abs(got["host_other_s"]["value"] - (0.06 - busy_s)) < 1e-12
    assert abs(got["device_idle_pct"]["value"]
               - 100 * (1 - busy_s / ((hi - lo) * 1e-9))) < 1e-9
    assert 0 < got["scan_roofline"]["value"] < 100
    assert got["peak_mem_gb"] == {"value": 0.049188352, "unit": "GB"}
