"""The benchmark harness: cells from data files, the timed window, the
traced unit, the per-layer readers and the comparison that decides
``correct``.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration is ``bench/configs/<config>.json`` (the fabric: scenario
string, link-rate scale, traffic window) and its traffic is
``bench/traffic/<traffic>.json`` (what a user submits: engine, policies,
pairs, loads, flow sizes). Every key of either that is a field of the
program's ``ExpSpec`` reaches each spec of the cell's grid; a traffic
file holds nothing else but the grid's keys, ``policies`` and ``seeds``
(policies x seeds, from the run's seed up). A configuration may name its
reference, ``bench/reference/<reference>.py`` (default ``sim``), whose
``run_cell`` answers each cell of the grid. A cell on more than one chip
shards its grid over them. The limits of its comparison are
``bench/limits/<cell>.json``. A per-layer metric is the
module ``bench/metrics/<name>.py``, whose ``read(run)`` returns the
number or None. Nothing here names a cell, a configuration or a metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import importlib
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.reference.npsim import Config

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
# the profiler's Python tracer names the host's work in idle gaps
PYTHON_TRACER_LEVEL = 1

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    params: dict     # the configuration's and the traffic's keys merged
    limits: dict     # number compared -> its limit
    reference: str = "sim"   # module of bench.reference that answers it

    @property
    def policies(self) -> List[str]:
        return list(self.params["policies"])

    @property
    def seeds(self) -> int:
        return int(self.params.get("seeds", 1))

    def grid(self, seed: int) -> List[Tuple[str, int]]:
        """(policy, seed) of each cell of the unit at the run's ``seed``."""
        return [(pol, seed + k) for k in range(self.seeds)
                for pol in self.policies]

    @property
    def num_cells(self) -> int:
        return len(self.policies) * self.seeds

    @property
    def num_steps(self) -> int:
        return 2 * self.params["duration_us"] // self.params["dt_us"]

    def sim_ms_per_unit(self) -> float:
        """Simulated milliseconds one unit answers: every cell's nominal
        horizon, twice the traffic window."""
        return self.num_cells * 2 * self.params["duration_us"] / 1000.0


CONFIG_KEYS = ("topology", "cap_scale", "duration_us")
TRAFFIC_KEYS = ("engine", "policies", "workload", "pairs", "load")
GRID_KEYS = ("policies", "seeds")
# ExpSpec fields that the grid sets, one value per cell
GRID_SET = ("policy", "seed")


def spec_fields() -> frozenset:
    from repro.netsim.experiment import ExpSpec
    return frozenset(f.name for f in dataclasses.fields(ExpSpec))


def load_cell(name: str, bench: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cfg = load_json(root / conf["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    for k in CONFIG_KEYS:
        if k not in cfg:
            raise KeyError(f"{conf['file']}: missing {k!r}")
    for k in TRAFFIC_KEYS:
        if k not in traffic:
            raise KeyError(f"traffic {entry['traffic']!r}: missing {k!r}")
    fields = spec_fields()
    params = {k: v for k, v in cfg.items() if k in fields}
    for k in traffic:
        if k in GRID_SET or (k not in fields and k not in GRID_KEYS):
            raise KeyError(f"traffic {entry['traffic']!r}: {k!r} is not a "
                           f"key of the program's spec or of the grid")
        if k in params:
            raise KeyError(f"traffic {entry['traffic']!r}: {k!r} is set "
                           f"by the configuration {conf['name']!r}")
    if int(traffic.get("seeds", 1)) < 1:
        raise ValueError(f"traffic {entry['traffic']!r}: seeds < 1")
    params["dt_us"] = Config.dt_us
    params.update(traffic)
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(entry["chips"]), params=params,
                limits=limits["limits"],
                reference=cfg.get("reference", "sim"))


def cell_specs(cell: Cell, seed: int):
    """The unit's grid at ``seed``: one spec per (policy, seed), each
    with every key of the cell that is a field of ``ExpSpec``."""
    from repro.netsim.experiment import ExpSpec
    fields = spec_fields()
    base = {k: v for k, v in cell.params.items() if k in fields}
    return [ExpSpec(**base, policy=pol, seed=s) for pol, s in cell.grid(seed)]


@dataclasses.dataclass
class Unit:
    start: float           # host clock, seconds
    end: float
    build_s: float         # SweepReport.build_s
    prints: List[str]      # one fingerprint per cell of the grid

    @property
    def wall_s(self) -> float:
        return self.end - self.start


def fingerprint(result) -> str:
    h = hashlib.sha256()
    for f in ("done", "fct_us", "flow_path", "serv_bytes"):
        h.update(np.ascontiguousarray(getattr(result.final, f)).tobytes())
    return h.hexdigest()


def run_unit(cell: Cell, specs):
    """One unit: the whole user path of one ``run_sweep`` call over the
    cell's grid (flows and world build, padding, the jitted scan, copy
    back and scoring). A cell on several chips shards its grid over
    them. Returns the unit's record and the sweep report."""
    from repro.netsim.sweep import run_sweep
    mesh = {"use_mesh": True, "devices": cell.chips} if cell.chips > 1 else {}
    t0 = time.perf_counter()
    rep = run_sweep(specs, **mesh)
    t1 = time.perf_counter()
    return Unit(start=t0, end=t1, build_s=rep.build_s,
                prints=[fingerprint(r) for r in rep.results]), rep


class CompileCounter:
    """Counts XLA compilations, persistent-cache loads and jaxpr traces
    through ``jax.monitoring``, and the seconds spent compiling. A load
    from the cache goes through the same compile event, with its hit
    reported inside it, so compilations are events less cache hits, and
    ``compile_s`` sums the events that had no hit."""

    def __init__(self):
        self.backend = self.hits = self.traces = 0
        self.compile_s = 0.0
        self._loads = 0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.backend += 1
            if self._loads:
                self._loads -= 1
            else:
                self.compile_s += duration
        elif event == TRACE_EVENT:
            self.traces += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT:
            self.hits += 1
            self._loads += 1

    def snapshot(self):
        return (self.backend - self.hits, self.hits, self.traces)


# ------------------------------------------------------------- correctness
def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (np.isfinite(a) and np.isfinite(b)):
        return float("inf")
    return abs(a - b) / max(abs(b), 1e-12)


CHECK_NAMES = ("flow_rows_differ", "completed_gap", "p50_gap", "p99_gap",
               "fg_p99_gap", "fct_gap_median", "path_share_gap", "util_gap")


def compare_cell(prog, ref) -> Dict[str, float]:
    """The numbers compared for one policy cell: the program's answer
    (a ``CellResult``) against the reference's."""
    pf, rf = prog.flows, ref.flows
    if pf.num_flows != rf.num_flows:
        rows = abs(pf.num_flows - rf.num_flows) + min(pf.num_flows,
                                                      rf.num_flows)
    else:
        rows = int(np.count_nonzero(
            (pf.arrival_us != rf.arrival_us) | (pf.size_bytes != rf.size_bytes)
            | (pf.pair_id != rf.pair_id) | (pf.flow_id != rf.flow_id)))
    out = {"flow_rows_differ": float(rows)}
    ps, rs = prog.stats, ref.stats
    out["completed_gap"] = abs(ps.completed - rs.completed) / max(rs.offered, 1)
    out["p50_gap"] = _rel(ps.p50, rs.p50)
    out["p99_gap"] = _rel(ps.p99, rs.p99)
    out["fg_p99_gap"] = _rel(prog.stats_fg.p99, ref.stats_fg.p99)
    if rows == 0:
        pd, rd = prog.final.done, ref.final.done
        both = pd & rd
        fp, fr = prog.final.fct_us[both], ref.final.fct_us[both]
        gap = np.abs(fp.astype(np.float64) - fr) / np.maximum(np.abs(fr), 1e-9)
        out["fct_gap_median"] = float(np.median(gap)) if len(gap) else 0.0
        npaths = int(max(prog.final.flow_path.max(initial=0),
                         ref.final.flow_path.max(initial=0))) + 2
        share = [np.bincount(x.final.flow_path + 1, minlength=npaths)
                 / max(len(x.final.flow_path), 1) for x in (prog, ref)]
        out["path_share_gap"] = float(np.abs(share[0] - share[1]).max())
    else:
        out["fct_gap_median"] = float("inf")
        out["path_share_gap"] = float("inf")
    out["util_gap"] = float(np.abs(np.asarray(prog.util, np.float64)
                                   - ref.util).max()) \
        if len(prog.util) == len(ref.util) else float("inf")
    return out


def reference_cells(cell: Cell, seed: int, precision: str = "float64"):
    """The reference's answer for every cell of the grid, in grid order.
    The grid's keys stay here: each cell gets its policy and seed."""
    ref = importlib.import_module(f"bench.reference.{cell.reference}")
    params = {k: v for k, v in cell.params.items() if k not in GRID_KEYS}
    return [ref.run_cell(dict(params), pol, s, precision)
            for pol, s in cell.grid(seed)]


def check(cell: Cell, results, refs):
    """Worst reading of each number over the grid's cells, and the
    indices of the cells that broke a limit."""
    worst: Dict[str, float] = {}
    bad = []
    for j, (p, r) in enumerate(zip(results, refs)):
        nums = compare_cell(p, r)
        for k, v in nums.items():
            worst[k] = max(worst.get(k, 0.0), v)
        if any(not (nums[k] <= cell.limits[k]) for k in cell.limits):
            bad.append(j)
    return worst, bad


# -------------------------------------------------------------- per layer
@dataclasses.dataclass
class RunData:
    """What a per-layer reader may read."""
    cell: Cell
    units: List[Unit]                 # the window's units
    traced: Optional[Unit] = None     # the traced unit (also in units)
    trace: object = None              # bench.trace.Trace of that unit
    devices: int = 1                  # chips the unit ran on
    unit_bytes: Optional[float] = None  # least bytes of one unit's scans
    peaks: Optional[dict] = None
    mem_peak_bytes: Optional[int] = None


def per_layer_names(bench: dict, cell_name: str) -> List[str]:
    return [m["name"] for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def read_per_layer(bench: dict, run: RunData) -> Dict[str, dict]:
    """Each of the cell's per-layer metrics that its reader finds."""
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    out = {}
    for name in per_layer_names(bench, run.cell.name):
        v = importlib.import_module(f"bench.metrics.{name}").read(run)
        if v is not None:
            out[name] = {"value": v, "unit": units[name]}
    return out


def unit_bytes(cell: Cell, results) -> float:
    """Least bytes moved by the scans of one unit, from its answers."""
    from bench import roofline
    from bench.reference.world import build_world
    _, table = build_world(cell.params["topology"])
    tot = 0.0
    for r in results:
        fp = np.asarray(r.final.flow_path)
        idx = np.maximum(fp, 0)
        tot += roofline.cell_bytes(
            cell.params["engine"], r.flows.arrival_us, r.final.fct_us,
            r.final.done, fp >= 0, table.path_len[idx],
            table.path_prop_us[idx], len(table._link_caps),
            cell.params["dt_us"], cell.num_steps)
    return tot


# ------------------------------------------------------------------- main
def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def chips_or_none(want: int):
    """The first ``want`` TPU devices, or None where JAX has fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < want:
        return None
    return devs[:want]


def device_peaks(kind: str) -> Optional[dict]:
    """The chip's published peaks, or None for a kind not in the table."""
    return load_json(BENCH / "peaks.json")["devices"].get(kind)


def mem_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def traced_unit(cell: Cell, specs):
    """One unit under the profiler; its trace stays in ``TRACE_DIR``."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = PYTHON_TRACER_LEVEL
    with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.unit"):
            return run_unit(cell, specs)


def reduce_trace(cell: Cell):
    """The traced unit's compact trace; the raw profile is deleted and the
    compact one kept as ``TRACE_DIR/<cell>.json.gz``."""
    from bench import trace as tracemod
    t0 = time.perf_counter()
    tr = tracemod.load(str(TRACE_DIR), devices=cell.chips)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir()
    tr.save(str(TRACE_DIR / f"{cell.name}.json.gz"))
    print(f"trace: {sum(len(d.ops) for d in tr.devices)} device ops, "
          f"{len(tr.host)} host events, read in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return tr


def main(argv, t_process: float) -> int:
    args = parse(argv)
    bench = load_benchmark()
    cell = load_cell(args.workload, bench)
    devs = chips_or_none(cell.chips)
    t_chip = time.perf_counter()
    if devs is None:
        err(f"no TPU with {cell.chips} chip(s) found; nothing measured")
        return 2
    kind = devs[0].device_kind
    peaks = device_peaks(kind)
    if peaks is None:
        err(f"device kind {kind!r} is not in bench/peaks.json")
        return 2

    import jax

    from repro import compile_cache
    cache_dir = compile_cache.enable()
    # cache every program, however fast it compiled: a program that is
    # not in the cache would compile again inside the window
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    counter.install()

    specs = cell_specs(cell, args.seed)
    warm, _ = run_unit(cell, specs)
    c_setup = counter.snapshot()
    t_win = time.perf_counter()
    # set-up as a warm run has it: the flow tables' shapes follow the
    # seed, so the first run of each seed compiles its scan, and that
    # compilation is reported on its own
    compile_s = counter.compile_s
    setup_s = t_win - t_process - compile_s
    print(f"setup: {setup_s:.3f} s (to the chip {t_chip - t_process:.3f} s, "
          f"warm-up unit {warm.wall_s:.3f} s); "
          f"compiles={c_setup[0]} compile_s={compile_s:.3f} "
          f"cache_loads={c_setup[1]} cache_dir={cache_dir}", flush=True)

    units: List[Unit] = []
    traced = tr = None
    while True:
        if args.trace and traced is None:
            u, last = traced_unit(cell, specs)
            traced = u
        else:
            u, last = run_unit(cell, specs)
        units.append(u)
        if u.end - t_win >= args.seconds:
            break
    c_win = counter.snapshot()
    compiles = c_win[0] - c_setup[0]
    print(f"window: {units[-1].end - t_win:.3f} s, units={len(units)} "
          f"compiles={compiles} cache_loads={c_win[1] - c_setup[1]} "
          f"traces={c_win[2] - c_setup[2]}", flush=True)
    if compiles:
        err(f"{compiles} compilation(s) inside the measured window")
        return 3
    mem = mem_peak(devs)
    results = last.results
    del last
    if args.trace:
        tr = reduce_trace(cell)
    sim_rate = (cell.sim_ms_per_unit() * len(units)
                / (units[-1].end - t_win))

    refs = reference_cells(cell, args.seed)
    worst, bad = check(cell, results, refs)
    final = units[-1].prints
    failed = sum(1 for u in units for j, fp in enumerate(u.prints)
                 if fp != final[j] or j in bad)
    attempted = len(units) * cell.num_cells
    correct = failed == 0 and all(
        worst[k] <= cell.limits[k] for k in cell.limits)

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    line = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        from bench import trace as tracemod
        lo, hi = tr.window
        busy = [tracemod.busy_ns(d.ops, lo, hi) for d in tr.devices]
        device["busy_s"] = float(np.mean(busy)) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        run = RunData(cell=cell, units=units, traced=traced, trace=tr,
                      devices=len(devs), unit_bytes=unit_bytes(cell, results),
                      peaks=peaks, mem_peak_bytes=mem)
        line["metrics"] = read_per_layer(bench, run)
        line["device"] = device
        line["breakdown"] = {
            "device_ops": tracemod.top_ops(tr.devices[0], lo, hi),
            "idle_gaps": tracemod.idle_gaps(tr, tr.devices[0])}
    else:
        line["metrics"] = {
            "sim_rate": {"value": sim_rate, "unit": "sim_ms/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        line["device"] = device
    line["checks"] = {k: {"value": worst[k], "limit": cell.limits[k]}
                      for k in cell.limits}
    for k in cell.limits:
        err(f"check {k}: {worst[k]!r} (limit {cell.limits[k]!r})")
    print(json.dumps(line), flush=True)
    return 0
