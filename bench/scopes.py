"""What the program's own spans, scopes and counters say about a run.

Device side: each scan op carries the ``step.<phase>`` named scope it
was traced under, in the ``op_name`` metadata of the compiled module's
HLO. A profiler op event names its op by the HLO instruction
(``%fusion.337 fusion`` in ``bench.trace``), so ``hlo_scopes`` maps each
instruction name to the innermost ``step.*`` scope of its path, and
``own_by_scope`` hands every busy nanosecond inside the unit's device
calls to the innermost op running then: the scopes' own times, with
``""`` for ops outside any ``step.*`` scope, sum to
``trace.busy_in_calls_ns``. A fusion carries the scope of its root op.
A map that leaves more than ``UNNAMED_SHARE`` of that time to ops it
does not name belongs to another program, and is not used.

Host side: the program records each batched ``run_sweep`` call
(``repro.netsim.sweep.RECENT``: its start and end on the host clock,
seconds per ``sweep.*`` stage, counters, and the newest call's compiled
programs). ``unit_records`` finds a unit's call by its clock.

Where the program records none of this, every reader here returns None.
"""
from __future__ import annotations

import heapq
import math
import re
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

from bench import trace as tracemod

# the scan's phases that each per-layer metric reads
PHASES = {
    "route": ("step.route", "step.redecide"),
    "links": ("step.links", "step.pfc", "step.inject", "step.hops",
              "step.drain"),
    "cc": ("step.cc",),
    "ctrl": ("step.failover", "step.monitor", "step.ctrl", "step.redte",
             "step.rings", "step.counters"),
}

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# the share of the device calls' busy time whose ops a scope map may
# fail to name before it is taken for another program's map
UNNAMED_SHARE = 0.01
_STEP = re.compile(r"(?:^|/)(step\.[A-Za-z0-9_]+)(?=/|$)")


def innermost_step(op_name: str) -> str:
    """``jit(run_cells)/.../step.monitor/step.rings/scatter`` ->
    ``step.rings``; ``""`` outside every ``step.*`` scope."""
    found = _STEP.findall(op_name)
    return found[-1] if found else ""


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name (with its ``%``) -> innermost ``step.*`` scope
    (``""``: none), for every instruction of the module. A fusion that
    the compiler left without metadata takes the scope most of its fused
    ops carry."""
    out: Dict[str, str] = {}
    fused: Dict[str, List[str]] = {}    # computation -> its ops' scopes
    calls: Dict[str, str] = {}          # unscoped fusion -> computation
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            head = _COMP.match(line)
            if head:
                comp = head.group(1)
                fused[comp] = []
            continue
        meta = _OP_NAME.search(line, m.end())
        scope = innermost_step(meta.group(1)) if meta else ""
        out["%" + m.group(1)] = scope
        if scope and comp is not None:
            fused[comp].append(scope)
        called = _CALLS.search(line, m.end())
        if not scope and called:
            calls["%" + m.group(1)] = called.group(1)
    for name, comp in calls.items():
        if fused.get(comp):
            out[name] = Counter(fused[comp]).most_common(1)[0][0]
    return out


def op_key(trace_op_name: str) -> str:
    """``%fusion.337 fusion`` (``trace.short_op_name``) -> ``%fusion.337``."""
    return trace_op_name.split(" ", 1)[0]


def own_by_label(iv: np.ndarray, labels: List, lo: int,
                 hi: int) -> Dict:
    """Nanoseconds of [lo, hi) under at least one interval, each handed to
    the innermost interval over it (the one that started last), summed
    by that interval's label."""
    iv = np.asarray(iv, np.int64).reshape(-1, 2)
    clipped = np.clip(iv, lo, hi)
    keep = np.flatnonzero(clipped[:, 1] > clipped[:, 0])
    if not len(keep):
        return {}
    order = keep[np.lexsort((-iv[keep, 1], iv[keep, 0]))]
    starts, ends = clipped[order, 0], clipped[order, 1]
    labs = [labels[i] for i in order]
    pts = np.unique(np.concatenate([starts, ends]))
    out: Dict = {}
    heap: List = []
    i, n = 0, len(starts)
    for x, y in zip(pts[:-1].tolist(), pts[1:].tolist()):
        while i < n and starts[i] <= x:
            heapq.heappush(heap, (-i, int(ends[i])))
            i += 1
        while heap and heap[0][1] <= x:
            heapq.heappop(heap)
        if heap:
            lab = labs[-heap[0][0]]
            out[lab] = out.get(lab, 0) + (y - x)
    return out


def own_by_scope(dev: tracemod.Device, lo: int, hi: int,
                 scopes: Dict[str, str]) -> Dict:
    """Own device nanoseconds per ``step.*`` scope (``""``: none, None:
    an op the map does not name) inside the device's calls in [lo, hi)."""
    labels = [scopes.get(op_key(n)) for n in dev.op_names]
    out: Dict = {}
    for a, b in tracemod.device_calls(dev, lo, hi):
        for k, v in own_by_label(dev.ops, labels, int(a), int(b)).items():
            out[k] = out.get(k, 0) + v
    return out


def _records():
    try:
        from repro.netsim import sweep
    except ImportError:
        return []
    return list(getattr(sweep, "RECENT", ()))


def program_scopes() -> Optional[Dict[str, str]]:
    """The scope map of the newest recorded call's compiled programs."""
    recs = _records()
    progs = getattr(recs[-1], "programs", ()) if recs else ()
    out: Dict[str, str] = {}
    for p in progs:
        out.update(hlo_scopes(p.as_text()))
    return out or None


_memo: list = [None, None, None]   # run, scope map, scope_ms: last read


def scope_ms(run) -> Optional[Dict[str, float]]:
    """Own milliseconds per ``step.*`` scope per cell step (the
    denominator of ``scan_step_ms``: cells per chip x steps), the mean
    over the chips; None without a trace or a scope map."""
    if run.trace is None:
        return None
    scopes = program_scopes()
    if _memo[0] is not run or _memo[1] != scopes:
        _memo[:] = [run, scopes, _scope_ms(run, scopes)]
    return _memo[2]


def _scope_ms(run, scopes) -> Optional[Dict[str, float]]:
    if not scopes:
        return None
    lo, hi = run.trace.window
    per_step = math.ceil(run.cell.num_cells / run.devices) \
        * run.cell.num_steps
    tot: Dict[str, float] = {}
    for d in run.trace.devices:
        for k, v in own_by_scope(d, lo, hi, scopes).items():
            tot[k] = tot.get(k, 0.0) + v * 1e-6 / per_step
    busy = sum(tot.values())
    if not busy or tot.get(None, 0.0) > UNNAMED_SHARE * busy \
            or not any(k for k in tot):
        return None
    tot.pop(None, None)
    return {k: v / len(run.trace.devices) for k, v in tot.items()}


def phase_ms(run, phase: str) -> Optional[float]:
    ms = scope_ms(run)
    if ms is None:
        return None
    return float(sum(ms.get(s, 0.0) for s in PHASES[phase]))


def unit_records(unit):
    """The recorded calls that ran inside ``unit``'s clock."""
    return [r for r in _records()
            if unit.start <= r.start and r.end <= unit.end]


def mean_stage_s(run, stages) -> Optional[float]:
    """Seconds per unit in ``stages``, the mean over the units that ran
    without the profiler (as ``host_build_s``); None where no unit has a
    recorded call."""
    units = [u for u in run.units if u is not run.traced] or run.units
    per = []
    for u in units:
        recs = unit_records(u)
        if recs:
            per.append(sum(r.stages.get(s, 0.0) for r in recs
                           for s in stages))
    return float(np.mean(per)) if per else None
