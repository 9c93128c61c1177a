"""Batched scenario-sweep engine: many experiment cells, one XLA program.

The paper's evaluation is a grid — topologies x workloads x loads x
policies x seeds (§6, Figs. 5-11). Running each ``ExpSpec`` through
``fluid.run`` one at a time re-traces and re-compiles the jitted scan for
every cell. This engine instead:

1. groups cells by their *static* key — everything that changes the
   traced program: scenario string (topology + schedules), simulation
   engine (fluid/packet, see ``repro.netsim.engine``), cc law,
   cap_scale, duration, the re-decision-plane knobs
   (``flowlet_gap_us``/``redecide_period_us``/``n_subflows``), and the
   Select/PathQ/Cong parameter dataclasses.
   Policy is NOT part of the key: ``fluid`` dispatches it dynamically on
   the per-cell ``policy_code`` (cfg.policy == "sweep"), so an entire
   load x policy figure grid is ONE group — re-decision-capable policies
   (``engine.REDECIDE_POLICIES``) included, their tick is gated per cell
   by ``policy_code`` so pinned cells sharing the trace stay bit-exact;
2. pads each group's per-cell arrays (flow tables and answers to the
   max flow count, arrival buckets to the max per-step batch — both
   padding-invariant by construction: padded flows never arrive, so
   they never take a slot of the active-flow window) and stacks them
   along a leading cell axis, every cell with a window of the same
   width (``engine.fitted_window``; regrown and run again where a
   cell's flows in flight outgrow it);
3. runs the whole group as ONE jitted invocation — one trace, one
   compile, one device dispatch — either ``jax.vmap`` over the cell axis
   (dispatch-bound small cells) or a compiled ``jax.lax.map`` loop over
   cells (compute-bound large cells, where vmap's batched-scatter
   lowering costs ~30% on CPU), and optionally ``jax.shard_map``s the
   cell axis across the host mesh (``repro.launch.mesh.make_host_mesh``)
   when multiple devices exist.

Per-cell results are bit-for-bit identical to the sequential loop (the
tier-1 suite asserts exact FCT equality): vmap batches the same IEEE ops,
padded flows never activate, and padded arrival slots take no slot.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from types import SimpleNamespace
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_host_mesh
from repro.netsim import engine as enginemod
from repro.netsim import fluid, metrics, sanitize
from repro.netsim.engine import SimArrays, SimState
from repro.netsim.experiment import (ExpSpec, build_world, make_flows,
                                     run_experiment, spec_to_cfg)
from repro.netsim.spans import span

# the batched path's host stages, in call order: one ``sweep.<stage>``
# span each (``repro.netsim.spans``), together the call's wall time
STAGES = ("group", "world", "flows", "pad", "lower", "run", "fetch",
          "score")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CellArrays:
    """The per-cell slice of ``SimArrays`` — everything a load/seed/
    workload/policy axis can change. The rest of ``SimArrays`` (link and
    path tables, schedules, switch tables) is shared across the group and
    enters the vmap unbatched."""
    arrivals: jnp.ndarray      # (T, A) i32
    f_arr_us: jnp.ndarray      # (F,) f32
    f_size: jnp.ndarray        # (F,) f32
    f_pair: jnp.ndarray        # (F,) i32
    f_id: jnp.ndarray          # (F,) u32
    policy_code: jnp.ndarray   # () i32


@dataclasses.dataclass
class CellResult:
    """One cell's outputs, sliced back out of the batch (numpy)."""
    spec: ExpSpec
    stats: metrics.FCTStats
    util: np.ndarray           # (L,) effective-capacity utilization
    final: SimpleNamespace     # done / fct_us / flow_path / serv_bytes / c_path
    flows: object              # the cell's FlowSet
    # foreground/background split when the cell doses cross-traffic
    # (spec.bg_load > 0): stats over the measured pairs vs the rest.
    # stats_fg == stats and stats_bg is None for all-foreground cells.
    stats_fg: metrics.FCTStats = None
    stats_bg: metrics.FCTStats = None
    # the scan's work counters (``engine.count_live``): live_flow_steps,
    # live_flow_peak
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SweepReport:
    results: List[CellResult]  # in the order of the input specs
    num_cells: int
    num_groups: int
    wall_s: float
    group_cells: List[int]     # cells per compiled group
    # batched path only: host seconds building worlds, flow tables and
    # padded cell stacks (stages world + flows + pad)
    build_s: Optional[float] = None
    # batched path only: host seconds per stage of ``STAGES``
    stages: Dict[str, float] = dataclasses.field(default_factory=dict)
    # the cells' counters (live_flow_steps summed, live_flow_peak the
    # largest) and the slots they fill: flow_slots (padded F x steps x
    # cells), window_slots (the window's W x steps x cells) and
    # window_regrows (runs repeated on a wider window); batched path
    # only: arrivals and arrival_slots (filled entries and T x A of the
    # padded (T, A) tables) and lowerings (group programs lowered in the
    # call, regrown ones included)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __iter__(self):
        return iter(self.results)


@dataclasses.dataclass(frozen=True)
class CallRecord:
    """What a batched ``run_sweep`` call leaves for a reader that did not
    make it, such as a timer around the call or a profile of it."""
    start: float               # time.perf_counter() at the call's start
    end: float                 # ... and at its end
    stages: Dict[str, float]
    counters: Dict[str, int]
    # the call's compiled group programs, regrown ones included (their
    # HLO names each op's ``step.*`` scope); kept for the newest call only
    programs: tuple = ()


# the latest batched calls, newest last
RECENT: Deque[CallRecord] = collections.deque(maxlen=64)


def static_key(spec: ExpSpec):
    """Everything that forces a separate trace/compile. Policy is
    deliberately absent (dynamic dispatch); load/seed/workload/pairs/
    bg_load/load_sched only change array *contents* — a whole diurnal
    schedule grid (``ExpSpec.load_sched``) batches into one trace."""
    scen, _ = build_world(spec.topology)
    return (spec.topology, dataclasses.replace(
        spec_to_cfg(spec, scen), policy="sweep"))


def _pad_tail(a: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad axis 0 of ``a`` to length ``n`` with ``fill``."""
    if a.shape[0] == n:
        return np.asarray(a)
    out = np.full((n,) + a.shape[1:], fill, dtype=np.asarray(a).dtype)
    out[: a.shape[0]] = a
    return out


def _pad_cell(arrs: SimArrays, F: int, A: int) -> CellArrays:
    """One built cell's per-cell arrays padded to the group's (F, A).
    Padded flows never appear in ``arrivals`` (pad = -1), so they never
    take a slot of the window and results are unchanged."""
    T = arrs.arrivals.shape[0]
    arrivals = np.full((T, A), -1, np.int32)
    arrivals[:, : arrs.arrivals.shape[1]] = np.asarray(arrs.arrivals)
    return CellArrays(
        arrivals=jnp.asarray(arrivals),
        f_arr_us=jnp.asarray(_pad_tail(np.asarray(arrs.f_arr_us), F, 0.0)),
        f_size=jnp.asarray(_pad_tail(np.asarray(arrs.f_size), F, 0.0)),
        f_pair=jnp.asarray(_pad_tail(np.asarray(arrs.f_pair), F, 0)),
        f_id=jnp.asarray(_pad_tail(np.asarray(arrs.f_id), F, 0)),
        policy_code=arrs.policy_code,
    )


def _pad_state(state: SimState, F: int, W: int) -> SimState:
    """One built cell's initial state with its answers padded to the
    group's F and its window cut or widened to W free slots. Which fields
    carry a flow or a slot axis (and their inert pad values) is the
    engine core's contract (``engine.FLOW_FIELDS``/``SLOT_FIELDS``; the
    packet engine's extra state is covered there too, and the state's
    own dataclass type is rebuilt)."""
    st = {}
    for f in dataclasses.fields(type(state)):
        v = getattr(state, f.name)
        if f.name in enginemod.FLOW_FIELDS:
            v = jnp.asarray(_pad_tail(np.asarray(v), F,
                                      enginemod.STATE_PAD.get(f.name, 0)))
        st[f.name] = v            # per-link / per-pair: shared shape
    return enginemod.with_window(type(state)(**st), W)


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


# auto batch-mode crossover (flows): below this, a grid is dispatch-bound
# and vmap's wider ops win; above it, it is compute-bound and vmap's
# batched-scatter lowering costs ~30% on CPU while lax.map (a compiled
# loop over cells inside the same single trace) runs at single-cell cost.
_VMAP_MAX_FLOWS = 512


def _group_runner(shared: SimArrays, cfg, mesh=None, mode: str = "vmap"):
    """One jitted callable running every cell of a group at once. The
    simulation backend is the group's static ``cfg.engine`` (part of the
    trace key), so fluid and packet cells batch in separate groups."""
    eng = enginemod.get_engine(cfg.engine)

    def one(cell: CellArrays, st: SimState):
        arrs = dataclasses.replace(
            shared, arrivals=cell.arrivals, f_arr_us=cell.f_arr_us,
            f_size=cell.f_size, f_pair=cell.f_pair, f_id=cell.f_id,
            policy_code=cell.policy_code)
        return eng.run_impl(arrs, st, cfg)

    def run_cells(cells: CellArrays, states: SimState):
        if mode == "vmap":
            return jax.vmap(one)(cells, states)
        return jax.lax.map(lambda args: one(*args), (cells, states))

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        run_cells = jax.shard_map(run_cells, mesh=mesh,
                                  in_specs=(P("data"), P("data")),
                                  out_specs=P("data"), check_vma=False)
    if sanitize.enabled(cfg):
        return sanitize.checked_call(run_cells)
    return jax.jit(run_cells)


def _compile(runner, cells: CellArrays, states: SimState):
    """The group's program, lowered and compiled before its run so that
    the run's span holds the run alone. A runner with no ``lower`` (the
    sanitizer's checkify wrapper) is returned as it is and lowers in its
    first call."""
    lower = getattr(runner, "lower", None)
    return runner if lower is None else lower(cells, states).compile()


def _cell_counters(final, j=()) -> Dict[str, int]:
    """The scan counters of a final state, or of its cell ``j``."""
    return {k: int(np.asarray(getattr(final, k))[j])
            for k in ("live_flow_steps", "live_flow_peak")}


def _sum_counters(cells: Sequence[Dict[str, int]]) -> Dict[str, int]:
    return {"live_flow_steps": sum(c["live_flow_steps"] for c in cells),
            "live_flow_peak": max((c["live_flow_peak"] for c in cells),
                                  default=0)}


def _chunk_by_flows(built, idxs, max_pad_frac: float):
    """Split a group's cells into chunks whose flow counts are within
    ``max_pad_frac`` of the chunk max. Padding a 30%-load cell to an
    80%-load cell's flow table makes the vmapped scan *compute* the
    padding (inert, but not free) — on compute-dominated grids that
    waste exceeds the saved traces, so bounded-waste chunks beat one
    maximal batch. Cells with near-equal F (seed/policy/workload axes)
    still share one trace."""
    order = sorted(range(len(built)), key=lambda j: -built[j][1].f_arr_us.shape[0])
    chunks, cur, cur_fmax = [], [], None
    for j in order:
        f = built[j][1].f_arr_us.shape[0]
        if cur and f < (1.0 - max_pad_frac) * cur_fmax:
            chunks.append(cur)
            cur, cur_fmax = [], None
        if not cur:
            cur_fmax = f
        cur.append(j)
    if cur:
        chunks.append(cur)
    return [([built[j] for j in chunk], [idxs[j] for j in chunk])
            for chunk in chunks]


def run_sweep(specs: Sequence[ExpSpec], sequential: bool = False,
              use_mesh: bool = False, devices: Optional[int] = None,
              max_pad_frac: float = 0.35,
              batch_mode: str = "auto") -> SweepReport:
    """Run a grid of experiment cells, batching compatible cells.

    Args:
      specs: the grid, any mix of scenarios/loads/policies/seeds/...
      sequential: run the classic one-cell-at-a-time loop instead (the
        before/after baseline for the batched engine; also what the
        equivalence test compares against).
      use_mesh: additionally shard the cell axis across host devices via
        ``shard_map`` when more than one device is visible. With a single
        device this is a no-op.
      devices: cap on the mesh size (default: all visible devices).
      max_pad_frac: flow-count padding budget per batch — cells whose
        flow tables are more than this fraction smaller than the largest
        cell in a batch go to their own chunk (see ``_chunk_by_flows``).
      batch_mode: "vmap" (cells as a leading batch axis), "map" (a
        compiled lax.map loop over cells inside one trace), or "auto"
        (vmap for small dispatch-bound cells, map past the
        ``_VMAP_MAX_FLOWS`` crossover). All modes share one trace per
        chunk and produce bit-identical results.
    """
    if not sequential and RECENT:
        # only the newest call keeps its programs: free the last call's
        # before this one's clock starts (tearing a compiled program
        # down is host work that no stage of this call owns)
        RECENT[-1] = dataclasses.replace(RECENT[-1], programs=())
    t0 = time.perf_counter()
    regrows = enginemod.REGROWS["regrows"]
    if sequential:
        results, flow_slots, window_slots = [], 0, 0
        for spec in specs:
            stats, util, (_, table, flows, cfg, final) = run_experiment(spec)
            view = SimpleNamespace(
                done=np.asarray(final.done),
                fct_us=np.asarray(final.fct_us),
                flow_path=np.asarray(final.flow_path),
                serv_bytes=np.asarray(final.serv_bytes),
                c_path=np.asarray(final.c_path))
            fg, bg = metrics.fg_bg_stats(view, table, flows, cfg,
                                         overall=stats)
            results.append(CellResult(spec=spec, stats=stats, util=util,
                                      final=view, flows=flows,
                                      stats_fg=fg, stats_bg=bg,
                                      counters=_cell_counters(final)))
            flow_slots += flows.num_flows * cfg.num_steps
            window_slots += final.slot_flow.shape[0] * cfg.num_steps
        counters = _sum_counters([r.counters for r in results])
        counters.update(
            flow_slots=flow_slots, window_slots=window_slots,
            window_regrows=enginemod.REGROWS["regrows"] - regrows)
        return SweepReport(results, len(results), len(results),
                           time.perf_counter() - t0, [1] * len(results),
                           counters=counters)

    stages: Dict[str, float] = {}
    counters = {"flow_slots": 0, "window_slots": 0, "arrivals": 0,
                "arrival_slots": 0, "lowerings": 0}
    programs = []
    # ---- group by static key, preserving input order within groups
    with span("sweep.group", stages):
        groups: dict = {}
        for i, spec in enumerate(specs):
            groups.setdefault(static_key(spec), []).append(i)

        ndev = 1
        if use_mesh:
            ndev = min(devices or len(jax.devices()), len(jax.devices()))

    results: List[Optional[CellResult]] = [None] * len(specs)
    group_cells: List[int] = []
    for (topology, cfg), idxs in groups.items():
        with span("sweep.world", stages):
            scen, table = build_world(topology)
        eng = enginemod.get_engine(cfg.engine)
        # narrow the dynamic dispatch to the policies actually present
        present = {specs[i].policy for i in idxs}
        cfg = dataclasses.replace(cfg, sweep_policies=tuple(
            p for p in fluid.POLICIES if p in present))
        built = []
        with span("sweep.flows", stages):
            for i in idxs:
                spec = specs[i]
                flows = make_flows(spec, scen, table)
                # build with the concrete policy so policy_code is baked;
                # the batched run itself uses the "sweep" meta-policy cfg
                cell_cfg = dataclasses.replace(cfg, policy=spec.policy)
                arrs, st = eng.build(table, flows, cell_cfg)
                built.append((flows, arrs, st))

        for chunk, chunk_idxs in _chunk_by_flows(built, idxs, max_pad_frac):
            with span("sweep.pad", stages):
                group_cells.append(len(chunk))
                Fmax = max(a.f_arr_us.shape[0] for _, a, _ in chunk)
                Amax = max(a.arrivals.shape[1] for _, a, _ in chunk)
                key = enginemod.window_key(cfg, Fmax, Amax)
                padded = [(_pad_cell(a, Fmax, Amax), s) for _, a, s in chunk]

                mesh = None
                ncells = len(padded)
                if ndev > 1:
                    # pad the cell axis to a multiple of the mesh so
                    # shard_map gets equal shards; clones are dropped after
                    mesh = make_host_mesh(data=ndev)
                    while len(padded) % ndev:
                        padded.append(padded[0])
                cells = _stack([c for c, _ in padded])

                # blank the per-cell fields before closure capture: one()
                # replaces them per cell, so leaving them would only bake
                # chunk[0]'s (T,A) arrivals + flow tables into the
                # compiled program as dead constants
                shared = dataclasses.replace(
                    chunk[0][1], arrivals=None, f_arr_us=None, f_size=None,
                    f_pair=None, f_id=None, policy_code=None)
                mode = batch_mode
                if mode == "auto":
                    mode = "vmap" if Fmax <= _VMAP_MAX_FLOWS else "map"

            def run_group(width):
                with span("sweep.pad", stages):
                    states = _stack([_pad_state(s, Fmax, width)
                                     for _, s in padded])
                with span("sweep.lower", stages):
                    prog = _compile(_group_runner(shared, cfg, mesh, mode),
                                    cells, states)
                    counters["lowerings"] += 1
                    if isinstance(prog, jax.stages.Compiled):
                        programs.append(prog)
                with span("sweep.run", stages):
                    final = jax.block_until_ready(prog(cells, states))
                with span("sweep.fetch", stages):
                    final = jax.tree_util.tree_map(np.asarray, final)
                    del states, prog    # freed here, not between spans
                return final

            # a window some cell's flows in flight outgrew runs again wider
            final, width = enginemod.run_regrowing(
                run_group, key, enginemod.fitted_window(key))

            with span("sweep.score", stages):
                for j, i in enumerate(chunk_idxs[:ncells]):
                    spec, (flows, _, _) = specs[i], chunk[j]
                    F = flows.num_flows
                    view = SimpleNamespace(done=final.done[j, :F],
                                           fct_us=final.fct_us[j, :F],
                                           flow_path=final.flow_path[j, :F],
                                           serv_bytes=final.serv_bytes[j],
                                           c_path=final.c_path[j])
                    stats = metrics.fct_stats(view, table, flows, cfg)
                    util = metrics.link_utilization(view, shared, cfg)
                    fg, bg = metrics.fg_bg_stats(view, table, flows, cfg,
                                                 overall=stats)
                    results[i] = CellResult(
                        spec=spec, stats=stats, util=util, final=view,
                        flows=flows, stats_fg=fg, stats_bg=bg,
                        counters=_cell_counters(final, j))
            counters["flow_slots"] += Fmax * cfg.num_steps * ncells
            counters["window_slots"] += width * cfg.num_steps * ncells
            # every flow fills one entry of its cell's (T, A) table
            counters["arrivals"] += sum(f.num_flows for f, _, _ in chunk)
            counters["arrival_slots"] += cfg.num_steps * Amax * ncells

    counters.update(_sum_counters([r.counters for r in results]))
    counters["window_regrows"] = enginemod.REGROWS["regrows"] - regrows
    report = SweepReport(
        results, len(specs), len(group_cells), time.perf_counter() - t0,
        group_cells,
        build_s=stages.get("world", 0.0) + stages.get("flows", 0.0)
        + stages.get("pad", 0.0),
        stages=stages, counters=counters)
    RECENT.append(CallRecord(t0, time.perf_counter(), dict(stages),
                             dict(counters), tuple(programs)))
    return report
