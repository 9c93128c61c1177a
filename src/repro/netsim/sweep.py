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
2. pads each group's per-cell arrays (flow tables to the max flow count,
   arrival buckets to the max per-step batch — both padding-invariant by
   construction, see ``fluid._route_arrivals``'s out-of-bounds-drop
   scatter) and stacks them along a leading cell axis;
3. runs the whole group as ONE jitted invocation — one trace, one
   compile, one device dispatch — either ``jax.vmap`` over the cell axis
   (dispatch-bound small cells) or a compiled ``jax.lax.map`` loop over
   cells (compute-bound large cells, where vmap's batched-scatter
   lowering costs ~30% on CPU), and optionally ``jax.shard_map``s the
   cell axis across the host mesh (``repro.launch.mesh.make_host_mesh``)
   when multiple devices exist.

Per-cell results are bit-for-bit identical to the sequential loop (the
tier-1 suite asserts exact FCT equality): vmap batches the same IEEE ops,
padded flows never activate, and padded arrival slots scatter out of
bounds and drop.
"""
from __future__ import annotations

import dataclasses
import time
from types import SimpleNamespace
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_host_mesh
from repro.netsim import engine as enginemod
from repro.netsim import fluid, metrics, sanitize
from repro.netsim.engine import SimArrays, SimState
from repro.netsim.experiment import (ExpSpec, build_world, make_flows,
                                     run_experiment, spec_to_cfg)


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


@dataclasses.dataclass
class SweepReport:
    results: List[CellResult]  # in the order of the input specs
    num_cells: int
    num_groups: int
    wall_s: float
    group_cells: List[int]     # cells per compiled group
    # batched path only: host seconds building worlds, flow tables and
    # padded cell stacks, and seconds in the jitted group calls (trace,
    # compile and run, each call ended by block_until_ready)
    build_s: Optional[float] = None
    device_s: Optional[float] = None

    def __iter__(self):
        return iter(self.results)


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


def _pad_cell(arrs: SimArrays, state: SimState, F: int, A: int):
    """Pad one built cell to the group's (F, A). Padded flows never appear
    in ``arrivals`` (pad = -1), never activate, and contribute exact 0.0
    to every link sum, so results are unchanged. Which fields carry a
    leading flow axis (and their inert pad values) is the engine core's
    contract (``engine.FLOW_FIELDS`` — the packet engine's extra state is
    covered there too, and the state's own dataclass type is rebuilt)."""
    T = arrs.arrivals.shape[0]
    arrivals = np.full((T, A), -1, np.int32)
    arrivals[:, : arrs.arrivals.shape[1]] = np.asarray(arrs.arrivals)
    cell = CellArrays(
        arrivals=jnp.asarray(arrivals),
        f_arr_us=jnp.asarray(_pad_tail(np.asarray(arrs.f_arr_us), F, 0.0)),
        f_size=jnp.asarray(_pad_tail(np.asarray(arrs.f_size), F, 0.0)),
        f_pair=jnp.asarray(_pad_tail(np.asarray(arrs.f_pair), F, 0)),
        f_id=jnp.asarray(_pad_tail(np.asarray(arrs.f_id), F, 0)),
        policy_code=arrs.policy_code,
    )
    st = {}
    for f in dataclasses.fields(type(state)):
        v = getattr(state, f.name)
        if f.name in enginemod.FLOW_FIELDS:
            st[f.name] = jnp.asarray(_pad_tail(
                np.asarray(v), F, enginemod.STATE_PAD.get(f.name, 0)))
        else:
            st[f.name] = v            # per-link / per-pair: shared shape
    return cell, type(state)(**st)


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
    t0 = time.perf_counter()
    if sequential:
        results = []
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
                                      stats_fg=fg, stats_bg=bg))
        return SweepReport(results, len(results), len(results),
                           time.perf_counter() - t0, [1] * len(results))

    # ---- group by static key, preserving input order within groups
    groups: dict = {}
    for i, spec in enumerate(specs):
        groups.setdefault(static_key(spec), []).append(i)

    ndev = 1
    if use_mesh:
        ndev = min(devices or len(jax.devices()), len(jax.devices()))

    results: List[Optional[CellResult]] = [None] * len(specs)
    group_cells: List[int] = []
    build_s = device_s = 0.0
    for (topology, cfg), idxs in groups.items():
        t_build = time.perf_counter()
        scen, table = build_world(topology)
        eng = enginemod.get_engine(cfg.engine)
        # narrow the dynamic dispatch to the policies actually present
        present = {specs[i].policy for i in idxs}
        cfg = dataclasses.replace(cfg, sweep_policies=tuple(
            p for p in fluid.POLICIES if p in present))
        built = []
        for i in idxs:
            spec = specs[i]
            flows = make_flows(spec, scen, table)
            # build with the concrete policy so policy_code is baked; the
            # batched run itself uses the "sweep" meta-policy cfg
            cell_cfg = dataclasses.replace(cfg, policy=spec.policy)
            arrs, st = eng.build(table, flows, cell_cfg)
            built.append((flows, arrs, st))

        build_s += time.perf_counter() - t_build

        for chunk, chunk_idxs in _chunk_by_flows(built, idxs, max_pad_frac):
            t_build = time.perf_counter()
            group_cells.append(len(chunk))
            Fmax = max(a.f_arr_us.shape[0] for _, a, _ in chunk)
            Amax = max(a.arrivals.shape[1] for _, a, _ in chunk)
            padded = [_pad_cell(a, s, Fmax, Amax) for _, a, s in chunk]

            mesh = None
            ncells = len(padded)
            if ndev > 1:
                # pad the cell axis to a multiple of the mesh so
                # shard_map gets equal shards; clones are dropped after
                mesh = make_host_mesh(data=ndev)
                while len(padded) % ndev:
                    padded.append(padded[0])
            cells = _stack([c for c, _ in padded])
            states = _stack([s for _, s in padded])

            # blank the per-cell fields before closure capture: one()
            # replaces them per cell, so leaving them would only bake
            # chunk[0]'s (T,A) arrivals + flow tables into the compiled
            # program as dead constants
            shared = dataclasses.replace(
                chunk[0][1], arrivals=None, f_arr_us=None, f_size=None,
                f_pair=None, f_id=None, policy_code=None)
            mode = batch_mode
            if mode == "auto":
                mode = "vmap" if Fmax <= _VMAP_MAX_FLOWS else "map"
            build_s += time.perf_counter() - t_build
            t_dev = time.perf_counter()
            final = jax.block_until_ready(
                _group_runner(shared, cfg, mesh, mode)(cells, states))
            device_s += time.perf_counter() - t_dev
            final = jax.tree_util.tree_map(np.asarray, final)

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
                results[i] = CellResult(spec=spec, stats=stats, util=util,
                                        final=view, flows=flows,
                                        stats_fg=fg, stats_bg=bg)

    return SweepReport(results, len(specs), len(group_cells),
                       time.perf_counter() - t0, group_cells,
                       build_s=build_s, device_s=device_s)
