"""Shared multi-engine simulation core (the surface both the fluid-rate
and the packet-level engines consume).

The repo ships two simulation backends behind one interface:

- ``repro.netsim.fluid``  — flow-level fluid-rate approximation (fast;
  max-min link sharing, analytic queue integration);
- ``repro.netsim.packet`` — slotted packet-level engine (the NS-3
  analogue of paper §6: per-hop FIFO byte/packet queues, ECN marking
  thresholds, PFC pause/resume with backward propagation delay, windowed
  sources).

Both engines are one jitted ``lax.scan`` over ``SimState`` and share,
*by construction* (same functions, not parallel implementations):

- ``SimConfig`` / ``SimArrays`` / ``SimState`` — the experiment config,
  static device arrays, and the dynamic pytree (the packet engine
  subclasses ``SimState`` with its extra per-hop queue state);
- the **active-flow window**: a step works on W fixed slots, not on all
  F flows of the run. A flow takes a slot at its arrival step
  (``_route_arrivals``) and gives it back the step it completes or
  loses every path (``retire``/``free_slots``), writing its answers
  (``done``/``fct_us``/``flow_path``, (F,)) as it goes. W follows the
  arrivals table (``window_size``); a run whose flows in flight outgrow
  it runs again on twice the slots (``run_windowed``), and answers do
  not depend on W;
- ``build()`` — tables, arrival bucketing, failure/degradation schedule
  folding, signal-delay precomputation, HIST validation;
- the **signal plane**: the ``core.cong`` register pipeline recorded per
  step in the ``hist_c`` ring (``monitor_tick``), read back with
  backward propagation delay (``path_cong_view``);
- the **control plane**: periodic ``C_path`` re-install from effective
  capacities (``ctrl_refresh`` / ``ctrl_tick``);
- **routing**: arrival-time decisions through ``select.select_egress``
  and the baselines, flow stickiness, and lazy failover
  (``_route_arrivals`` / ``_reroute_dead``);
- the **CC rate laws** (``_cc_update``): DCQCN/DCTCP/TIMELY/HPCC-like,
  reacting to RTT-delayed signals from the ``hist_q``/``hist_u`` rings —
  the fluid engine uses the rate directly, the packet engine paces
  packet injection with it and bounds in-flight bytes by the rate-BDP
  window.

An *engine* is any module satisfying the ``Engine`` protocol below
(``name`` / ``build`` / ``run_impl`` / ``run``); ``get_engine`` resolves
the ``SimConfig.engine`` / ``ExpSpec.engine`` string. Final states feed
``metrics.fct_stats`` unchanged — every scenario, sweep axis, and figure
grid runs on either backend.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as bl
from repro.core import cong as congmod
from repro.core import select as selmod
from repro.core.cong import CongParams, CongState
from repro.core.pathq import (PathQParams, calc_path_quality,
                              path_bottleneck_stats)
from repro.core.select import SelectParams
from repro.core.tables import CELL_BYTES, bootstrap_tables
from repro.netsim.paths import PathTable
from repro.traffic.gen import FlowSet

HIST = 8192          # history rings (steps); must exceed the max RTT and
                     # signal-delay offsets — build() validates this

# Every history-ring scatter index is `t % HIST`, in-bounds by
# construction, so the write sites state that instead of inheriting the
# default FILL_OR_DROP (which would silently drop an out-of-bounds write
# if the wrap ever regressed). Tests flip this to None to pin that both
# modes are bit-identical for in-bounds indices.
RING_SCATTER_MODE = "promise_in_bounds"

# Policy name -> dense code. "sweep" is a meta-policy: the step function
# dispatches on the per-experiment ``SimArrays.policy_code`` scalar instead
# of a Python branch, so a vmapped batch can mix policies in one trace
# (the sweep engine's whole-grid-single-XLA-computation mode).
#
# The mapping is FROZEN — codes leak into trace keys, CSV rows and
# ``SimArrays.policy_code``, so new policies may only append fresh codes,
# never renumber existing ones (pinned by tests/test_redecision.py).
POLICY_CODES = {
    "lcmp": 0,       # paper §3-§5: cost + congestion two-stage select
    "lcmp_w": 1,     # beyond-paper: capacity-weighted stage-2 hash
    "ecmp": 2,
    "ucmp": 3,
    "wcmp": 4,
    "redte": 5,
    "fatpaths": 6,   # layered min-stretch routing + flowlet re-hash
    "amp": 7,        # multi-subflow transport (per-subflow ECMP hash)
    "lcmp_r": 8,     # ablation: LCMP with periodic mid-flow re-decision
    "matchrdma": 9,  # segmented per-span rate matching on OTN hauls
}
POLICIES = tuple(POLICY_CODES)
# policies whose law re-decides mid-flow when the engine's eligibility
# trigger fires (flowlet idle gap / re-decision epoch)
REDECIDE_POLICIES = ("fatpaths", "lcmp_r")
ENGINES = ("fluid", "packet")
_NEVER = (1 << 30)   # sentinel step for "this link never fails/degrades"


def policy_code(policy: str) -> int:
    if policy not in POLICY_CODES:
        raise ValueError(f"unknown policy {policy!r}; valid: {POLICIES}")
    return POLICY_CODES[policy]


@runtime_checkable
class Engine(Protocol):
    """What a simulation backend must provide (modules satisfy this)."""
    name: str

    def build(self, table: PathTable, flows: FlowSet, cfg: "SimConfig"):
        """Pack tables + flows -> (SimArrays, SimState-like pytree)."""

    def run_impl(self, arrs: "SimArrays", state, cfg: "SimConfig"):
        """Unjitted scan body (the sweep engine vmaps this)."""

    def run(self, arrs: "SimArrays", state, cfg: "SimConfig"):
        """Jitted single-experiment entry point -> final state."""


def get_engine(name: str) -> Engine:
    """Resolve an engine string (``SimConfig.engine``) to its module."""
    if name == "fluid":
        from repro.netsim import fluid
        return fluid
    if name == "packet":
        from repro.netsim import packet
        return packet
    raise ValueError(f"unknown engine {name!r}; valid: {ENGINES}")


@dataclasses.dataclass(frozen=True)
class SimConfig:
    engine: str = "fluid"         # fluid|packet (see get_engine)
    policy: str = "lcmp"          # lcmp|ecmp|ucmp|wcmp|redte|sweep
    cc: str = "dcqcn"             # dcqcn|dctcp|timely|hpcc
    dt_us: int = 200
    horizon_us: int = 2_000_000
    cap_scale: float = 0.125      # uniform capacity scale (sim speed knob)
    buffer_bytes: float = 6e9     # long-haul switch buffer (paper §6.2)
    ecn_kmin_bytes: float = 4e5   # ECN mark threshold Kmin (scaled caps)
    ecn_kmax_factor: float = 10.0  # Kmax = factor * Kmin (RED ramp top)
    ai_frac: float = 0.002        # additive increase per step, frac of line
    md_factor: float = 0.7        # multiplicative decrease
    # MD reaction timer (us): real DCQCN/TIMELY decrease on a NIC timer,
    # not once per RTT — on a 250 ms long-haul path a per-RTT gate would
    # leave flows effectively uncontrolled. Feedback *delay* stays RTT.
    cc_dec_period_us: int = 1_600
    redte_period_us: int = 100_000
    # routing-signal staleness: each hop's C_cong reaches the ingress
    # after scale x its one-way propagation distance back (1.0 = physics;
    # 0.0 = oracle visibility; >1 models slower telemetry channels)
    sig_delay_scale: float = 1.0
    # control-plane C_path re-install period (paper §7.3); 0 = never
    # refresh (the build-time static table)
    ctrl_period_us: int = 100_000
    # ---- packet-engine knobs (ignored by the fluid engine) ----
    mtu_bytes: int = 1024         # packet size; == CELL_BYTES so queue
                                  # depth in packets == monitor cells
    # PFC pause/resume hysteresis as fractions of the (scaled) buffer:
    # XOFF fires above, XON releases below. The pause frame reaches the
    # upstream transmitter one backward link propagation late, so queues
    # overshoot XOFF by up to rate x delay — the long-haul headroom
    # problem the paper's 6 GB buffers exist for.
    pfc_xoff_frac: float = 0.7
    pfc_xon_frac: float = 0.5
    select: SelectParams = SelectParams()
    pathq: PathQParams = PathQParams()
    congp: CongParams = CongParams()
    # optional single-link failure injection (legacy single-event form;
    # folded into the schedule arrays at build time)
    fail_link: int = -1
    fail_at_us: int = -1
    # scenario schedules (hashable static tuples, see netsim.scenarios):
    # fail_sched    = ((link_idx, at_us), ...)          hard link trips
    # degrade_sched = ((link_idx, at_us, factor), ...)  silent capacity loss
    fail_sched: tuple = ()
    degrade_sched: tuple = ()
    # policy=="sweep" only: the policies the dynamic dispatch must cover.
    # The sweep engine narrows this to the ones actually present in a
    # batch so un-swept policies cost nothing per step.
    sweep_policies: tuple = POLICIES
    # ---- mid-flow re-decision plane (REDECIDE_POLICIES only) ----
    # Eligibility is engine-specific: the packet engine re-hashes a flow
    # whose queues drained for >= flowlet_gap_us (FatPaths flowlet
    # switching — observable idle gaps exist only where packets do); the
    # fluid engine re-decides on a redecide_period_us timer epoch. 0
    # disables the plane for that engine and keeps the step bit-identical
    # to pinned-path routing (asserted in tests).
    flowlet_gap_us: int = 0
    redecide_period_us: int = 0
    # amp only: subflows per flow (traffic/gen.py splits sizes; metrics
    # scores the parent flow at last-subflow completion)
    n_subflows: int = 1
    # debug mode: thread the checkify physics-invariant sanitizer
    # (repro.netsim.sanitize) through the scan. Static, so the unchecked
    # program is bit-for-bit untouched when False (asserted in tests).
    checks: bool = False

    @property
    def num_steps(self) -> int:
        return self.horizon_us // self.dt_us

    @property
    def has_failures(self) -> bool:
        return self.fail_link >= 0 or len(self.fail_sched) > 0

    @property
    def has_degrade(self) -> bool:
        return len(self.degrade_sched) > 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    # per flow slot: the step works on a window of W fixed slots, each
    # free or holding one flow in flight (admitted at its arrival step,
    # freed the step it completes); W is sized from the arrivals table
    # (``window_size``), not from the run's flow count F
    slot_flow: jnp.ndarray     # (W,) i32 flow index, -1 while free
    slot_path: jnp.ndarray     # (W,) i32 the flow's current path
    remaining: jnp.ndarray     # (W,) f32 bytes
    rate: jnp.ndarray          # (W,) f32 bytes/us
    active: jnp.ndarray        # (W,) bool
    extra_wait: jnp.ndarray    # (W,) f32 queue-wait component
    rtt_steps: jnp.ndarray     # (W,) i32
    route_step: jnp.ndarray    # (W,) i32 step the flow was (re)routed at
    route_nonce: jnp.ndarray   # (W,) i32 re-decision counter (salts the
                               # flow's hash key per flowlet/epoch)
    last_dec: jnp.ndarray      # (W,) i32 step of last MD
    cc_alpha: jnp.ndarray      # (W,) f32 (DCTCP EWMA)
    cc_target: jnp.ndarray     # (W,) f32 (DCQCN target rate / fast recovery)
    prev_delay: jnp.ndarray    # (W,) f32 (TIMELY gradient)
    # per flow: the answers, written as slots are routed and freed
    flow_path: jnp.ndarray     # (F,) i32 path (latest re-route), -1 until routed
    done: jnp.ndarray          # (F,) bool
    fct_us: jnp.ndarray        # (F,) f32
    # per link
    q_bytes: jnp.ndarray       # (L,) f32
    hist_q: jnp.ndarray        # (L, HIST) f32 queue bytes
    hist_u: jnp.ndarray        # (L, HIST) f32 utilization
    hist_c: jnp.ndarray        # (L, HIST) i32 quantized C_cong per step
    u_ewma: jnp.ndarray        # (L,) f32
    link_alive: jnp.ndarray    # (L,) bool
    serv_bytes: jnp.ndarray    # (L,) f32 served-byte counter (metrics)
    cong: CongState            # LCMP per-link registers
    c_cong: jnp.ndarray        # (L,) i32 current LCMP congestion score
    # control-plane installed path scores — *state*, periodically
    # re-installed from effective capacities (see ``ctrl_refresh``)
    c_path: jnp.ndarray        # (NP,) i32
    redte_w: jnp.ndarray       # (NPAIR, K) i32 split weights
    # work counters, not part of the answers (``count_live``): the flows
    # each step moves (active once its arrivals are routed), summed over
    # steps and at most. int32 is exact while F x steps < 2**31 (the
    # benchmark's cells reach 4.2e7)
    live_flow_steps: jnp.ndarray  # () i32
    live_flow_peak: jnp.ndarray   # () i32
    # () bool, sticky: some step had more routed arrivals than free
    # slots. The run's answers are then incomplete, and ``run_regrowing``
    # runs it again on a window twice as wide
    window_overflow: jnp.ndarray


# SimState fields with a leading slot axis (W) — every slot starts free
# and a freed slot is reset to these fields' pad values. Packet-engine
# extras are appended here so one list covers both state types; fields
# absent from a given state dataclass are simply never looked up.
SLOT_FIELDS = ("slot_flow", "slot_path", "remaining", "rate", "active",
               "extra_wait", "rtt_steps", "route_step", "route_nonce",
               "last_dec", "cc_alpha", "cc_target", "prev_delay",
               # packet engine (see packet.PacketState)
               "fq", "credit", "delivered", "last_tx")
# SimState fields with a leading flow axis (F): the answers. The sweep
# engine pads exactly these when batching cells (the rest is per-slot,
# per-link or per-pair, and shape-shared across a group)
FLOW_FIELDS = ("flow_path", "done", "fct_us")
# slot/flow field -> inert pad value (a free slot; an unrouted flow)
STATE_PAD = {"slot_flow": -1, "slot_path": -1, "flow_path": -1,
             "route_step": 1 << 20, "last_dec": -(1 << 20), "rtt_steps": 1,
             "last_tx": 1 << 20}


def window_size(num_flows: int, arrival_width: int) -> int:
    """Slots of a run's window: ``min(F, max(256, next_pow2(8 x A)))``
    for F flows and an arrivals table A wide (8 steps' worth of the
    widest step's arrivals). A window as wide as the flow table always
    fits."""
    want = max(256, 8 * arrival_width)
    return max(1, min(num_flows, 1 << (want - 1).bit_length()))


# the widest window a run needed, by ``window_key``: later runs of the
# same key start there instead of regrowing again
_WINDOW_FIT: Dict[tuple, int] = {}
# windows regrown (``run_regrowing``) since import
REGROWS = collections.Counter()


def window_key(cfg: "SimConfig", num_flows: int, arrival_width: int) -> tuple:
    """What a fitted window is remembered under: the traced program's
    cfg and the flow-table shapes."""
    return (cfg, int(num_flows), int(arrival_width))


def fitted_window(key: tuple) -> int:
    """``window_size`` for ``key``'s shapes, or the wider window an
    earlier run of ``key`` regrew to."""
    _, num_flows, arrival_width = key
    return max(window_size(num_flows, arrival_width),
               min(_WINDOW_FIT.get(key, 0), num_flows))


def with_window(state, width: int):
    """``state`` with its slot fields cut or padded (free slots) to
    ``width`` slots."""
    if state.slot_flow.shape[0] == width:
        return state
    out = {}
    for f in dataclasses.fields(type(state)):
        v = getattr(state, f.name)
        if f.name in SLOT_FIELDS:
            v = np.asarray(v)[:width]
            pad = np.full((width - v.shape[0],) + v.shape[1:],
                          STATE_PAD.get(f.name, 0), v.dtype)
            v = jnp.asarray(np.concatenate([v, pad]))
        out[f.name] = v
    return type(state)(**out)


def run_regrowing(run, key: tuple, width: int):
    """``run(width)`` -> final state (or a stack of them), run again on
    twice the slots while some run's window overflowed. A window as wide
    as the flow table always fits; the width that fit is remembered under
    ``key``. Returns the final state and its width."""
    num_flows = key[1]
    while True:
        final = run(width)
        if width >= num_flows or not np.asarray(final.window_overflow).any():
            return final, width
        width = min(2 * width, num_flows)
        _WINDOW_FIT[key] = max(_WINDOW_FIT.get(key, 0), width)
        REGROWS["regrows"] += 1


def run_windowed(run_fn, arrs: "SimArrays", state, cfg: "SimConfig"):
    """``run_fn(arrs, state)`` on a window wide enough for every flow
    (``run_regrowing``). Returns the final state."""
    key = window_key(cfg, state.flow_path.shape[0], arrs.arrivals.shape[1])
    width = max(state.slot_flow.shape[0], fitted_window(key))
    return run_regrowing(lambda w: run_fn(arrs, with_window(state, w)),
                         key, width)[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimArrays:
    """Static (non-scanned) device arrays."""
    link_cap: jnp.ndarray      # (L,) f32 bytes/us (scaled)
    link_cap_gbps: jnp.ndarray # (L,) i32 (unscaled, for tables)
    path_links: jnp.ndarray    # (NP, H) i32
    path_prop: jnp.ndarray     # (NP,) i32 us
    path_cap: jnp.ndarray      # (NP,) f32 bytes/us (scaled bottleneck)
    path_cap_gbps: jnp.ndarray # (NP,) i32
    path_first: jnp.ndarray    # (NP,) i32
    pair_cand: jnp.ndarray     # (NPAIR, K) i32
    arrivals: jnp.ndarray      # (T, A) i32 flow idx, -1 pad
    f_arr_us: jnp.ndarray      # (F,) f32
    f_size: jnp.ndarray        # (F,) f32
    f_pair: jnp.ndarray        # (F,) i32
    f_id: jnp.ndarray          # (F,) u32
    # () i32 — read only when cfg.policy=="sweep"
    policy_code: jnp.ndarray = None
    link_fail_step: jnp.ndarray = None    # (L,) i32 trip step (_NEVER)
    link_deg_step: jnp.ndarray = None     # (L,) i32 degradation onset step
    link_deg_factor: jnp.ndarray = None   # (L,) f32 cap multiplier after onset
    path_len: jnp.ndarray = None          # (NP,) i32 valid hop count
    link_delay_us: jnp.ndarray = None     # (L,) i32 one-way propagation
    # (NP, H) i32 — steps each hop's congestion signal takes to propagate
    # back to the ingress (cumulative upstream one-way delay, scaled by
    # cfg.sig_delay_scale); hop 0 is the ingress's own egress port (0)
    path_sig_delay: jnp.ndarray = None
    tables: object = None      # SwitchTables


def build(table: PathTable, flows: FlowSet, cfg: SimConfig):
    """Pack numpy tables + flows into device arrays and init state: an
    empty window of ``fitted_window`` slots and unanswered flows.

    Engine-agnostic: returns the base ``SimState``; the packet engine
    wraps it with its extra per-hop queue fields (``packet.build``).
    """
    # links
    from repro.netsim.topo import Topology  # noqa: F401 (doc only)
    link_cap_gbps = _infer_link_caps(table)
    L = len(link_cap_gbps)
    link_cap = jnp.asarray(link_cap_gbps * 125.0 * cfg.cap_scale, jnp.float32)

    # the whole simulated world is capacity-scaled, so the switch tables
    # (trend normalization = cells per interval at line rate) and buffers
    # scale identically — timescales are then invariant under cap_scale.
    tb = bootstrap_tables([max(int(c * cfg.cap_scale), 1) for c in link_cap_gbps],
                          buffer_bytes=max(int(cfg.buffer_bytes * cfg.cap_scale),
                                           1 << 20),
                          sample_interval_us=cfg.dt_us)
    c_path = calc_path_quality(jnp.asarray(table.path_prop_us),
                               jnp.asarray(table.path_cap),
                               tb.cap_thresh, cfg.pathq)

    # per-path per-hop signal-propagation offsets: hop h's congestion
    # score travels back over hops 0..h-1, so the ingress sees it
    # sum(delay[0..h-1]) late (x sig_delay_scale)
    link_delay_us = _infer_link_delays(table)
    pl = np.asarray(table.path_links)
    hop_delay = np.where(pl >= 0, link_delay_us[np.maximum(pl, 0)], 0)
    upstream = np.concatenate([np.zeros((pl.shape[0], 1), np.int64),
                               np.cumsum(hop_delay, -1)[:, :-1]], axis=1)
    sig_delay_f = cfg.sig_delay_scale * upstream / cfg.dt_us
    sig_delay = sig_delay_f.astype(np.int32)

    # the history rings silently alias once a read offset wraps: a
    # "delayed" read would return recent/future data. Guard both readers
    # (on the pre-cast floats — an int32-wrapped offset must not pass).
    max_rtt = int(np.max(2 * np.asarray(table.path_prop_us) // cfg.dt_us,
                         initial=1))
    max_sig = int(sig_delay_f.max(initial=0))
    if max(max_rtt, max_sig) >= HIST:
        raise ValueError(
            f"history ring too short: HIST={HIST} steps but the worst path "
            f"needs rtt={max_rtt} and signal-delay={max_sig} steps at "
            f"dt_us={cfg.dt_us} (sig_delay_scale={cfg.sig_delay_scale}); "
            "increase dt_us or reduce sig_delay_scale")

    # arrivals bucketed by step — vectorized (at 200k flows the per-flow
    # Python loop this replaces was a real build cost). Stable argsort
    # keeps flows within a step in ascending-index order, exactly the
    # order the old loop filled slots in (bit-identical, see tests).
    T = cfg.num_steps
    step = np.minimum(flows.arrival_us // cfg.dt_us, T - 1).astype(np.int64)
    counts = np.bincount(step, minlength=T)
    A = max(int(counts.max()), 1)
    arrivals = np.full((T, A), -1, np.int32)
    order = np.argsort(step, kind="stable")
    srt = step[order]
    # slot within the step = rank among same-step flows (cumcount):
    # searchsorted on the sorted array gives each element's first index
    slot = np.arange(len(srt)) - np.searchsorted(srt, srt, side="left")
    arrivals[srt, slot] = order

    # failure / degradation schedules -> per-link step arrays (the legacy
    # single-event fields fold into the same representation)
    fail_step = np.full(L, _NEVER, np.int32)
    if cfg.fail_link >= 0:
        fail_step[cfg.fail_link] = cfg.fail_at_us // cfg.dt_us
    for li, at_us in cfg.fail_sched:
        fail_step[li] = min(int(fail_step[li]), int(at_us) // cfg.dt_us)
    deg_step = np.full(L, _NEVER, np.int32)
    deg_factor = np.ones(L, np.float32)
    for li, at_us, fac in cfg.degrade_sched:
        deg_step[li] = int(at_us) // cfg.dt_us
        deg_factor[li] = float(fac)

    arr = SimArrays(
        link_cap=link_cap,
        link_cap_gbps=jnp.asarray(link_cap_gbps, jnp.int32),
        path_links=jnp.asarray(table.path_links),
        path_prop=jnp.asarray(table.path_prop_us),
        path_cap=jnp.asarray(table.path_cap * 125.0 * cfg.cap_scale, jnp.float32),
        path_cap_gbps=jnp.asarray(table.path_cap),
        path_first=jnp.asarray(table.path_first),
        pair_cand=jnp.asarray(table.pair_cand),
        arrivals=jnp.asarray(arrivals),
        f_arr_us=jnp.asarray(flows.arrival_us, jnp.float32),
        f_size=jnp.asarray(flows.size_bytes, jnp.float32),
        f_pair=jnp.asarray(flows.pair_id),
        f_id=jnp.asarray(flows.flow_id),
        policy_code=jnp.int32(policy_code(cfg.policy)
                              if cfg.policy != "sweep" else 0),
        link_fail_step=jnp.asarray(fail_step),
        link_deg_step=jnp.asarray(deg_step),
        link_deg_factor=jnp.asarray(deg_factor),
        path_len=jnp.asarray(table.path_len),
        link_delay_us=jnp.asarray(link_delay_us, jnp.int32),
        path_sig_delay=jnp.asarray(sig_delay),
        tables=tb,
    )
    F = flows.num_flows
    W = fitted_window(window_key(cfg, F, A))
    NPAIR, K = table.pair_cand.shape
    state = SimState(
        slot_flow=jnp.full((W,), -1, jnp.int32),
        slot_path=jnp.full((W,), -1, jnp.int32),
        remaining=jnp.zeros((W,), jnp.float32),
        rate=jnp.zeros((W,), jnp.float32),
        active=jnp.zeros((W,), bool),
        extra_wait=jnp.zeros((W,), jnp.float32),
        rtt_steps=jnp.ones((W,), jnp.int32),
        route_step=jnp.full((W,), 1 << 20, jnp.int32),   # sentinel: unrouted
        route_nonce=jnp.zeros((W,), jnp.int32),
        last_dec=jnp.full((W,), -(1 << 20), jnp.int32),
        cc_alpha=jnp.zeros((W,), jnp.float32),
        cc_target=jnp.zeros((W,), jnp.float32),
        prev_delay=jnp.zeros((W,), jnp.float32),
        flow_path=jnp.full((F,), -1, jnp.int32),
        done=jnp.zeros((F,), bool),
        fct_us=jnp.zeros((F,), jnp.float32),
        q_bytes=jnp.zeros((L,), jnp.float32),
        hist_q=jnp.zeros((L, HIST), jnp.float32),
        hist_u=jnp.zeros((L, HIST), jnp.float32),
        hist_c=jnp.zeros((L, HIST), jnp.int32),
        u_ewma=jnp.zeros((L,), jnp.float32),
        link_alive=jnp.ones((L,), bool),
        serv_bytes=jnp.zeros((L,), jnp.float32),
        cong=CongState.init(L),
        c_cong=jnp.zeros((L,), jnp.int32),
        c_path=c_path,
        redte_w=jnp.ones((NPAIR, K), jnp.int32),
        live_flow_steps=jnp.int32(0),
        live_flow_peak=jnp.int32(0),
        window_overflow=jnp.asarray(False),
    )
    return arr, state


def _infer_link_caps(table: PathTable) -> np.ndarray:
    """Recover per-link capacities from path hop data (bottleneck-safe:
    every link appears in some path with its true cap recorded at build
    time via topo arrays — we stash them on the table)."""
    if hasattr(table, "_link_caps"):
        return table._link_caps  # set by attach_link_caps
    raise ValueError("call attach_link_caps(table, topo) before build()")


def _infer_link_delays(table: PathTable) -> np.ndarray:
    if hasattr(table, "_link_delays"):
        return table._link_delays  # set by attach_link_caps
    raise ValueError("call attach_link_caps(table, topo) before build()")


def attach_link_caps(table: PathTable, topo) -> PathTable:
    _, _, cap, dly = topo.arrays()
    object.__setattr__(table, "_link_caps", cap.astype(np.float32))
    object.__setattr__(table, "_link_delays", dly.astype(np.int64))
    return table


# ---------------------------------------------------------- shared step parts
def path_cong_view(hist_c: jnp.ndarray, path_links: jnp.ndarray,
                   sig_delay: jnp.ndarray, t) -> jnp.ndarray:
    """Ingress-visible congestion of candidate paths at step ``t``.

    The max over hops of each hop's *quantized* ``C_cong`` (the
    ``core.cong`` register-pipeline output recorded in the ``hist_c``
    ring), read ``sig_delay`` steps late — the one-way propagation
    distance the signal travels back to the ingress. A remote hop's
    congestion can never be seen earlier than physics delivers it.

    ``path_links``/``sig_delay``: (..., H) hop link indices (-1 pad) and
    per-hop delay offsets; returns (...,) int32 scores.
    """
    lidx = jnp.maximum(path_links, 0)
    slot = jnp.asarray((t - sig_delay) % HIST, jnp.int32)
    v = hist_c.reshape(-1)[lidx * HIST + slot]
    return jnp.where(path_links >= 0, v, 0).max(-1)


def ctrl_refresh(t, st: SimState, ar: SimArrays, cfg: SimConfig) -> jnp.ndarray:
    """One control-plane tick (paper §3.2 install, §7.3 update period):
    recompute the C_path table from *effective* per-link capacities — the
    degrade schedule and link liveness applied — via the shared
    ``core.pathq`` helpers. Propagation delays are physical and static;
    only the capacity term can change at runtime."""
    eff = ar.link_cap_gbps * jnp.where(t >= ar.link_deg_step,
                                       ar.link_deg_factor, 1.0)
    eff = jnp.where(st.link_alive, eff, 0.0).astype(jnp.int32)
    _, cap_eff = path_bottleneck_stats(ar.link_delay_us, eff,
                                       ar.path_links, ar.path_len)
    return calc_path_quality(ar.path_prop, cap_eff,
                             ar.tables.cap_thresh, cfg.pathq)


def monitor_tick(t, st, ar: SimArrays, cfg: SimConfig):
    """Switch monitor pass (every dt — the paper's "modest cadence"):
    run the ``core.cong`` register pipeline on current queue depths and
    land the quantized score in the ``hist_c`` ring at slot ``t``, where
    ingress decisions read it back hop-by-hop with propagation delay.
    Identical for both engines — only the queue dynamics feeding
    ``st.q_bytes`` differ."""
    qcells = (st.q_bytes / CELL_BYTES).astype(jnp.int32)
    cong = congmod.monitor_update(st.cong, qcells, t * cfg.dt_us,
                                  ar.tables, cfg.congp)
    c_cong = congmod.calc_cong_cost(cong, ar.tables, cfg.congp)
    with jax.named_scope("step.rings"):
        hist_c = st.hist_c.at[:, jnp.asarray(t % HIST, jnp.int32)].set(
            c_cong, mode=RING_SCATTER_MODE)
    return dataclasses.replace(st, cong=cong, c_cong=c_cong, hist_c=hist_c)


def ctrl_tick(t, st, ar: SimArrays, cfg: SimConfig):
    """Periodic control-plane C_path re-install (``ctrl_refresh`` every
    ``ctrl_period_us``). Skipped entirely when no schedule can change the
    effective capacities (the refresh would be a no-op) or when the
    period is 0 (frozen build-time table)."""
    if cfg.ctrl_period_us > 0 and (cfg.has_failures or cfg.has_degrade):
        period = max(cfg.ctrl_period_us // cfg.dt_us, 1)
        st = dataclasses.replace(
            st, c_path=jnp.where((t % period) == 0,
                                 ctrl_refresh(t, st, ar, cfg), st.c_path))
    return st


def live_flows(st) -> jnp.ndarray:
    """The flows a step moves: those active once its arrivals are routed
    (a flow that arrives and completes in one step counts). Padded flows
    never take a slot, so neither a sweep's padding nor the window's
    width adds anything."""
    return st.active.sum(dtype=jnp.int32)


def count_live(st, n):
    """End-of-step work counters: add ``n`` (``live_flows``) to
    ``live_flow_steps`` and raise ``live_flow_peak`` to it."""
    return dataclasses.replace(
        st, live_flow_steps=st.live_flow_steps + n,
        live_flow_peak=jnp.maximum(st.live_flow_peak, n))


def redte_tick(t, st, ar: SimArrays, cfg: SimConfig):
    """RedTE periodic split-ratio re-optimization (100 ms loop). In sweep
    mode the weights are maintained unconditionally (cheap (NPAIR,K)
    integer ops) — only redte-coded cells ever read them."""
    if cfg.policy == "redte" or (cfg.policy == "sweep"
                                 and "redte" in cfg.sweep_policies):
        period = max(cfg.redte_period_us // cfg.dt_us, 1)
        due = (t % period) == 0
        util_q8 = jnp.clip(st.u_ewma * 256, 0, 255).astype(jnp.int32)
        first = ar.path_first[jnp.maximum(ar.pair_cand, 0)]
        head = jnp.maximum(256 - util_q8[first], 1)
        w = jnp.where(ar.pair_cand >= 0, head, 0).astype(jnp.int32)
        st = dataclasses.replace(
            st, redte_w=jnp.where(due, w, st.redte_w))
    return st


def _path_queue_wait(st: SimState, ar: SimArrays, path_idx) -> jnp.ndarray:
    """Standing-queue wait a path's first packets see: sum over hops of
    queue bytes / link capacity. ``path_idx`` must be pre-clamped >= 0."""
    hop = ar.path_links[path_idx]
    return jnp.where(hop >= 0, st.q_bytes[jnp.maximum(hop, 0)]
                     / ar.link_cap[jnp.maximum(hop, 0)], 0.0).sum(-1)


def decide(t, fid, pair, st: SimState, ar: SimArrays, cfg: SimConfig,
           sig_step=None):
    """The single policy-dispatched path-decision core.

    Every caller that turns (hash key, pair) into a candidate choice —
    arrival routing, lazy failover, and the mid-flow re-decision tick —
    goes through here, so all policies apply *their own law* at every
    decision point and sweep-mode dynamic dispatch is implemented once.

    ``fid``: (N,) u32 hash keys. Re-decision callers salt these with the
    flow's nonce so a re-hash can land elsewhere; nonce 0 leaves the key
    unchanged (``fmix32(0) == 0``), preserving arrival decisions exactly.
    ``sig_step``: the step whose ``hist_c`` slot the congestion view
    reads (default ``t``; the failover caller runs before this step's
    monitor tick and passes ``t - 1``).

    Returns ``(k_idx, chosen)``: (N,) candidate-slot index and (N,)
    global path index, both -1 where no valid candidate exists.
    """
    cand = ar.pair_cand[pair]                                   # (N, K)
    cpad = jnp.maximum(cand, 0)

    # candidate liveness: every hop of the path must be alive
    hop = ar.path_links[cpad]                                   # (N,K,H)
    hop_alive = jnp.where(hop >= 0, st.link_alive[jnp.maximum(hop, 0)], True)
    valid = (cand >= 0) & hop_alive.all(-1)

    c_path = st.c_path[cpad]
    c_cong = path_cong_view(st.hist_c, hop, ar.path_sig_delay[cpad],
                            t if sig_step is None else sig_step)
    delay = ar.path_prop[cpad]
    capg = ar.path_cap_gbps[cpad]

    def _choice(policy: str) -> jnp.ndarray:
        if policy in ("lcmp", "lcmp_r"):    # lcmp_r differs only in the
            return selmod.select_egress(fid, c_path, c_cong, valid,  # tick
                                        cfg.select)[0]
        if policy == "lcmp_w":  # beyond-paper: capacity-weighted stage 2
            return selmod.select_egress(fid, c_path, c_cong, valid,
                                        cfg.select, weights=capg)[0]
        if policy in ("ecmp", "amp"):       # amp = per-subflow ECMP hash
            return bl.ecmp(fid, delay, capg, valid)
        if policy == "ucmp":
            return bl.ucmp(fid, delay, capg, valid)
        if policy == "wcmp":
            return bl.wcmp(fid, delay, capg, valid)
        if policy == "redte":
            return bl._weighted_hash(fid, st.redte_w[pair], valid)
        if policy == "fatpaths":
            return bl.fatpaths(fid, ar.path_len[cpad], valid, c_cong,
                               cong_thresh=cfg.select.cong_fallback)
        if policy == "matchrdma":
            # matched rate per candidate: the tightest span's *effective*
            # capacity (degrade schedule applied at decision time — the
            # per-span rate matching) x the congestion headroom seen at
            # the ingress. The headroom reads the SAME delayed signal
            # plane LCMP does (c_cong via hist_c + path_sig_delay) — a
            # rate-matching loop learns about congestion one telemetry
            # RTT late too, no oracle. Padding hops never bind the min.
            eff = ar.link_cap_gbps * jnp.where(
                t >= ar.link_deg_step, ar.link_deg_factor, 1.0)
            lidx = jnp.maximum(hop, 0)
            bneck = jnp.where(hop >= 0, eff[lidx],
                              jnp.float32(1e9)).min(-1)          # (N, K)
            avail = bneck * (256 - c_cong).astype(jnp.float32)
            return bl.matchrdma(
                fid, jnp.minimum(avail, 1e9).astype(jnp.int32), valid)
        raise ValueError(policy)

    if cfg.policy == "sweep":
        # dynamic dispatch on the per-experiment code: every *swept*
        # policy's decision is computed (m<=8 candidates — cheap relative
        # to the per-flow state updates) and the cell's one is gathered,
        # so a vmapped batch can mix policies inside a single trace.
        codes = jnp.asarray([policy_code(p) for p in cfg.sweep_policies],
                            jnp.int32)
        k_all = jnp.stack([_choice(p) for p in cfg.sweep_policies])
        k_idx = jnp.take(k_all, jnp.argmax(codes == ar.policy_code), axis=0)
    else:
        k_idx = _choice(cfg.policy)

    chosen = jnp.take_along_axis(cand, jnp.maximum(k_idx, 0)[:, None],
                                 axis=1)[:, 0]
    chosen = jnp.where(k_idx >= 0, chosen, -1)                  # (N,)
    return k_idx, chosen


def _route_arrivals(t, st: SimState, ar: SimArrays, cfg: SimConfig):
    """Decide paths for the batch of flows arriving this step, and admit
    the routed ones to the window, in arrival-table order, in the slots
    past its last occupied one (``compact_slots`` first where those are
    too few). Flows in flight therefore sit in the window in the order
    they were admitted, whatever its width: every link sum adds the same
    terms in the same order. A step with more routed arrivals than free
    slots admits what fits and sets the sticky ``window_overflow`` flag
    (``run_windowed`` then runs again wider)."""
    idx = ar.arrivals[t]                        # (A,)
    is_flow = idx >= 0
    fidx = jnp.maximum(idx, 0)
    pair = ar.f_pair[fidx]                      # (A,)

    _, chosen = decide(t, ar.f_id[fidx], pair, st, ar, cfg)
    chosen = jnp.where(is_flow, chosen, -1)                     # (A,)

    ok = chosen >= 0
    cpath_sel = jnp.maximum(chosen, 0)
    # queue wait seen by the first packets (standing queues on the path)
    qw = _path_queue_wait(st, ar, cpath_sel)

    rtt = jnp.maximum(2 * ar.path_prop[cpath_sel] // cfg.dt_us, 1)

    A = idx.shape[0]
    W = st.slot_flow.shape[0]
    F = st.flow_path.shape[0]
    rank = jnp.cumsum(ok, dtype=jnp.int32) - 1                  # (A,)
    slot_no = jnp.arange(W, dtype=jnp.int32)
    tail = jnp.where(st.slot_flow >= 0, slot_no + 1, 0).max()
    st = jax.lax.cond(tail + rank[-1] >= W, compact_slots, lambda s: s, st)
    tail = jnp.where(st.slot_flow >= 0, slot_no + 1, 0).max()
    slot = jnp.where(ok, tail + rank, W)
    admitted = slot < W
    # each slot's arrival (-1: none admitted there); pads and unrouted
    # flows take no slot, so results never depend on the pad width
    src = jnp.full((W,), -1, jnp.int32).at[slot].set(
        jnp.arange(A, dtype=jnp.int32), mode="drop")
    new = src >= 0
    a = jnp.maximum(src, 0)

    def put(field, vals):
        return jnp.where(new, vals[a], field)

    return dataclasses.replace(
        st,
        slot_flow=put(st.slot_flow, idx),
        slot_path=put(st.slot_path, chosen),
        remaining=put(st.remaining, ar.f_size[fidx]),
        rate=put(st.rate, ar.path_cap[cpath_sel]),
        cc_target=put(st.cc_target, ar.path_cap[cpath_sel]),
        active=st.active | new,
        extra_wait=put(st.extra_wait, qw),
        rtt_steps=put(st.rtt_steps, rtt.astype(jnp.int32)),
        route_step=jnp.where(new, jnp.int32(0) + t, st.route_step),
        flow_path=st.flow_path.at[jnp.where(admitted, fidx, F)].set(
            chosen, mode="drop"),
        window_overflow=st.window_overflow | (ok & ~admitted).any())


def _slot_pad(name: str, v, keep):
    """``v`` where ``keep`` (W,) holds, slot field ``name``'s pad value
    elsewhere."""
    k = keep.reshape(keep.shape + (1,) * (v.ndim - 1))
    return jnp.where(k, v, jnp.asarray(STATE_PAD.get(name, 0), v.dtype))


def compact_slots(st):
    """``st`` with its occupied slots moved, in their order, to the
    front of the window, and every slot after them free."""
    occ = st.slot_flow >= 0
    W = occ.shape[0]
    src = jnp.full((W,), -1, jnp.int32).at[
        jnp.where(occ, jnp.cumsum(occ, dtype=jnp.int32) - 1, W)].set(
        jnp.arange(W, dtype=jnp.int32), mode="drop")
    keep, at = src >= 0, jnp.maximum(src, 0)
    return dataclasses.replace(st, **{
        name: _slot_pad(name, getattr(st, name)[at], keep)
        for name in SLOT_FIELDS if hasattr(st, name)})


def free_slots(st, gone):
    """``st`` with the slots in ``gone`` (W,) freed: every slot field
    back to its pad value, as ``build`` starts a slot."""
    return dataclasses.replace(st, **{
        name: _slot_pad(name, getattr(st, name), ~gone)
        for name in SLOT_FIELDS if hasattr(st, name)})


def retire(st, finished, fct):
    """Write the flows of the ``finished`` slots (W,) to the answers —
    done, with FCT ``fct`` (W,) — and free their slots."""
    at = jnp.where(finished, st.slot_flow, st.flow_path.shape[0])
    st = dataclasses.replace(
        st, done=st.done.at[at].set(True, mode="drop"),
        fct_us=st.fct_us.at[at].set(fct, mode="drop"))
    return free_slots(st, finished)


def _cc_update(t, st: SimState, ar: SimArrays, cfg: SimConfig,
               path_of_flow, links_f, links_ok):
    """Rate laws reacting to RTT-delayed per-path congestion signals.

    Realism notes (these interact with the routing signal, see DESIGN):
    - ECN marking is RED-style probabilistic between Kmin and Kmax, so the
      equilibrium queue *grows with the number of backlogged flows* — a
      CC that pinned queues at Kmin regardless of load would blind the
      switch's Q estimator (and real DCQCN does not).
    - DCQCN-style decrease/recovery: MD cuts both rate and target; the
      increase phase fast-recovers halfway to target per RTT and only
      probes (+AI on target) once recovered. Without a target bound, N
      backlogged flows each AI-ing a line-rate fraction diverge.

    Both engines call this verbatim: the fluid engine applies ``rate``
    directly as the sending rate; the packet engine paces injection with
    it and bounds in-flight bytes by the rate-BDP window — the "per-flow
    windows driven by the same CC laws" contract.
    """
    slot = jnp.asarray((t - st.rtt_steps) % HIST, jnp.int32)
    # Feedback exists only once the flow's own first packets have had a
    # full RTT on its *current* path: gate on steps since the flow's
    # routing step, not the global clock — otherwise a flow arriving at
    # t >> RTT immediately reads congestion history recorded *before* it
    # was routed (stale signals from traffic it never shared a path with).
    have_fb = (t - st.route_step) > st.rtt_steps
    lidx = jnp.maximum(links_f, 0)                              # (F,H)
    flat = lidx * HIST + slot[:, None]
    q_sig = jnp.where(links_ok, st.hist_q.reshape(-1)[flat], 0.0).max(-1)
    u_sig = jnp.where(links_ok, st.hist_u.reshape(-1)[flat], 0.0).max(-1)
    q_sig = jnp.where(have_fb, q_sig, 0.0)
    u_sig = jnp.where(have_fb, u_sig, 0.0)

    line = ar.path_cap[jnp.maximum(path_of_flow, 0)]
    # the CC control loop operates per RTT; discretize increments per step
    inv_rtt = 1.0 / st.rtt_steps.astype(jnp.float32)
    ai = cfg.ai_frac * line * inv_rtt          # ai_frac = per-RTT probe frac
    # MD cadence: a reaction timer, never slower than one per RTT and
    # never faster than ~8 decreases per feedback epoch (the rtt//8
    # floor bounds how often a flow can cut on the *same* stale signal)
    dec_gap = jnp.minimum(
        st.rtt_steps,
        jnp.maximum(max(cfg.cc_dec_period_us // cfg.dt_us, 1),
                    st.rtt_steps // 8))
    can_dec = (t - st.last_dec) >= dec_gap

    # RED-style marking probability from the delayed queue signal
    kmin = cfg.ecn_kmin_bytes * cfg.cap_scale
    kmax = cfg.ecn_kmax_factor * kmin
    p_mark = jnp.clip((q_sig - kmin) / (kmax - kmin), 0.0, 1.0)
    fid = ar.f_id[jnp.maximum(st.slot_flow, 0)]
    u01 = (selmod.fmix32(fid ^ jnp.uint32(t)).astype(jnp.float32)
           * (1.0 / 4294967296.0))
    marked = u01 < p_mark

    target = jnp.maximum(st.cc_target, 0.05 * line)

    def aimd(dec_event, md_rate):
        """Shared DCQCN-shaped decrease/fast-recovery/probe machinery.
        Recovery moves halfway to target per *RTT* (not per step) and the
        target probes +ai_frac of line per RTT once recovered."""
        dec = dec_event & can_dec
        new_target = jnp.where(dec, st.rate, target)
        recover = st.rate + (new_target - st.rate) * 0.5 * inv_rtt
        probe = jnp.where(st.rate >= 0.95 * new_target, ai, 0.0)
        rate = jnp.where(dec, st.rate * md_rate, recover + probe)
        new_target = jnp.where(dec, new_target, new_target + probe)
        return rate, new_target, dec

    if cfg.cc == "dcqcn":
        rate, new_target, dec = aimd(marked, cfg.md_factor)
        alpha, pdel = st.cc_alpha, st.prev_delay
    elif cfg.cc == "dctcp":
        alpha = st.cc_alpha * (1 - 1 / 16) + marked.astype(jnp.float32) / 16
        rate, new_target, dec = aimd(marked, 1.0 - alpha / 2)
        pdel = st.prev_delay
    elif cfg.cc == "timely":
        lcap = ar.link_cap[lidx]
        d_us = jnp.where(links_ok, st.hist_q.reshape(-1)[flat] / lcap, 0.0).max(-1)
        d_us = jnp.where(have_fb, d_us, 0.0)
        grad = d_us - st.prev_delay
        t_high = 2.0 * kmin / line
        rate, new_target, dec = aimd(((d_us > t_high) | (grad > 0)) & (d_us > 0),
                                     cfg.md_factor)
        alpha, pdel = st.cc_alpha, d_us
    elif cfg.cc == "hpcc":
        eta = 0.95
        bdp = line * jnp.maximum(st.rtt_steps.astype(jnp.float32) * cfg.dt_us, 1.0)
        u_tot = u_sig + q_sig / jnp.maximum(bdp, 1.0)   # inflight-based U
        corr = jnp.clip(eta / jnp.maximum(u_tot, 1e-3), 0.3, 1.0)
        rate, new_target, dec = aimd(u_tot > eta, 1.0)  # md via corr below
        rate = jnp.where(dec, st.rate * corr, rate)
        alpha, pdel = st.cc_alpha, st.prev_delay
    else:
        raise ValueError(cfg.cc)

    rate = jnp.clip(rate, 0.001 * line, line)
    new_target = jnp.clip(new_target, 0.001 * line, line)
    last_dec = jnp.where(dec, jnp.int32(t), st.last_dec)
    act = st.active
    return dataclasses.replace(
        st, rate=jnp.where(act, rate, st.rate),
        cc_target=jnp.where(act, new_target, st.cc_target),
        cc_alpha=alpha, prev_delay=pdel,
        last_dec=jnp.where(act, last_dec, st.last_dec))


def _reroute_dead(t, st: SimState, ar: SimArrays, cfg: SimConfig) -> SimState:
    """Re-decide every active flow whose pinned path lost a link (the
    data-plane lazy-failover semantics, vectorized over the window once
    at the trip step). Failover goes through the shared decision core, so
    every policy — wcmp/ucmp/redte cells in a sweep included — re-decides
    under its *own* law against the post-trip liveness mask. A flow with
    no live candidate leaves the network: its slot is freed, and its
    answer keeps the dead path, not done.

    The reroute runs before this step's monitor tick, so slot t is not
    yet written: the freshest signal physics offers here is step t-1."""
    hop = ar.path_links[jnp.maximum(st.slot_path, 0)]
    dead = jnp.where(hop >= 0, ~st.link_alive[jnp.maximum(hop, 0)], False).any(-1)
    move = st.active & dead & (st.slot_path >= 0)

    sf = jnp.maximum(st.slot_flow, 0)
    k_idx, new_path = decide(t, ar.f_id[sf], ar.f_pair[sf], st, ar, cfg,
                             sig_step=t - 1)
    ok = move & (k_idx >= 0)
    npad = jnp.maximum(new_path, 0)
    # CC state re-initializes with the path: a rerouted flow is "first
    # packets" again — target line rate of the NEW path, a fresh MD
    # timer, and the new path's standing-queue wait (not the dead one's)
    qw = _path_queue_wait(st, ar, npad)
    F = st.flow_path.shape[0]
    st = dataclasses.replace(
        st,
        slot_path=jnp.where(ok, new_path, st.slot_path),
        flow_path=st.flow_path.at[jnp.where(ok, st.slot_flow, F)].set(
            new_path, mode="drop"),
        rate=jnp.where(ok, ar.path_cap[npad], st.rate),
        cc_target=jnp.where(ok, ar.path_cap[npad], st.cc_target),
        last_dec=jnp.where(ok, jnp.int32(-(1 << 20)), st.last_dec),
        cc_alpha=jnp.where(ok, 0.0, st.cc_alpha),
        prev_delay=jnp.where(ok, 0.0, st.prev_delay),
        extra_wait=jnp.where(ok, qw, st.extra_wait),
        rtt_steps=jnp.where(
            ok, jnp.maximum(2 * ar.path_prop[npad]
                            // cfg.dt_us, 1).astype(jnp.int32), st.rtt_steps),
        route_step=jnp.where(ok, jnp.int32(0) + t, st.route_step))
    return free_slots(st, move & (k_idx < 0))


def wants_redecide(cfg: SimConfig) -> bool:
    """Python-level (trace-time) gate for the mid-flow re-decision plane:
    true iff the engine's eligibility knob is armed AND some policy in
    the dispatch set actually re-decides. False keeps the step function
    bit-identical to the pinned-path program (no extra ops traced)."""
    knob = (cfg.flowlet_gap_us if cfg.engine == "packet"
            else cfg.redecide_period_us)
    if knob <= 0:
        return False
    pols = cfg.sweep_policies if cfg.policy == "sweep" else (cfg.policy,)
    return any(p in REDECIDE_POLICIES for p in pols)


def redecide_tick(t, st: SimState, ar: SimArrays, cfg: SimConfig,
                  eligible) -> SimState:
    """Mid-flow re-decision for eligible active flows (the third caller
    of ``decide``). ``eligible`` is the engine-specific trigger mask:
    the packet engine passes flowlet idle-gap detection, the fluid
    engine an all-true mask under a ``redecide_period_us`` timer cond.

    Each opportunity bumps the flow's nonce, and the decision hashes on
    ``f_id ^ fmix32(nonce)`` — a fresh pseudo-random key per flowlet
    (FatPaths re-hash semantics) that still replays deterministically.
    Unlike failover, a path change here keeps the flow's CC rate state:
    a flowlet switch is the same transport entity continuing on a new
    path, not a restart — only the route bookkeeping (RTT, route step,
    standing-queue wait) follows the new path. The feedback gate in
    ``_cc_update`` then holds rates steady until the new path's own
    signals are a full RTT old."""
    move = st.active & (st.slot_path >= 0) & eligible & (t > st.route_step)
    if cfg.policy == "sweep":
        # only re-decision-capable cells may move; others stay pinned
        # bit-for-bit even when sharing the trace with fatpaths/lcmp_r
        cell_ok = jnp.asarray(False)
        for p in cfg.sweep_policies:
            if p in REDECIDE_POLICIES:
                cell_ok = cell_ok | (ar.policy_code == policy_code(p))
        move = move & cell_ok
    elif cfg.policy not in REDECIDE_POLICIES:
        return st

    nonce = st.route_nonce + move.astype(jnp.int32)
    sf = jnp.maximum(st.slot_flow, 0)
    fid = ar.f_id[sf] ^ selmod.fmix32(nonce.astype(jnp.uint32))
    k_idx, new_path = decide(t, fid, ar.f_pair[sf], st, ar, cfg)
    changed = move & (k_idx >= 0) & (new_path != st.slot_path)
    npad = jnp.maximum(new_path, 0)
    qw = _path_queue_wait(st, ar, npad)
    rtt = jnp.maximum(2 * ar.path_prop[npad] // cfg.dt_us, 1).astype(jnp.int32)
    F = st.flow_path.shape[0]
    return dataclasses.replace(
        st,
        route_nonce=nonce,
        slot_path=jnp.where(changed, new_path, st.slot_path),
        flow_path=st.flow_path.at[jnp.where(changed, st.slot_flow, F)].set(
            new_path, mode="drop"),
        rtt_steps=jnp.where(changed, rtt, st.rtt_steps),
        route_step=jnp.where(changed, jnp.int32(0) + t, st.route_step),
        extra_wait=jnp.where(changed, qw, st.extra_wait))
