"""Slotted packet-level simulation engine (the NS-3 analogue of paper
§6, Figs. 7-9) — the high-fidelity backend of the multi-engine core.

Where the fluid engine (``repro.netsim.fluid``) abstracts links as
max-min rate dividers with analytically integrated queues, this engine
moves *bytes of whole MTU packets* hop by hop through per-flow FIFO
queues, as one fully-batched jitted ``lax.scan`` over time slots:

- **windowed, paced sources**: each flow injects whole ``mtu_bytes``
  packets paced by its CC rate (a per-flow credit accumulator carries
  fractional packets across slots), bounded by the rate-BDP window
  ``rate x RTT`` — in-flight (queued) bytes never exceed the window, so
  the CC laws govern both rate *and* burst size. The final sub-MTU runt
  packet is injected exactly.
- **store-and-forward hop queues**: ``fq[f, h]`` holds flow ``f``'s
  bytes queued at the egress of its ``h``-th hop link. Each slot serves
  hops in path order under per-link byte budgets (``cap x dt``, shared
  across all hop positions a link appears in), so a packet can cut
  through an idle path within one slot but never exceeds any link's
  service rate. Per-flow service within a slot splits a link's budget
  proportionally to queued bytes (byte-wise FIFO fairness).
- **PFC pause/resume (lossless RDMA)**: per-link XOFF/XON hysteresis on
  instantaneous queue depth (``pfc_xoff_frac``/``pfc_xon_frac`` of the
  scaled buffer). The pause state reaches the *upstream* transmitter one
  backward link-propagation delay late (the ``hist_pause`` ring), so a
  paused long-haul queue keeps absorbing in-flight bytes for a full
  one-way delay — the headroom problem 6 GB long-haul buffers exist
  for. Buffer space itself is a hard bound (byte-conserving acceptance
  factors), so nothing is ever dropped.
- **ECN at the switch, delayed to the source**: per-slot queue depths
  land in the shared ``hist_q`` ring; the shared ``engine._cc_update``
  laws read them one RTT late and mark RED-style between ``Kmin`` and
  ``Kmax = ecn_kmax_factor x Kmin`` — the same signal chain as the
  fluid engine, fed by packet-granular queue dynamics.
- **identical control/signal/routing planes**: the ``core.cong``
  register pipeline (``engine.monitor_tick`` -> ``hist_c``), the
  propagation-delayed ``path_cong_view``, the periodic ``C_path``
  re-install (``engine.ctrl_tick``), arrival-time routing through
  ``select.select_egress``/baselines (``engine._route_arrivals``), flow
  stickiness, and lazy failover are the *same functions* the fluid
  engine runs — the engines differ only in data-plane dynamics. The
  mid-flow re-decision plane (``engine.redecide_tick``) is shared too,
  but its *eligibility* is this engine's own: genuine flowlet idle gaps
  (``last_tx`` + drained hop queues for >= ``flowlet_gap_us``), where
  the fluid engine can only offer a timer epoch.

FCT is measured by actual delivery: a flow completes when its last byte
leaves its last hop queue; propagation (applied analytically, exactly as
the fluid engine does) is added once. Queueing delay is therefore
*experienced*, not estimated — no ``extra_wait`` correction terms.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.netsim import engine, sanitize
from repro.netsim.engine import (HIST, SimArrays, SimConfig, SimState,
                                 _cc_update, _reroute_dead, _route_arrivals,
                                 ctrl_tick, monitor_tick, redecide_tick,
                                 redte_tick, wants_redecide)
from repro.netsim.paths import PathTable
from repro.traffic.gen import FlowSet

name = "packet"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PacketState(SimState):
    """``SimState`` plus the packet data plane, per slot of the window.
    In-flight bytes of the flow in slot ``w`` are exactly ``fq[w].sum()``
    — injected but not yet delivered."""
    fq: jnp.ndarray          # (W, H) f32 bytes queued at each hop egress
    credit: jnp.ndarray      # (W,) f32 pacing credit (fractional packets)
    delivered: jnp.ndarray   # (W,) f32 bytes delivered at destination
    last_tx: jnp.ndarray     # (W,) i32 last slot the flow had bytes in
                             # flight (flowlet idle-gap detection; only
                             # maintained when the re-decision plane is on)
    pfc_pause: jnp.ndarray   # (L,) bool current XOFF state
    hist_pause: jnp.ndarray  # (L, HIST) bool pause ring (upstream reads
                             # it one backward link propagation late)


def build(table: PathTable, flows: FlowSet, cfg: SimConfig):
    """Shared ``engine.build`` plus zero-initialized packet state."""
    arr, base = engine.build(table, flows, cfg)
    W = base.slot_flow.shape[0]
    L = base.q_bytes.shape[0]
    H = arr.path_links.shape[1]
    state = PacketState(
        **{f.name: getattr(base, f.name)
           for f in dataclasses.fields(SimState)},
        fq=jnp.zeros((W, H), jnp.float32),
        credit=jnp.zeros((W,), jnp.float32),
        delivered=jnp.zeros((W,), jnp.float32),
        last_tx=jnp.full((W,), 1 << 20, jnp.int32),  # sentinel: never sent
                                                     # (t - last_tx < 0 so a
                                                     # routed-but-quiet flow
                                                     # is not flowlet-eligible)
        pfc_pause=jnp.zeros((L,), bool),
        hist_pause=jnp.zeros((L, HIST), bool),
    )
    return arr, state


def _reroute_dead_packet(t, st: PacketState, ar: SimArrays,
                         cfg: SimConfig) -> PacketState:
    """Lazy failover with packet-queue cleanup: the shared reroute
    re-decides paths/CC; bytes stranded in the dead path's queues are
    treated as lost-and-retransmitted (go-back-N) — returned to
    ``remaining`` so the flow re-sends them on the new path. (A flow
    with no live candidate leaves with its slot, queues and all.)"""
    old_path = st.slot_path
    st2 = _reroute_dead(t, st, ar, cfg)
    moved = st2.active & (st2.slot_path != old_path)
    stranded = st.fq.sum(-1)
    return dataclasses.replace(
        st2,
        remaining=jnp.where(moved, st2.remaining + stranded, st2.remaining),
        fq=jnp.where(moved[:, None], 0.0, st2.fq),
        credit=jnp.where(moved, 0.0, st2.credit))


def make_step(ar: SimArrays, cfg: SimConfig):
    L = ar.link_cap.shape[0]
    H = ar.path_links.shape[1]
    dt = float(cfg.dt_us)
    mtu = float(cfg.mtu_bytes)
    buf = float(cfg.buffer_bytes * cfg.cap_scale)
    xoff = cfg.pfc_xoff_frac * buf
    xon = cfg.pfc_xon_frac * buf
    checks_on = sanitize.enabled(cfg)

    def seg(vals, idx):
        return jax.ops.segment_sum(vals, idx, num_segments=L)

    def step(st: PacketState, t):
        # Each phase runs under a ``step.<phase>`` named scope (op
        # metadata only), the shared ones as in the fluid engine.
        # 0) failure injection + lazy fast-failover (shared semantics,
        # plus dead-queue cleanup — see _reroute_dead_packet)
        if cfg.has_failures:
            with jax.named_scope("step.failover"):
                st = dataclasses.replace(st, link_alive=t < ar.link_fail_step)
                is_trip = (ar.link_fail_step == t).any()
                st = jax.lax.cond(
                    is_trip, lambda s: _reroute_dead_packet(t, s, ar, cfg),
                    lambda s: s, st)

        # 1) switch monitor tick + control-plane refresh (shared)
        with jax.named_scope("step.monitor"):
            st = monitor_tick(t, st, ar, cfg)
        with jax.named_scope("step.ctrl"):
            st = ctrl_tick(t, st, ar, cfg)

        # 2) arrivals + routing decisions (shared herd batch)
        with jax.named_scope("step.route"):
            st = _route_arrivals(t, st, ar, cfg)

        # 2b) flowlet re-hash (FatPaths semantics): a flow whose hop
        # queues fully drained >= flowlet_gap_us ago may re-decide — the
        # inter-flowlet idle gap guarantees no packets of the previous
        # flowlet are still in flight, so switching paths cannot reorder.
        # Eligibility is data-dependent (per flow, batched under vmap),
        # so unlike the fluid engine's timer epoch this runs every slot
        # when the plane is armed; the Python-level gate keeps the
        # pinned-path program untouched otherwise.
        if wants_redecide(cfg):
            gap_steps = max(cfg.flowlet_gap_us // cfg.dt_us, 1)
            with jax.named_scope("step.redecide"):
                idle = st.fq.sum(-1) <= 0.0
                st = redecide_tick(t, st, ar, cfg,
                                   idle & ((t - st.last_tx) >= gap_steps))

        # 2c) the flows this step moves, counted (not part of the answers)
        with jax.named_scope("step.counters"):
            live = engine.live_flows(st)

        # flow/link geometry of the routed flows
        with jax.named_scope("step.hops"):
            pf = st.slot_path
            routed = pf >= 0
            links_f = ar.path_links[jnp.maximum(pf, 0)]         # (W,H)
            geom_ok = (links_f >= 0) & routed[:, None]
            lidx = jnp.maximum(links_f, 0)

        # 3) PFC XOFF/XON hysteresis on the instantaneous queue depth;
        # the new state lands in the pause ring at slot t and is read
        # back by upstream transmitters with backward propagation delay.
        with jax.named_scope("step.pfc"):
            pause = jnp.where(st.q_bytes > xoff, True,
                              jnp.where(st.q_bytes < xon, False,
                                        st.pfc_pause))
        with jax.named_scope("step.rings"):
            hist_pause = st.hist_pause.at[:, jnp.asarray(t % HIST,
                                                         jnp.int32)].set(
                pause, mode=engine.RING_SCATTER_MODE)
        with jax.named_scope("step.pfc"):
            st = dataclasses.replace(st, pfc_pause=pause,
                                     hist_pause=hist_pause)
            pause_flat = hist_pause.reshape(-1)

        # 4) injection: CC-paced credit, rate-BDP window, whole packets.
        # The NIC sits at the ingress switch, so its pause gate reads the
        # first link's *current* XOFF state (zero propagation).
        with jax.named_scope("step.inject"):
            act = st.active & routed
            win = jnp.maximum(
                st.rate * st.rtt_steps.astype(jnp.float32) * dt, mtu)
            inflight = st.fq.sum(-1)
            credit = jnp.where(act, st.credit + st.rate * dt, 0.0)
            credit = jnp.minimum(credit, win)        # pause != stored burst
            avail = jnp.minimum(credit, jnp.clip(win - inflight, 0.0, None))
            l0 = lidx[:, 0]
            avail = jnp.where(act & ~pause[l0], avail, 0.0)
            inject = jnp.where(st.remaining <= avail, st.remaining,
                               jnp.floor(avail / mtu) * mtu)
            # ingress buffer space is a hard bound (byte-conserving even
            # when the delayed PFC gate reacts too late)
            space0 = jnp.clip(buf - st.q_bytes, 0.0, None)
            inj_factor = jnp.minimum(
                1.0, space0 / jnp.maximum(seg(inject, l0), 1e-9))
            scaled = inject * inj_factor[l0]
            # re-quantize a space-limited injection to whole packets so
            # the packet model survives buffer pressure (the exact-runt
            # path is the unscaled branch and stays byte-exact)
            inject = jnp.where(scaled < inject,
                               jnp.floor(scaled / mtu) * mtu, inject)
            st = dataclasses.replace(
                st,
                remaining=st.remaining - inject,
                credit=jnp.where(act, credit - inject, 0.0))

        # 5) hop-by-hop store-and-forward under per-link budgets.
        # Serving hops in path order lets a packet cross an idle path
        # within one slot (cut-through) while the shared ``served``
        # accumulator keeps every link inside cap x dt no matter how many
        # hop positions it appears at. ``q_now`` tracks intra-slot depth
        # for the buffer-space acceptance factors.
        with jax.named_scope("step.hops"):
            cap_nom = ar.link_cap
            if cfg.has_degrade:
                cap_nom = cap_nom * jnp.where(t >= ar.link_deg_step,
                                              ar.link_deg_factor, 1.0)
            cap = jnp.where(st.link_alive, cap_nom, 1e-9)
            budget = cap * dt
            fq = st.fq.at[:, 0].add(inject)
            served = jnp.zeros((L,), jnp.float32)
            in_l = seg(inject, l0)                   # arrivals per link
            q_now = st.q_bytes + in_l
            delivered_add = jnp.zeros_like(st.delivered)
            for h in range(H):
                lh = lidx[:, h]
                okh = geom_ok[:, h]
                if h + 1 < H:
                    lnext = links_f[:, h + 1]
                    has_next = lnext >= 0
                    lnextc = jnp.maximum(lnext, 0)
                    # PFC gate: the downstream queue's pause state, read
                    # one backward propagation of THIS link late (the
                    # pause frame travels upstream over hop h's fiber)
                    pd = ar.link_delay_us[lh] // cfg.dt_us
                    pslot = jnp.asarray((t - pd) % HIST, jnp.int32)
                    paused_next = pause_flat[lnextc * HIST + pslot] & has_next
                else:
                    has_next = jnp.zeros_like(okh)
                    lnextc = lh
                    paused_next = jnp.zeros_like(okh)
                # in checked mode the PFC send gate routes through the
                # sanitizer seam (identity in production; the
                # pfc_lossless mutation corrupts it to prove check_pfc
                # fires)
                gate = sanitize.pfc_gate(okh, paused_next) if checks_on \
                    else (okh & ~paused_next)
                sendable = jnp.where(gate, fq[:, h], 0.0)
                demand = seg(sendable, lh)
                f_serv = jnp.minimum(
                    1.0, jnp.clip(budget - served, 0.0, None)
                    / jnp.maximum(demand, 1e-9))
                out = sendable * f_serv[lh]
                # downstream buffer acceptance (delivery is never blocked)
                offered_in = seg(jnp.where(has_next, out, 0.0), lnextc)
                f_in = jnp.minimum(1.0, jnp.clip(buf - q_now, 0.0, None)
                                   / jnp.maximum(offered_in, 1e-9))
                out = out * jnp.where(has_next, f_in[lnextc], 1.0)
                fwd = jnp.where(has_next, out, 0.0)
                if checks_on:
                    # pfc_lossless: XOFF downstream => nothing forwarded
                    sanitize.check_pfc(fwd, paused_next)
                fq = fq.at[:, h].add(-out)
                if h + 1 < H:
                    fq = fq.at[:, h + 1].add(fwd)
                served = served + seg(out, lh)
                in_l = in_l + seg(fwd, lnextc)
                q_now = q_now - seg(out, lh) + seg(fwd, lnextc)
                delivered_add = delivered_add + jnp.where(has_next, 0.0, out)

            q_new = seg(jnp.where(geom_ok, fq, 0.0).reshape(-1),
                        lidx.reshape(-1))
            # offered-load utilization: standing backlog + every byte
            # that arrived wanting service this slot, over the service
            # capacity — exceeds 1 under overload and stays high while
            # PFC-paused backlog sits unserved, matching the fluid
            # engine's offered/cap semantics for the HPCC law and RedTE's
            # weights
            util = (st.q_bytes + in_l) / jnp.maximum(budget, 1e-9)
            hslot = jnp.asarray(t % HIST, jnp.int32)
            delivered = st.delivered + delivered_add
        with jax.named_scope("step.rings"):
            hist_q = st.hist_q.at[:, hslot].set(
                q_new, mode=engine.RING_SCATTER_MODE)
            hist_u = st.hist_u.at[:, hslot].set(
                util, mode=engine.RING_SCATTER_MODE)
        with jax.named_scope("step.hops"):
            st = dataclasses.replace(
                st, fq=fq, q_bytes=q_new, delivered=delivered,
                hist_q=hist_q, hist_u=hist_u,
                u_ewma=st.u_ewma * 0.99 + 0.01 * jnp.minimum(util, 1.0),
                serv_bytes=st.serv_bytes + served)

        # 5b) flowlet clock: a flow is "transmitting" any slot it injects
        # or still has bytes queued somewhere — the idle gap the flowlet
        # detector measures starts when both go to zero. (inject covers
        # the inject-and-cut-through-in-one-slot case.)
        if wants_redecide(cfg):
            with jax.named_scope("step.redecide"):
                busy = (inject > 0.0) | (st.fq.sum(-1) > 0.0)
                st = dataclasses.replace(
                    st, last_tx=jnp.where(busy, jnp.int32(0) + t,
                                          st.last_tx))

        # 6) CC rate update from the RTT-delayed rings (shared laws)
        with jax.named_scope("step.cc"):
            links_ok = geom_ok & st.active[:, None]
            st = _cc_update(t, st, ar, cfg, pf, links_f, links_ok)

        # 7) completion by delivery: all bytes injected AND every hop
        # queue fully drained (the final drain is exact in f32: the last
        # service factor is 1, so fq hits 0.0, not an epsilon). A
        # finished flow's FCT goes to the answers and its slot is freed.
        with jax.named_scope("step.drain"):
            newly_done = (st.active & (st.remaining <= 0.0)
                          & (st.fq.sum(-1) <= 0.0))
            prop = ar.path_prop[jnp.maximum(pf, 0)].astype(jnp.float32)
            arr_us = ar.f_arr_us[jnp.maximum(st.slot_flow, 0)]
            fct = (t + 1) * dt - arr_us + prop
            st = engine.retire(st, newly_done, fct)

        # 8) RedTE periodic split-ratio re-optimization (shared tick)
        with jax.named_scope("step.redte"):
            st = redte_tick(t, st, ar, cfg)

        # 9) the work counters (not part of the answers)
        with jax.named_scope("step.counters"):
            st = engine.count_live(st, live)

        # 10) debug-mode physics invariants (Python gate: the unchecked
        # trace carries no extra ops)
        if checks_on:
            with jax.named_scope("step.checks"):
                st = sanitize.step_check(t, st, ar, cfg)

        return st, None

    return step


def run_impl(arrs: SimArrays, state: PacketState, cfg: SimConfig) -> PacketState:
    """Unjitted scan body — the sweep engine vmaps/shard_maps this and
    wraps its own single jit around the whole batch."""
    step = make_step(arrs, cfg)
    final, _ = jax.lax.scan(step, state, jnp.arange(cfg.num_steps))
    return final


_run_jit = jax.jit(run_impl, static_argnames=("cfg",))


def run(arrs: SimArrays, state: PacketState, cfg: SimConfig) -> PacketState:
    """Single-experiment entry: the plain jit, or the checkify-wrapped
    sanitizer program when ``cfg.checks`` is set (raises
    ``checkify.JaxRuntimeError`` on an invariant violation), run again
    on a wider window where the flows in flight outgrew it
    (``engine.run_windowed``)."""
    if sanitize.enabled(cfg):
        return engine.run_windowed(
            lambda a, s: sanitize.run_with_checks(run_impl, a, s, cfg),
            arrs, state, cfg)
    return engine.run_windowed(lambda a, s: _run_jit(a, s, cfg),
                               arrs, state, cfg)
