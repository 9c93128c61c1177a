"""Flow-level fluid simulator for inter-DC RDMA routing (paper §6), as
one jitted ``lax.scan`` — the fast backend of the multi-engine core
(``repro.netsim.engine``; the packet-level backend is
``repro.netsim.packet``).

Model (standard fluid FCT-benchmark abstractions):
- flows arrive (Poisson, CDF-sized), are routed at arrival (per-flow
  stickiness — the paper never migrates active flows; the FatPaths/lcmp_r
  baselines may additionally re-decide on a ``redecide_period_us`` epoch
  via the shared re-decision tick), start at line rate
  (RDMA), and share links max-min-proportionally: each link scales the
  flows through it by ``min(1, cap/offered)`` and a flow sends at its
  path-min factor — so per-link service never exceeds capacity.
- per-link byte queues integrate overload ``(offered - cap)+ dt`` and
  drain otherwise (PFC-lossless: clamped at the 6 GB long-haul buffer,
  never dropped). Queues contribute waiting time to FCT (at arrival and
  completion) and are the congestion-signal source.
- congestion feedback is **RTT-delayed**: rate control reads link signals
  from ``t - RTT(path)`` via per-link history rings — the paper's
  "slow and easily outdated feedback" is modeled explicitly.
- end-host CC is a pluggable rate law (DCQCN / DCTCP / TIMELY / HPCC
  -like), all reacting to the delayed signals, MD gated by a reaction
  timer (min of one RTT and ``cc_dec_period_us``).
- the LCMP switch runs inside the loop: per-link Q/T/D registers are
  refreshed every ``dt`` (the monitor cadence) and new-flow batches run
  the exact ``repro.core`` decision path — a batch arriving in the same
  step *is* the paper's simultaneous-arrival herd case.
- the *routing* signal is propagation-faithful too: each hop's quantized
  ``C_cong`` (the ``core.cong`` register-pipeline output, stored per step
  in the ``hist_c`` ring) reaches the ingress only after the hop's
  one-way propagation distance back to it (``SimArrays.path_sig_delay``,
  scaled by ``sig_delay_scale`` for staleness ablations). The decision
  reads the max over hops of these delayed scores — never raw queue
  bytes, and never fresher than physics allows.
- the control plane is live: ``C_path`` is switch *state*, re-installed
  every ``ctrl_period_us`` from **effective** link capacities (degrade
  schedule + liveness applied) via ``core.pathq`` — the paper's §7.3
  update-period knob. ``ctrl_period_us=0`` freezes the build-time table.

Everything dynamic lives in ``SimState`` (a pytree); one ``run()`` call
lowers to a single XLA while-loop. The config/state/arrays dataclasses,
``build()``, the signal/control planes, routing and the CC laws live in
``repro.netsim.engine`` (shared with the packet engine) and are
re-exported here for compatibility.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# Shared multi-engine core — re-exported so `fluid.X` keeps working for
# every name that predates the engine split.
from repro.netsim import engine, sanitize
from repro.netsim.engine import (  # noqa: F401
    ENGINES, HIST, POLICIES, POLICY_CODES, REDECIDE_POLICIES, _NEVER,
    SimArrays, SimConfig, SimState, _cc_update, _path_queue_wait,
    _reroute_dead, _route_arrivals, attach_link_caps, build, ctrl_refresh,
    ctrl_tick, decide, monitor_tick, path_cong_view, policy_code,
    redecide_tick, redte_tick, wants_redecide)

name = "fluid"


# --------------------------------------------------------------------- step
def make_step(ar: SimArrays, cfg: SimConfig):
    L = ar.link_cap.shape[0]
    dt = float(cfg.dt_us)
    checks_on = sanitize.enabled(cfg)

    def step(st: SimState, t):
        # Each phase runs under a ``step.<phase>`` named scope: op
        # metadata only, so a device trace can charge each op's time to
        # its phase.
        # 0) failure injection + lazy fast-failover (paper §3.4): at a
        # trip step, flows pinned to a dead path are treated as "first
        # packets" again and re-hashed onto live candidates. The schedule
        # lives in (L,) arrays shared across sweep cells, so the trip
        # predicate stays unbatched under vmap and the reroute cond is a
        # real branch (paid only at trip steps), not a select.
        if cfg.has_failures:
            with jax.named_scope("step.failover"):
                st = dataclasses.replace(st, link_alive=t < ar.link_fail_step)
                is_trip = (ar.link_fail_step == t).any()
                st = jax.lax.cond(is_trip,
                                  lambda s: _reroute_dead(t, s, ar, cfg),
                                  lambda s: s, st)

        # 1) switch monitor tick + 1b) control-plane refresh (shared)
        with jax.named_scope("step.monitor"):
            st = monitor_tick(t, st, ar, cfg)
        with jax.named_scope("step.ctrl"):
            st = ctrl_tick(t, st, ar, cfg)

        # 2) arrivals + routing decisions (the herd batch)
        with jax.named_scope("step.route"):
            st = _route_arrivals(t, st, ar, cfg)

        # 2b) mid-flow re-decision epoch (fluid eligibility is a timer:
        # every redecide_period_us all re-decision-capable flows may
        # re-hash). The gate is Python-level when the plane is off —
        # nothing extra is traced — and a real lax.cond branch when on
        # (t is unbatched under vmap, so off-epoch steps pay nothing).
        if wants_redecide(cfg):
            period = max(cfg.redecide_period_us // cfg.dt_us, 1)
            with jax.named_scope("step.redecide"):
                st = jax.lax.cond(
                    (t % period) == 0,
                    lambda s: redecide_tick(t, s, ar, cfg,
                                            jnp.ones_like(s.active)),
                    lambda s: s, st)

        # 2c) the flows this step moves, counted (not part of the answers)
        with jax.named_scope("step.counters"):
            live = engine.live_flows(st)

        with jax.named_scope("step.links"):
            # 3) offered load per link
            pf = st.slot_path
            links_f = ar.path_links[jnp.maximum(pf, 0)]         # (W,H)
            links_ok = (links_f >= 0) & st.active[:, None] & (pf >= 0)[:, None]
            lidx = jnp.maximum(links_f, 0)
            contrib = jnp.where(links_ok, st.rate[:, None], 0.0)
            offered = jax.ops.segment_sum(contrib.reshape(-1),
                                          lidx.reshape(-1),
                                          num_segments=L)       # (L,) B/us

            # 4) per-link share factor and queue integration. Degradation
            # is *silent* (an OTN segment loses capacity but stays up):
            # flows stay pinned and only CC + the switch's congestion
            # registers react — the scenario the paper's cost model is
            # meant to absorb.
            cap_nom = ar.link_cap
            if cfg.has_degrade:
                cap_nom = cap_nom * jnp.where(t >= ar.link_deg_step,
                                              ar.link_deg_factor, 1.0)
            cap = jnp.where(st.link_alive, cap_nom, 1e-9)
            factor_l = jnp.minimum(1.0, cap / jnp.maximum(offered, 1e-9))
            served = jnp.minimum(offered, cap)
            q = jnp.clip(st.q_bytes + (offered - cap) * dt, 0.0,
                         float(cfg.buffer_bytes * cfg.cap_scale))
            util = offered / cap
        with jax.named_scope("step.rings"):
            hslot = jnp.asarray(t % HIST, jnp.int32)
            hist_q = st.hist_q.at[:, hslot].set(
                q, mode=engine.RING_SCATTER_MODE)
            hist_u = st.hist_u.at[:, hslot].set(
                util, mode=engine.RING_SCATTER_MODE)
        with jax.named_scope("step.links"):
            st = dataclasses.replace(
                st, q_bytes=q, hist_q=hist_q, hist_u=hist_u,
                u_ewma=st.u_ewma * 0.99 + 0.01 * jnp.minimum(util, 1.0),
                serv_bytes=st.serv_bytes + served * dt)

        # 5) CC rate update from delayed signals
        with jax.named_scope("step.cc"):
            st = _cc_update(t, st, ar, cfg, pf, links_f, links_ok)

        # 6) drain flows at bottleneck-shared rate; a finished flow's
        # FCT goes to the answers and its slot is freed
        with jax.named_scope("step.drain"):
            f_factor = jnp.where(links_ok, factor_l[lidx], 1.0).min(-1)
            send = jnp.where(st.active, st.rate * f_factor, 0.0)
            remaining = st.remaining - send * dt

            newly_done = st.active & (remaining <= 0)
            # completion: propagation + residual queue wait on the path
            qw_now = jnp.where(links_ok, q[lidx] / ar.link_cap[lidx],
                               0.0).sum(-1)
            prop = ar.path_prop[jnp.maximum(pf, 0)].astype(jnp.float32)
            arr_us = ar.f_arr_us[jnp.maximum(st.slot_flow, 0)]
            fct = ((t + 1) * dt - arr_us + prop
                   + 0.5 * (st.extra_wait + qw_now))
            st = engine.retire(dataclasses.replace(st, remaining=remaining),
                               newly_done, fct)

        # 7) RedTE periodic split-ratio re-optimization (shared tick)
        with jax.named_scope("step.redte"):
            st = redte_tick(t, st, ar, cfg)

        # 8) the work counters (not part of the answers)
        with jax.named_scope("step.counters"):
            st = engine.count_live(st, live)

        # 9) debug-mode physics invariants (Python gate: the unchecked
        # trace carries no extra ops)
        if checks_on:
            with jax.named_scope("step.checks"):
                st = sanitize.step_check(t, st, ar, cfg)

        return st, None

    return step


def run_impl(arrs: SimArrays, state: SimState, cfg: SimConfig) -> SimState:
    """Unjitted scan body — the sweep engine vmaps/shard_maps this and
    wraps its own single jit around the whole batch."""
    step = make_step(arrs, cfg)
    final, _ = jax.lax.scan(step, state, jnp.arange(cfg.num_steps))
    return final


# jitted entry point for single experiments (the sweep engine jits its
# own vmap of run_impl instead, one trace per cell group)
_run_jit = jax.jit(run_impl, static_argnames=("cfg",))


def run(arrs: SimArrays, state: SimState, cfg: SimConfig) -> SimState:
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
