"""Signal-plane fidelity contracts: routed congestion signals obey
propagation delay, the control plane re-installs C_path on its period
(and only then), failover re-initializes CC state, and the history ring
rejects configurations it cannot represent."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pathq import calc_path_quality
from repro.netsim import fluid, paths, scenarios, topo
from repro.netsim.experiment import ExpSpec, build_experiment
from repro.netsim.fluid import SimConfig


# ------------------------------------------- propagation-delayed visibility
def test_remote_congestion_invisible_before_one_way_prop():
    """A remote hop's congestion score recorded at step t0 must not reach
    the ingress decision before t0 + its backward propagation delay."""
    d = 50
    hist_c = np.zeros((2, fluid.HIST), np.int32)
    t0 = 1000
    # reprolint: ignore[RNG001] host-side setup writes one in-range slot
    hist_c[1, t0] = 200                     # remote hop flags congestion
    pl = jnp.asarray([[0, 1, -1]])          # one path: local hop, remote hop
    sd = jnp.asarray([[0, d, 0]])           # remote signal is d steps away
    for t, expect in [(t0, 0), (t0 + d - 1, 0), (t0 + d, 200),
                      (t0 + d + 1, 0)]:     # (one-step pulse moves past)
        v = fluid.path_cong_view(jnp.asarray(hist_c), pl, sd, t)
        assert int(v[0]) == expect, (t, expect)


def test_local_hop_reads_current_score_and_max_over_hops():
    hist_c = np.zeros((2, fluid.HIST), np.int32)
    hist_c[0, 7] = 40                       # local hop, current step
    hist_c[1, 7] = 90                       # remote hop, same step
    pl = jnp.asarray([[0, 1]])
    v_now = fluid.path_cong_view(jnp.asarray(hist_c), pl,
                                 jnp.asarray([[0, 0]]), 7)
    assert int(v_now[0]) == 90              # zero delay: max over both hops
    v_dly = fluid.path_cong_view(jnp.asarray(hist_c), pl,
                                 jnp.asarray([[0, 30]]), 7)
    assert int(v_dly[0]) == 40              # remote entry not yet arrived


def test_build_precomputes_cumulative_upstream_delays():
    """path_sig_delay[h] = scaled sum of upstream hop propagation; hop 0
    (the ingress's own egress port) is always 0."""
    t = topo.segmented_parallel([100], [120_000], segs=3)
    table = paths.build_path_table(t, [(0, t.num_nodes - 1)])
    fluid.attach_link_caps(table, t)
    from repro.traffic.gen import FlowSet
    flows = FlowSet(arrival_us=np.array([0], np.int64),
                    size_bytes=np.array([1e6]),
                    pair_id=np.array([0], np.int32),
                    flow_id=np.array([1], np.uint32))
    for scale in (1.0, 2.0):
        cfg = SimConfig(dt_us=200, sig_delay_scale=scale)
        arr, _ = fluid.build(table, flows, cfg)
        sig = np.asarray(arr.path_sig_delay[0])
        seg = 40_000  # 120 ms split over 3 segments
        want = (scale * np.array([0, seg, 2 * seg, 3 * seg]) // 200)
        assert (sig[:4] == want).all(), (scale, sig)


def test_build_rejects_history_ring_overflow():
    """Satellite: HIST carries a "must exceed max RTT" invariant — build()
    must enforce it instead of silently wrapping the ring."""
    t = topo.parallel_paths(caps=(100,), delays_us=(250_000,))
    table = paths.build_path_table(t, [(0, 2)])
    fluid.attach_link_caps(table, t)
    from repro.traffic.gen import FlowSet
    flows = FlowSet(arrival_us=np.array([0], np.int64),
                    size_bytes=np.array([1e6]),
                    pair_id=np.array([0], np.int32),
                    flow_id=np.array([1], np.uint32))
    with pytest.raises(ValueError, match="HIST"):        # rtt overflow
        fluid.build(table, flows, SimConfig(dt_us=10))
    with pytest.raises(ValueError, match="sig_delay_scale"):  # offset overflow
        fluid.build(table, flows, SimConfig(dt_us=200, sig_delay_scale=40.0))
    fluid.build(table, flows, SimConfig(dt_us=200))      # sane cfg passes


# --------------------------------------------------- control-plane refresh
def _degrade_world(ctrl_period_us, horizon_us, deg_at_us=10_000, factor=0.25):
    spec = ExpSpec(topology="parallel:n=2,cap=100", load=0.3, policy="ecmp",
                   duration_us=60_000, seed=3)
    _, table, flows, cfg = build_experiment(spec)
    first = int(table.path_first[0])
    cfg = dataclasses.replace(cfg, horizon_us=horizon_us,
                              ctrl_period_us=ctrl_period_us,
                              degrade_sched=((first, deg_at_us, factor),))
    arrs, st = fluid.build(table, flows, cfg)
    return table, cfg, arrs, st, first


def test_degrade_changes_c_path_after_and_only_after_refresh():
    """deg at 10 ms, refresh period 20 ms: the installed score must be
    unchanged at 16 ms (last refresh predates the degrade) and repriced
    by 24 ms (first refresh after it)."""
    table, cfg, arrs, st, _ = _degrade_world(ctrl_period_us=20_000,
                                             horizon_us=16_000)
    initial = np.asarray(st.c_path).copy()
    before = fluid.run(arrs, st, cfg)
    assert np.array_equal(np.asarray(before.c_path), initial)

    table, cfg, arrs, st, _ = _degrade_world(ctrl_period_us=20_000,
                                             horizon_us=24_000)
    after = fluid.run(arrs, st, cfg)
    got = np.asarray(after.c_path)
    assert got[0] > initial[0]          # degraded path repriced upward
    assert got[1] == initial[1]         # untouched path unchanged


def test_ctrl_period_zero_freezes_build_time_table():
    table, cfg, arrs, st, _ = _degrade_world(ctrl_period_us=0,
                                             horizon_us=40_000)
    final = fluid.run(arrs, st, cfg)
    assert np.array_equal(np.asarray(final.c_path), np.asarray(st.c_path))


def test_ctrl_refresh_matches_pathq_on_effective_caps():
    """The refresh output is exactly core.pathq over per-path bottlenecks
    of the effective (degraded) link capacities."""
    table, cfg, arrs, st, first = _degrade_world(ctrl_period_us=20_000,
                                                 horizon_us=24_000)
    t_after = cfg.num_steps - 1
    got = fluid.ctrl_refresh(t_after, st, arrs, cfg)
    # independent numpy reconstruction: degrade the link, min over hops
    eff_link = np.asarray(arrs.link_cap_gbps, np.float64)
    eff_link[first] *= 0.25
    pl = np.asarray(table.path_links)
    eff_path = np.where(pl >= 0, eff_link[np.maximum(pl, 0)],
                        np.inf).min(-1)
    want = calc_path_quality(jnp.asarray(table.path_prop_us),
                             jnp.asarray(eff_path.astype(np.int32)),
                             arrs.tables.cap_thresh, cfg.pathq)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- failover CC reset
def test_reroute_dead_reinitializes_cc_state():
    """Satellite regression: a failed-over flow must restart CC on the new
    path — fresh target, fresh MD timer, the NEW path's standing queue —
    not blast at line rate against the dead path's AIMD remnants."""
    spec = ExpSpec(topology="parallel:n=2,cap=100", load=0.3, policy="lcmp",
                   duration_us=60_000, seed=1)
    scen, table, flows, cfg = build_experiment(spec)
    arrs, st = fluid.build(table, flows, cfg)
    t = 500
    # the main pair's two candidate paths (global indices)
    main = table.pair_index()[(0, 3)]
    dead_p, live_p = (int(x) for x in table.pair_cand[main][:2])
    dead_first = int(table.path_first[dead_p])
    surv_first = int(table.path_first[live_p])
    alive_q = 2e6                                # standing queue, live path
    st = dataclasses.replace(
        st,
        slot_flow=st.slot_flow.at[0].set(0),
        slot_path=st.slot_path.at[0].set(dead_p),
        flow_path=st.flow_path.at[0].set(dead_p),
        active=st.active.at[0].set(True),
        remaining=st.remaining.at[0].set(1e8),
        rate=st.rate.at[0].set(1.0),
        cc_target=st.cc_target.at[0].set(1.0),   # deep AIMD backoff remnants
        last_dec=st.last_dec.at[0].set(t - 1),
        cc_alpha=st.cc_alpha.at[0].set(0.5),
        extra_wait=st.extra_wait.at[0].set(1234.5),
        q_bytes=st.q_bytes.at[surv_first].set(alive_q),
        link_alive=st.link_alive.at[dead_first].set(False))
    out = fluid._reroute_dead(t, st, arrs, cfg)
    assert int(out.slot_path[0]) == live_p       # moved to the live path
    assert int(out.flow_path[0]) == live_p       # ... and its answer too
    line = float(arrs.path_cap[live_p])
    assert float(out.rate[0]) == line
    assert float(out.cc_target[0]) == line       # target re-initialized
    assert int(out.last_dec[0]) == -(1 << 20)    # MD timer reset
    assert float(out.cc_alpha[0]) == 0.0
    want_qw = alive_q / float(arrs.link_cap[surv_first])
    assert np.isclose(float(out.extra_wait[0]), want_qw)  # new path's queue


# --------------------------------------------------- end-to-end staleness
def test_staleness_hurts_reactive_policies_ecmp_flat():
    """Acceptance: a stale routing signal worsens LCMP's tail on the
    staleness scenario (remote-span degrade, control plane frozen so only
    the signal-plane knob acts), while ECMP — which never reads the
    congestion signal — is bit-for-bit flat. The hurt is asserted on the
    seed-averaged p99 for each stale point against the fresh view; past
    the queue-buildup timescale extra staleness saturates rather than
    compounding, so no strict ordering *between* stale points is claimed.
    The grid runs batched through the sweep engine (sig_delay_scale is a
    static axis: one trace per value; policy x seed stay dynamic)."""
    from repro.netsim.sweep import run_sweep
    seeds, sdss = (1, 2, 3), (0.0, 2.0, 6.0)
    specs = [ExpSpec(topology="staleness:deg_ms=60", load=0.4, policy=pol,
                     duration_us=300_000, seed=seed, sig_delay_scale=sds,
                     ctrl_period_us=0)
             for sds in sdss for seed in seeds for pol in ("lcmp", "ecmp")]
    rep = run_sweep(specs)
    res = {(r.spec.sig_delay_scale, r.spec.seed, r.spec.policy): r
           for r in rep.results}
    p99 = {sds: np.mean([res[(sds, seed, "lcmp")].stats.p99
                         for seed in seeds]) for sds in sdss}
    assert p99[0.0] < p99[2.0], p99
    assert p99[0.0] < p99[6.0], p99
    for seed in seeds:
        fct = [res[(sds, seed, "ecmp")].final.fct_us for sds in sdss]
        assert np.array_equal(fct[0], fct[1]) and np.array_equal(fct[0], fct[2])


def test_staleness_scenario_targets_a_remote_span():
    """The degraded link must not be a first hop of any candidate path —
    otherwise the ablation is vacuous (zero signal delay)."""
    scen = scenarios.get("staleness")
    table = paths.build_path_table(scen.topology,
                                   paths.all_pairs(scen.topology))
    deg = scen.degrade_sched[0][0]
    main = table.pair_index()[scen.main_pair]
    cands = table.pair_cand[main][: table.pair_ncand[main]]
    assert deg not in set(table.path_first[cands].tolist())
    assert any(deg in table.path_links[p] for p in cands)
