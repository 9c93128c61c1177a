"""Mid-flow re-decision plane contracts: the shared decision core must
leave every pinned-path policy bit-for-bit unchanged when the plane is
off, failover must apply each policy's *own* law, the packet engine's
flowlet detector must fire only after a genuine idle gap, and the
amp subflow split must aggregate back to parent flows exactly."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as bl
from repro.core.select import ecmp_select
from repro.netsim import fluid, metrics, packet, paths, sweep, topo
from repro.netsim.engine import (POLICY_CODES, REDECIDE_POLICIES, SimConfig,
                                 attach_link_caps)
from repro.netsim.experiment import ExpSpec, build_experiment, run_experiment
from repro.traffic.gen import FlowSet, generate


def _run_seen(mod, arrs, st, cfg):
    """``mod``'s scan from ``st``: its final state and, per flow, the
    most re-decisions it had (``route_nonce``) and the latest step it was
    routed onto path 1 (``route_step``), read at every step's end while
    the flow held a slot of the window (-1: never seen)."""
    step = mod.make_step(arrs, cfg)
    F = st.flow_path.shape[0]

    def body(carry, t):
        s, nonce, on1 = carry
        s, _ = step(s, t)
        at = jnp.where(s.slot_flow >= 0, s.slot_flow, F)
        nonce = nonce.at[at].max(s.route_nonce, mode="drop")
        on1 = on1.at[at].max(jnp.where(s.slot_path == 1, s.route_step, -1),
                             mode="drop")
        return (s, nonce, on1), None

    none = jnp.full((F,), -1, jnp.int32)
    (final, nonce, on1), _ = jax.jit(lambda s: jax.lax.scan(
        body, (s, none, none), jnp.arange(cfg.num_steps)))(st)
    return final, np.asarray(nonce), np.asarray(on1)


def _max_nonce(spec):
    _, table, flows, cfg = build_experiment(spec)
    arrs, st = fluid.build(table, flows, cfg)
    return int(_run_seen(fluid, arrs, st, cfg)[1].max())


# ------------------------------------------------ frozen policy registry
def test_policy_codes_pinned():
    """The name->code mapping is wire-format: SimArrays.policy_code values
    bake into sweep traces and stored results. Appending is fine;
    renumbering is a silent-corruption bug this pin catches."""
    assert POLICY_CODES == {
        "lcmp": 0, "lcmp_w": 1, "ecmp": 2, "ucmp": 3, "wcmp": 4,
        "redte": 5, "fatpaths": 6, "amp": 7, "lcmp_r": 8, "matchrdma": 9,
    }
    assert REDECIDE_POLICIES == ("fatpaths", "lcmp_r")


# ------------------------------------- plane off => bit-for-bit identical
_OFF_POLICIES = ("lcmp", "lcmp_w", "ecmp", "ucmp", "wcmp", "redte")


@pytest.mark.parametrize("topology", ["testbed8", "wan2000:dcs=6,segs=2"])
def test_knobs_are_inert_for_pinned_policies(topology):
    """Acceptance bar: with re-decision not applicable (policy outside
    REDECIDE_POLICIES), arming the knobs changes *nothing* — every
    existing policy stays bit-for-bit on the testbed and the WAN mesh.
    (``wants_redecide`` is a Python-level gate, so the armed run must
    trace the identical program.)"""
    for pol in _OFF_POLICIES:
        base = ExpSpec(topology=topology, load=0.3, policy=pol,
                       duration_us=60_000, seed=1)
        armed = dataclasses.replace(base, flowlet_gap_us=800,
                                    redecide_period_us=10_000)
        _, _, (_, _, _, _, fa) = run_experiment(base)
        _, _, (_, _, _, _, fb) = run_experiment(armed)
        assert np.array_equal(np.asarray(fa.fct_us), np.asarray(fb.fct_us)), pol
        assert np.array_equal(np.asarray(fa.flow_path),
                              np.asarray(fb.flow_path)), pol
        assert np.array_equal(np.asarray(fa.done), np.asarray(fb.done)), pol


@pytest.mark.parametrize("engine", ["fluid", "packet"])
def test_lcmp_r_knobs_off_is_lcmp_bit_for_bit(engine):
    """lcmp_r with both knobs at 0 is exactly lcmp on both engines — the
    ablation's control cell costs nothing and proves the refactor kept
    the arrival/decision path byte-identical."""
    kw = dict(topology="testbed8", load=0.3, duration_us=60_000, seed=1,
              engine=engine)
    _, _, (_, _, _, _, fa) = run_experiment(ExpSpec(policy="lcmp", **kw))
    _, _, (_, _, _, _, fb) = run_experiment(ExpSpec(policy="lcmp_r", **kw))
    assert np.array_equal(np.asarray(fa.fct_us), np.asarray(fb.fct_us))
    assert np.array_equal(np.asarray(fa.flow_path), np.asarray(fb.flow_path))
    assert np.array_equal(np.asarray(fa.done), np.asarray(fb.done))


def test_mixed_sweep_keeps_pinned_cells_exact():
    """A sweep mixing lcmp with an armed lcmp_r cell shares one trace, so
    the re-decision tick is compiled in — but the per-cell policy_code
    gate must keep the lcmp cell bit-identical to its solo run."""
    kw = dict(topology="testbed8", load=0.3, duration_us=60_000, seed=1,
              redecide_period_us=10_000)
    specs = [ExpSpec(policy="lcmp", **kw), ExpSpec(policy="lcmp_r", **kw)]
    bat = sweep.run_sweep(specs)
    assert bat.num_groups == 1          # same static key: one shared trace
    for i in range(2):
        _, _, (_, _, _, _, solo) = run_experiment(specs[i])
        cell = bat.results[i].final
        assert np.array_equal(np.asarray(cell.fct_us),
                              np.asarray(solo.fct_us)), specs[i].policy
        assert np.array_equal(np.asarray(cell.flow_path),
                              np.asarray(solo.flow_path)), specs[i].policy
        if specs[i].policy == "lcmp_r":
            # the armed cell's tick is live (nonce advances at epochs)
            assert _max_nonce(specs[i]) > 0


def test_sweep_with_new_policies_matches_sequential():
    """Batched == sequential, bit-for-bit, with the three new policies
    mixed into the dynamic-dispatch plane."""
    specs = [ExpSpec(topology="testbed8", load=0.3, policy=pol,
                     duration_us=60_000, seed=0)
             for pol in ("lcmp", "fatpaths", "ecmp")]
    seq = sweep.run_sweep(specs, sequential=True)
    bat = sweep.run_sweep(specs)
    for a, b in zip(seq.results, bat.results):
        assert np.array_equal(a.final.fct_us, b.final.fct_us), b.spec
        assert np.array_equal(a.final.flow_path, b.final.flow_path), b.spec
        assert np.array_equal(a.final.done, b.final.done), b.spec


# --------------------------------------- failover under each policy's law
def _hetero_failover(policy):
    t = topo.parallel_paths(caps=(100, 400, 40),
                            delays_us=(5000, 5000, 5000))
    table = paths.build_path_table(t, [(0, 4)])
    attach_link_caps(table, t)
    F = 300
    rng = np.random.default_rng(0)
    flows = FlowSet(arrival_us=np.zeros(F, np.int64),
                    size_bytes=np.full(F, 1e6),
                    pair_id=np.zeros(F, np.int32),
                    flow_id=rng.integers(1, 1 << 32, F, dtype=np.uint32))
    cfg = SimConfig(engine="fluid", policy=policy, horizon_us=60_000)
    arrs, st = fluid.build(table, flows, cfg)
    dead_p = 0
    st = dataclasses.replace(
        st, slot_flow=jnp.arange(F, dtype=jnp.int32),
        slot_path=jnp.full_like(st.slot_path, dead_p),
        flow_path=jnp.full_like(st.flow_path, dead_p),
        active=jnp.ones_like(st.active),
        remaining=jnp.full_like(st.remaining, 1e6),
        link_alive=st.link_alive.at[int(table.path_first[dead_p])].set(False))
    out = fluid._reroute_dead(500, st, arrs, cfg)
    return np.asarray(out.flow_path)[:F]


def test_wcmp_failover_uses_capacity_weights_not_ecmp():
    """Satellite regression: before the shared core, ``_reroute_dead``
    failed every policy over with LCMP's selector. wcmp must now re-hash
    capacity-weighted (skewed to the 400G survivor), ecmp uniformly —
    different placements on a heterogeneous-capacity pair."""
    wcmp, ecmp = _hetero_failover("wcmp"), _hetero_failover("ecmp")
    assert not np.array_equal(wcmp, ecmp)
    # survivors are path 1 (400G) and path 2 (40G); wcmp weights 10:1
    w_share = (wcmp == 1).mean()
    e_share = (ecmp == 1).mean()
    assert w_share > 0.75                      # ~10/11 capacity-weighted
    assert 0.35 < e_share < 0.65               # ~1/2 uniform
    # every flow left the dead path under both laws
    assert (wcmp != 0).all() and (ecmp != 0).all()


# ------------------------------------------------ fluid re-decision epoch
def test_fluid_lcmp_r_beats_stale_lcmp_tail():
    """The ablation's reason to exist: under a stale signal plane, pinned
    LCMP parks flows on a degraded haul for their whole lifetime; the
    periodic re-decision epoch lets them escape, so lcmp_r's p99 must
    not be worse. (Empirically ~35% better on this grid; the bound
    leaves slack for numeric drift, not for regression.)"""
    for seed in (1, 2):
        kw = dict(topology="staleness:deg_ms=60", load=0.4, engine="fluid",
                  duration_us=200_000, seed=seed, sig_delay_scale=4.0)
        lcmp, _, _ = run_experiment(ExpSpec(policy="lcmp", **kw))
        lr_spec = ExpSpec(policy="lcmp_r", redecide_period_us=10_000, **kw)
        lr, _, _ = run_experiment(lr_spec)
        assert _max_nonce(lr_spec) > 0                      # epoch fired
        assert lr.completion_rate >= lcmp.completion_rate
        assert lr.p99 <= lcmp.p99 * 1.05, (seed, lr.p99, lcmp.p99)


# -------------------------------------------- packet-engine flowlet gap
def _flowlet_world(n=12, size=2e5, cap=1):
    """World where a genuine idle gap is reachable: 1G parallel paths so
    the DCQCN saturation floor (~2.6% of line) paces flows well below
    one MTU per slot, every flow hash-pinned to path 0, and a *mild*
    mid-run degrade (x0.5) so the shared queue floors the rates but
    still drains while the flows are alive."""
    t = topo.parallel_paths(caps=(cap, cap), delays_us=(200, 200))
    table = paths.build_path_table(t, [(0, 3)])
    attach_link_caps(table, t)
    fids = np.arange(1, 4000, dtype=np.uint32)
    k = np.asarray(ecmp_select(jnp.asarray(fids),
                               jnp.ones((len(fids), 2), bool)))
    on0 = fids[k == 0][:n]
    flows = FlowSet(arrival_us=np.full(n, 1000, np.int64),
                    size_bytes=np.full(n, float(size)),
                    pair_id=np.zeros(n, np.int32),
                    flow_id=np.array(on0, np.uint32))
    return table, flows


def _flowlet_run(table, flows, gap_us, degrade=True):
    deg = ((int(table.path_first[0]), 5000, 0.5),) if degrade else ()
    cfg = SimConfig(engine="packet", policy="fatpaths",
                    horizon_us=1_000_000, flowlet_gap_us=gap_us,
                    ecn_kmin_bytes=2e4, degrade_sched=deg)
    arrs, st = packet.build(table, flows, cfg)
    return _run_seen(packet, arrs, st, cfg)


def test_packet_flowlet_fires_after_genuine_idle_gap():
    """Positive case: the mid-run degrade floors the co-located flows'
    rates below one MTU/slot; once the backlog drains, their paced
    injections leave multi-slot idle gaps, the detector fires, and the
    re-hash actually moves traffic onto the clean path — all of it only
    *after* the degrade hit."""
    table, flows = _flowlet_world()
    f, nonce, on1 = _flowlet_run(table, flows, gap_us=800)
    fp = np.asarray(f.flow_path)
    assert (nonce > 0).sum() >= len(nonce) // 2      # detector fired
    moved = fp == 1
    assert moved.any()                               # traffic re-balanced
    deg_step = 5000 // int(f.rtt_steps.dtype.type(200))  # 200us slots
    seen = on1 >= 0
    assert seen.any() and (on1[seen] > deg_step).all()
    assert np.asarray(f.done).all()


def test_packet_flowlet_needs_idle_not_just_time():
    """Negative cases: (a) an uncongested pair never drains below one
    in-flight packet-gap, so an armed detector must stay silent and the
    run must be bit-identical to gap=0; (b) on the degraded world a gap
    threshold far above the real idle runs must also never fire."""
    t = topo.parallel_paths(caps=(1, 1), delays_us=(200, 200))
    table = paths.build_path_table(t, [(0, 3)])
    attach_link_caps(table, t)
    flows = FlowSet(arrival_us=np.array([1000, 1000], np.int64),
                    size_bytes=np.array([2e5, 2e5]),
                    pair_id=np.zeros(2, np.int32),
                    flow_id=np.array([42, 99], np.uint32))
    armed, nonce, _ = _flowlet_run(table, flows, gap_us=800, degrade=False)
    off, _, _ = _flowlet_run(table, flows, gap_us=0, degrade=False)
    assert int(nonce.max()) == 0
    assert np.array_equal(np.asarray(armed.fct_us), np.asarray(off.fct_us))
    assert np.array_equal(np.asarray(armed.flow_path),
                          np.asarray(off.flow_path))
    # (b) same congested world as the positive case, threshold too high
    table, flows = _flowlet_world()
    _, nonce, _ = _flowlet_run(table, flows, gap_us=400_000)
    assert int(nonce.max()) == 0


# --------------------------------------------------- amp subflow plumbing
def test_amp_generator_split_invariants():
    from repro.netsim.experiment import build_world
    from repro.traffic import cdf as cdfmod
    _, table = build_world("testbed8")
    kw = dict(load=0.3, duration_us=60_000, pair_ids=[0], seed=3)
    base = generate(table, cdfmod.WORKLOADS["websearch"], **kw)
    split = generate(table, cdfmod.WORKLOADS["websearch"], n_subflows=3, **kw)
    n = len(base.arrival_us)
    assert base.subflow_of is None                # legacy sets untouched
    assert len(split.arrival_us) == 3 * n
    assert np.array_equal(split.subflow_of, np.repeat(np.arange(n), 3))
    # parent byte counts preserved exactly by the equal split
    np.testing.assert_allclose(
        np.add.reduceat(split.size_bytes, np.arange(0, 3 * n, 3)),
        base.size_bytes)
    assert np.array_equal(np.repeat(base.arrival_us, 3), split.arrival_us)
    assert np.array_equal(np.repeat(base.pair_id, 3), split.pair_id)
    # subflow hash keys: all nonzero, and siblings never collide (a
    # collision would silently collapse two subflows onto one ECMP draw)
    ids = split.flow_id.reshape(n, 3)
    assert (ids != 0).all()
    assert all(len(set(row)) == 3 for row in ids)


def test_amp_metrics_score_parent_at_last_subflow():
    from types import SimpleNamespace
    t = topo.parallel_paths(caps=(100,), delays_us=(1000,))
    table = paths.build_path_table(t, [(0, 2)])
    attach_link_caps(table, t)
    # two parents x 2 subflows: parent 0 complete (last lands at 900),
    # parent 1 has one straggler -> not done
    flows = FlowSet(arrival_us=np.zeros(4, np.int64),
                    size_bytes=np.array([500.0, 500.0, 300.0, 300.0]),
                    pair_id=np.zeros(4, np.int32),
                    flow_id=np.array([1, 2, 3, 4], np.uint32),
                    subflow_of=np.array([0, 0, 1, 1], np.int32))
    final = SimpleNamespace(done=np.array([True, True, True, False]),
                            fct_us=np.array([900.0, 400.0, 100.0, 0.0]))
    cfg = SimConfig(engine="fluid", policy="ecmp", horizon_us=10_000)
    stats = metrics.fct_stats(final, table, flows, cfg)
    assert stats.offered == 2 and stats.completed == 1
    ideal = (float(table.pair_ideal_prop[0])
             + 1000.0 / (float(table.pair_ideal_cap[0]) * 125.0
                         * cfg.cap_scale))
    np.testing.assert_allclose(stats.slowdown,
                               [max(900.0 / ideal, 1.0)])
    np.testing.assert_allclose(stats.sizes, [1000.0])


def test_amp_end_to_end_completes():
    """amp runs through the full stack (gen split -> per-subflow ECMP ->
    parent-level stats): offered counts parents, not subflows, and the
    quiet testbed completes everything."""
    stats, _, (_, _, flows, _, _) = run_experiment(
        ExpSpec(topology="testbed8", load=0.3, policy="amp", n_subflows=4,
                duration_us=60_000, seed=1))
    assert flows.subflow_of is not None
    assert stats.offered == int(flows.subflow_of.max()) + 1
    assert stats.completion_rate > 0.95


# ------------------------------------------------------- fatpaths layers
def test_fatpaths_prefers_min_stretch_layer_and_spills():
    F = 64
    fids = np.arange(1, F + 1, dtype=np.uint32)
    plen = jnp.asarray(np.tile([2, 2, 4, 4], (F, 1)), jnp.int32)
    valid = jnp.ones((F, 4), bool)
    cool = jnp.zeros((F, 4), jnp.float32)
    # uncongested: every choice stays in the min-hop layer {0, 1}
    k = np.asarray(bl.fatpaths(jnp.asarray(fids), plen, valid, cool))
    assert set(k) <= {0, 1} and len(set(k)) == 2     # layered ECMP spread
    # layer-0 congestion beyond the threshold: spill to the full set
    hot = jnp.asarray(np.tile([255.0, 255.0, 0.0, 0.0], (F, 1)),
                      jnp.float32)
    k = np.asarray(bl.fatpaths(jnp.asarray(fids), plen, valid, hot))
    assert {2, 3} & set(k)                           # long paths now used
    # invalid candidates are never chosen even when the layer is hot
    valid2 = jnp.asarray(np.tile([True, True, False, False], (F, 1)))
    k = np.asarray(bl.fatpaths(jnp.asarray(fids), plen, valid2, hot))
    assert set(k) <= {0, 1}
