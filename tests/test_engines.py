"""Multi-engine contracts: the packet engine hits closed-form FCT when
uncongested, agrees with the fluid engine within stated tolerance bands
on the quick testbed, and both engines drive the *same* routing path
through the degenerate candidate cases (one valid slot, all-invalid,
weighted-hash bounds). Plus the Engine protocol/registry itself."""
import dataclasses

import numpy as np
import pytest

from repro.netsim import engine as enginemod
from repro.netsim import fluid, packet, paths, topo
from repro.netsim.engine import Engine, SimConfig, attach_link_caps
from repro.netsim.experiment import ExpSpec, build_experiment, run_experiment
from repro.traffic.gen import FlowSet


# ------------------------------------------------------------ registry
def test_engine_registry_and_protocol():
    for name in enginemod.ENGINES:
        eng = enginemod.get_engine(name)
        assert eng.name == name
        assert isinstance(eng, Engine)          # build/run_impl/run present
    with pytest.raises(ValueError, match="fluid"):
        enginemod.get_engine("ns3")             # error names the valid set


def test_spec_engine_threads_into_config():
    from repro.netsim.experiment import spec_to_cfg
    from repro.netsim import scenarios
    scen = scenarios.get("testbed8")
    assert spec_to_cfg(ExpSpec(engine="packet"), scen).engine == "packet"
    assert spec_to_cfg(ExpSpec(), scen).engine == "fluid"


# --------------------------------------------- closed-form single flow
def _single_flow_world(size, cap=100, delay=5000, arrival=1000):
    t = topo.parallel_paths(caps=(cap,), delays_us=(delay,))
    table = paths.build_path_table(t, [(0, 2)])
    attach_link_caps(table, t)
    flows = FlowSet(arrival_us=np.array([arrival], np.int64),
                    size_bytes=np.array([float(size)]),
                    pair_id=np.array([0], np.int32),
                    flow_id=np.array([42], np.uint32))
    return table, flows


@pytest.mark.parametrize("policy", ["lcmp", "ecmp"])
@pytest.mark.parametrize("size", [5e6, 1e5])
def test_packet_single_flow_matches_closed_form(policy, size):
    """A flow alone in the network: the packet engine's measured FCT must
    equal ``prop + size / bottleneck_cap`` within one slot (slot
    quantization is the engine's only discretization error here — pacing
    injects whole MTU packets at line rate and the idle path cuts
    through within the slot)."""
    table, flows = _single_flow_world(size)
    cfg = SimConfig(engine="packet", policy=policy, horizon_us=200_000,
                    cap_scale=1.0)
    arrs, st = packet.build(table, flows, cfg)
    final = packet.run(arrs, st, cfg)
    assert bool(final.done[0])
    ideal = 6000.0 + size / (100 * 125.0)   # prop(5ms+1ms tail) + serialize
    got = float(final.fct_us[0])
    assert abs(got - ideal) <= cfg.dt_us + 1e-3, (got, ideal)
    # lossless delivery: every byte of the flow arrived, exactly once —
    # all of them served by its path's last link
    path = int(final.flow_path[0])
    last = int(table.path_links[path][int(table.path_len[path]) - 1])
    assert abs(float(final.serv_bytes[last]) - size) < 1.0


def test_packet_queues_lossless_and_buffer_bounded():
    """Silent 99% degradation of a single-route world with the PFC
    thresholds tightened (the configurable-knob path): XOFF must engage
    on the degraded link, the queue must stay inside the (scaled) buffer
    at every recorded step — pause plus the space bound, never drops —
    and in-flight bytes must remain non-negative."""
    spec = ExpSpec(topology="parallel:n=1,cap=100", load=0.5, policy="ecmp",
                   engine="packet", duration_us=100_000, seed=3)
    _, table, flows, cfg = build_experiment(spec)
    first = int(table.path_first[0])
    cfg = dataclasses.replace(cfg, degrade_sched=((first, 20_000, 0.01),),
                              pfc_xoff_frac=0.02, pfc_xon_frac=0.01)
    arrs, st = packet.build(table, flows, cfg)
    final = packet.run(arrs, st, cfg)
    buf = cfg.buffer_bytes * cfg.cap_scale
    assert float(np.asarray(final.hist_q).max()) <= buf + 1e-3
    assert float(np.asarray(final.fq).min()) >= -1e-3
    # the degraded link's pause state engaged at some point in the run...
    # reprolint: ignore[RNG001] link-axis index over the whole ring
    assert np.asarray(final.hist_pause)[first].any()
    # ...and the queue peak stayed near the XOFF line, far below the
    # buffer (pause is doing the limiting, not the space clamp)
    # reprolint: ignore[RNG001] link-axis index over the whole ring
    peak = float(np.asarray(final.hist_q)[first].max())
    assert peak < 0.5 * buf


# ------------------------------------------------- cross-engine parity
def test_engines_parity_quick_testbed8():
    """Stated tolerance bands on the quick 8-DC testbed at 30% load:
    oblivious policies (placement-dominated FCT) agree on p50 within
    10%; the congestion-reactive lcmp — where the engines' queue models
    legitimately differ (analytic wait estimates vs experienced queueing)
    — within a factor of 2. The paper's headline ordering (LCMP below
    ECMP on median AND tail) must hold under both backends."""
    st = {}
    for pol in ("lcmp", "ecmp"):
        for eng in ("fluid", "packet"):
            stats, _, _ = run_experiment(ExpSpec(
                topology="testbed8", load=0.3, policy=pol, engine=eng,
                duration_us=300_000, seed=1))
            assert stats.completed / stats.offered > 0.95
            st[(pol, eng)] = stats
    f, p = st[("ecmp", "fluid")], st[("ecmp", "packet")]
    assert abs(p.p50 - f.p50) / f.p50 < 0.10, (f.p50, p.p50)
    f, p = st[("lcmp", "fluid")], st[("lcmp", "packet")]
    assert 0.5 < p.p50 / f.p50 < 2.0, (f.p50, p.p50)
    for eng in ("fluid", "packet"):
        assert st[("lcmp", eng)].p50 < st[("ecmp", eng)].p50, eng
        assert st[("lcmp", eng)].p99 < st[("ecmp", eng)].p99, eng


# ------------------------------- degenerate candidates, both engines
def _burst_world(topology, n_flows=64, size=2e4):
    """A same-slot burst (the herd case) against a named scenario world:
    every decision is made at t=0 on identical all-zero congestion
    state, so the two engines' shared routing path must produce
    *identical* placements."""
    from repro.netsim import scenarios
    scen = scenarios.get(topology)
    t = scen.topology
    pair_list = paths.all_pairs(t)
    table = paths.build_path_table(t, pair_list)
    attach_link_caps(table, t)
    pidx = table.pair_index()[scen.main_pair]
    rng = np.random.default_rng(0)
    flows = FlowSet(
        arrival_us=np.zeros(n_flows, np.int64),
        size_bytes=np.full(n_flows, float(size)),
        pair_id=np.full(n_flows, pidx, np.int32),
        flow_id=rng.integers(1, 1 << 32, n_flows, dtype=np.uint32))
    return table, flows, pidx


def _both_engines(table, flows, **cfg_kw):
    out = {}
    for eng_name in ("fluid", "packet"):
        eng = enginemod.get_engine(eng_name)
        cfg = SimConfig(engine=eng_name, horizon_us=100_000, **cfg_kw)
        arrs, st = eng.build(table, flows, cfg)
        out[eng_name] = (np.asarray(eng.run(arrs, st, cfg).flow_path), arrs)
    return out


def test_single_valid_candidate_identical():
    """One candidate slot: every flow lands on it, in both engines."""
    table, flows, _ = _burst_world("parallel:n=1")
    res = _both_engines(table, flows, policy="lcmp")
    for fp, _ in res.values():
        assert (fp == fp[0]).all() and fp[0] >= 0
    assert np.array_equal(res["fluid"][0], res["packet"][0])


def test_all_candidates_invalid_identical():
    """Every candidate dead at arrival: select reports -1, no flow ever
    activates or completes — identically in both engines."""
    table, flows, _ = _burst_world("parallel:n=2")
    firsts = sorted({int(f) for f in table.path_first})
    res = {}
    for eng_name in ("fluid", "packet"):
        eng = enginemod.get_engine(eng_name)
        cfg = SimConfig(engine=eng_name, policy="lcmp", horizon_us=50_000,
                        fail_sched=tuple((li, 0) for li in firsts))
        arrs, st = eng.build(table, flows, cfg)
        final = eng.run(arrs, st, cfg)
        assert (np.asarray(final.flow_path) == -1).all()
        assert not np.asarray(final.done).any()
        res[eng_name] = np.asarray(final.flow_path)
    assert np.array_equal(res["fluid"], res["packet"])


def test_weighted_hash_bounds_identical():
    """lcmp_w (capacity-weighted stage-2 hash) on heterogeneous parallel
    routes: choices stay inside the pair's candidate set, the kept
    (lowest-cost) prefix is actually load-shared, and the same-slot herd
    places identically under both engines."""
    table, flows, pidx = _burst_world(
        "longhaul_mesh:routes=4,segs=1,caps=200+100+40,hi_ms=5")
    res = _both_engines(table, flows, policy="lcmp_w")
    cands = set(table.pair_cand[pidx][:table.pair_ncand[pidx]].tolist())
    for fp, _ in res.values():
        assert set(fp.tolist()) <= cands          # never out of bounds
        assert (fp >= 0).all()
        assert len(set(fp.tolist())) >= 2         # hash spreads the herd
    assert np.array_equal(res["fluid"][0], res["packet"][0])


def test_select_egress_weighted_hash_degenerate_slots():
    """Unit-level weighted-hash bounds: a single valid slot always wins
    regardless of weights; zero/extreme weights never index outside the
    kept prefix."""
    import jax.numpy as jnp
    from repro.core.select import select_egress
    fid = jnp.asarray((np.arange(128, dtype=np.uint64) * 2654435761)
                      % (1 << 32), jnp.uint32)
    c_path = jnp.asarray([10, 20, 30, 40], jnp.int32)
    c_cong = jnp.zeros(4, jnp.int32)
    only1 = jnp.asarray([False, True, False, False])
    w_extreme = jnp.asarray([1, 1 << 20, 0, 1], jnp.int32)
    choice, _ = select_egress(fid, c_path, c_cong, only1, weights=w_extreme)
    assert (np.asarray(choice) == 1).all()
    allv = jnp.ones(4, bool)
    choice, _ = select_egress(fid, c_path, c_cong, allv, weights=w_extreme)
    got = np.asarray(choice)
    assert ((got >= 0) & (got < 4)).all()
    # keep = ceil(4/2) = 2 -> only the two cheapest slots are eligible
    assert set(got.tolist()) <= {0, 1}


# ------------------------------------------------- sweep x engine axis
def test_sweep_engine_axis_groups_and_matches_sequential():
    """engine is a static (trace-level) sweep axis: a mixed fluid+packet
    grid forms one group per engine, and the batched packet results are
    bit-for-bit equal to the sequential per-cell loop."""
    from repro.netsim import sweep
    specs = [ExpSpec(topology="testbed8", load=0.3, policy=pol, engine=eng,
                     duration_us=60_000, seed=1)
             for eng in ("fluid", "packet") for pol in ("lcmp", "ecmp")]
    seq = sweep.run_sweep(specs, sequential=True)
    bat = sweep.run_sweep(specs)
    assert bat.num_groups == 2
    for a, b in zip(seq.results, bat.results):
        assert np.array_equal(a.final.fct_us, b.final.fct_us), b.spec
        assert np.array_equal(a.final.done, b.final.done), b.spec
        assert np.array_equal(a.util, b.util), b.spec


def test_packet_failover_completes_and_avoids_dead_link():
    """Packet-engine lazy failover: stranded queued bytes are returned to
    the source (go-back-N), flows re-hash onto live candidates and still
    complete; nothing re-lands on the dead link."""
    spec = ExpSpec(topology="testbed8_failover:fail_ms=60,link=12",
                   load=0.3, policy="lcmp", engine="packet",
                   duration_us=180_000, seed=5)
    stats, _, (_, table, flows, cfg, final) = run_experiment(spec)
    done = np.asarray(final.done)
    assert done.mean() > 0.95
    path_links = np.asarray(table.path_links)
    uses12 = (path_links == 12).any(-1)[np.maximum(np.asarray(final.flow_path),
                                                   0)]
    late = done & (flows.arrival_us > 60_000)
    assert not uses12[late].any()
