"""Compile-only checks for a described (not attached) TPU v5e.

The TPU compiler is installed on CPU hosts too, so the Pallas kernels
(``interpret=False``) and the engines' scans are compiled here for one
v5e chip. This catches what interpret mode cannot: block shapes the
tiling rules refuse, casts Mosaic lacks, programs that do not fit.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cong import CongParams, CongState
from repro.core.select import SelectParams
from repro.core.tables import bootstrap_tables
from repro.kernels.cong_update import cong_update
from repro.kernels.lcmp_decide import lcmp_decide
from repro.kernels.qsr_int8 import qsr_dequant, qsr_int8
from repro.netsim import engine as enginemod
from repro.netsim.experiment import ExpSpec, build_experiment


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shaped(one_chip):
    """Concrete (or ShapeDtypeStruct) pytree -> shapes on the v5e chip."""
    def to_shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), jnp.asarray(x).dtype, sharding=one_chip), tree)
    return to_shapes


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


N_QSR = 1 << 20                # elements of one quantized gradient slice


@pytest.mark.parametrize("kernel", ["qsr_int8", "qsr_dequant"])
def test_qsr_kernels_compile(kernel, shaped):
    if kernel == "qsr_int8":
        fn = lambda x, b: qsr_int8(x, b, interpret=False)  # noqa: E731
        args = (jnp.zeros(N_QSR, jnp.float32), jnp.zeros(N_QSR, jnp.uint32))
    else:
        fn = lambda q, s: qsr_dequant(q, s, interpret=False)  # noqa: E731
        args = (jnp.zeros(N_QSR, jnp.int8), jnp.zeros(N_QSR // 1024))
    assert "tpu_custom_call" in _compiled_text(fn, *shaped(args))


def test_lcmp_decide_compiles(shaped):
    F, P = 4096, 8
    args = (jnp.zeros(F, jnp.uint32), jnp.zeros((F, P), jnp.int32),
            jnp.zeros((F, P), jnp.int32), jnp.zeros((F, P), bool))
    fn = lambda *a: lcmp_decide(*a, SelectParams(), interpret=False)  # noqa: E731
    assert "tpu_custom_call" in _compiled_text(fn, *shaped(args))


def test_cong_update_compiles(shaped):
    n = 1024
    tables = bootstrap_tables([100] * n, buffer_bytes=6 * 10**9)

    def fn(state, queues, tables):
        return cong_update(state, queues, 0, tables, CongParams(),
                           interpret=False)
    args = (CongState.init(n), jnp.zeros(n, jnp.int32), tables)
    assert "tpu_custom_call" in _compiled_text(fn, *shaped(args))


@pytest.mark.parametrize("engine", ["fluid", "packet"])
def test_engine_scan_compiles(engine, shaped):
    spec = ExpSpec(topology="testbed8", engine=engine, duration_us=20_000)
    _, table, flows, cfg = build_experiment(spec)
    eng = enginemod.get_engine(engine)
    arrs, state = eng.build(table, flows, cfg)
    compiled = jax.jit(eng.run_impl, static_argnames=("cfg",)).lower(
        shaped(arrs), shaped(state), cfg).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30


# the benchmark's wan2000-24 cells (bench/configs, bench/traffic): ~41k
# flows over 1,000 steps at real link rates
WAN24 = dict(topology="wan2000:dcs=24,segs=2,chords=12,deg_ms=33,"
             "deg_factor=0.25", load=0.5, bg_load=0.15, pairs="main",
             cap_scale=1.0, duration_us=100_000, seed=2100004403)


@pytest.mark.parametrize("engine", ["fluid", "packet"])
def test_windowed_scan_compiles_at_benchmark_size(engine, shaped):
    """Both windowed scans at the benchmark's wan2000-24 size: F ~ 41k
    answers, a 1024-slot window, within one chip's memory."""
    _, table, flows, cfg = build_experiment(ExpSpec(engine=engine, **WAN24))
    eng = enginemod.get_engine(engine)
    arrs, state = eng.build(table, flows, cfg)
    assert state.flow_path.shape[0] > 40_000
    assert state.slot_flow.shape[0] == 1024
    compiled = jax.jit(eng.run_impl, static_argnames=("cfg",)).lower(
        shaped(arrs), shaped(state), cfg).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
