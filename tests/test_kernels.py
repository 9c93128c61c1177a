"""Per-kernel allclose validation against the pure-jnp oracles (ref.py),
swept over shapes and dtypes, in Pallas interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cong import CongParams, CongState
from repro.core.select import SelectParams
from repro.core.tables import bootstrap_tables
from repro.kernels import ops, ref


# ---------------------------------------------------------------- lcmp_decide
@pytest.mark.parametrize("F", [1, 7, 128, 300, 1024])
@pytest.mark.parametrize("P", [2, 3, 6, 8])
def test_lcmp_decide_matches_ref_shapes(F, P):
    k = jax.random.key(F * 17 + P)
    k1, k2, k3, k4 = jax.random.split(k, 4)
    fids = jax.random.randint(k1, (F,), 0, 1 << 30).astype(jnp.uint32)
    c_path = jax.random.randint(k2, (F, P), 0, 256).astype(jnp.int32)
    c_cong = jax.random.randint(k3, (F, P), 0, 256).astype(jnp.int32)
    valid = jax.random.bernoulli(k4, 0.8, (F, P))
    got = ops.lcmp_decide(fids, c_path, c_cong, valid)
    want = ref.lcmp_decide_ref(fids, c_path, c_cong, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", range(4))
def test_lcmp_decide_matches_ref_param_sweep(seed):
    params = [SelectParams(alpha=1, beta=1), SelectParams(alpha=1, beta=3),
              SelectParams(alpha=3, beta=1, cong_fallback=100),
              SelectParams(alpha=2, beta=2, keep_num=3)][seed]
    k = jax.random.key(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    F, P = 256, 6
    fids = jax.random.randint(k1, (F,), 0, 1 << 30).astype(jnp.uint32)
    c_path = jax.random.randint(k2, (F, P), 0, 256).astype(jnp.int32)
    c_cong = jax.random.randint(k3, (F, P), 0, 256).astype(jnp.int32)
    valid = jnp.ones((F, P), bool)
    got = ops.lcmp_decide(fids, c_path, c_cong, valid, params)
    want = ref.lcmp_decide_ref(fids, c_path, c_cong, valid, params)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lcmp_decide_rejects_more_than_8_candidates():
    F, P = 16, 9
    z = jnp.zeros((F, P), jnp.int32)
    with pytest.raises(ValueError, match="at most 8 candidates"):
        ops.lcmp_decide(jnp.arange(F, dtype=jnp.uint32), z, z,
                        jnp.ones((F, P), bool))


def test_lcmp_decide_all_invalid_rows():
    F, P = 130, 4
    fids = jnp.arange(F, dtype=jnp.uint32)
    z = jnp.zeros((F, P), jnp.int32)
    valid = jnp.zeros((F, P), bool).at[0].set(True)
    got = ops.lcmp_decide(fids, z, z, valid)
    want = ref.lcmp_decide_ref(fids, z, z, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got)[1:] == -1).all()


# ---------------------------------------------------------------- cong_update
@pytest.mark.parametrize("n_ports", [1, 5, 128, 400])
def test_cong_update_matches_ref(n_ports):
    tb = bootstrap_tables([100] * n_ports, buffer_bytes=6 * 10**9)
    st = CongState.init(n_ports)
    k = jax.random.key(n_ports)
    for step in range(4):
        k, sub = jax.random.split(k)
        q = jax.random.randint(sub, (n_ports,), 0, 5 * 10**6).astype(jnp.int32)
        st_k, cc_k = ops.cong_update(st, q, step * 100, tb)
        st_r, cc_r = ref.cong_update_ref(st, q, step * 100, tb)
        np.testing.assert_array_equal(np.asarray(cc_k), np.asarray(cc_r))
        for f in ("queue_cur", "queue_prev", "trend", "dur_cnt"):
            np.testing.assert_array_equal(np.asarray(getattr(st_k, f)),
                                          np.asarray(getattr(st_r, f)), err_msg=f)
        st = st_r


def test_cong_update_param_sweep():
    tb = bootstrap_tables([25, 100, 400], buffer_bytes=10**9)
    p = CongParams(w_ql=1, w_tl=2, w_dp=1, ewma_k=2, dur_shift=1)
    st = CongState.init(3)
    q = jnp.array([10**5, 5 * 10**5, 9 * 10**5], jnp.int32)
    st_k, cc_k = ops.cong_update(st, q, 100, tb, p)
    st_r, cc_r = ref.cong_update_ref(st, q, 100, tb, p)
    np.testing.assert_array_equal(np.asarray(cc_k), np.asarray(cc_r))


# ------------------------------------------------------------------- qsr_int8
# 130 scale groups: more than one grid step, padded to a multiple of 128
@pytest.mark.parametrize("n", [1024, 4096, 64 * 1024, 130 * 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qsr_int8_matches_ref(n, dtype):
    k1, k2 = jax.random.split(jax.random.key(n))
    x = (jax.random.normal(k1, (n,), jnp.float32) * 3).astype(dtype).astype(jnp.float32)
    bits = jax.random.bits(k2, (n,), jnp.uint32)
    qk, sk = ops.qsr_int8(x, bits)
    qr, sr = ref.qsr_int8_ref(x, bits)
    # float contract: XLA may fuse x*(127/amax) differently between the two
    # programs, so floor() ties can flip by one step on ~1e-5 of elements;
    # everything else must match exactly.
    dq = np.abs(np.asarray(qk, np.int32) - np.asarray(qr, np.int32))
    assert dq.max() <= 1
    assert (dq != 0).mean() < 1e-4
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    # roundtrip error bounded by one quantization step per element
    xr = ops.qsr_dequant(qk, sk)
    step = np.repeat(np.asarray(sr), 1024)
    assert (np.abs(np.asarray(xr - x)) <= step + 1e-7).all()


def test_qsr_int8_zero_block_and_unbiasedness():
    n = 2048
    x = jnp.zeros((n,), jnp.float32).at[1024:].set(0.3)
    reps = 64
    acc = np.zeros(n)
    for s in range(reps):
        bits = jax.random.bits(jax.random.key(s), (n,), jnp.uint32)
        q, sc = ops.qsr_int8(x, bits)
        acc += np.asarray(ops.qsr_dequant(q, sc))
    acc /= reps
    assert (acc[:1024] == 0).all()                       # zero block stays zero
    np.testing.assert_allclose(acc[1024:], 0.3, atol=2e-3)  # SR is unbiased
