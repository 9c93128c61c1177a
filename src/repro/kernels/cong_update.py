"""Pallas TPU kernel: fleet-wide congestion-register update (paper §3.3).

One monitor tick for *many* ports at once (a pod-level telemetry sweep
updates thousands of per-route registers): Eq. 3 shift-EWMA, qThresh /
trend-threshold quantization, duration counter, and the fused C_cong —
all int32 adds/shifts/compares on the VPU.

Layout: ports on the lane axis (blocks of 128); the threshold vectors
ride along as (16, 128) blocks (per-port trend thresholds are genuinely
per-lane; the shared qThresh/levelScore vectors are broadcast to lanes by
the wrapper — 8 KiB per block, negligible VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.cong import CongParams, CongState
from repro.core.tables import SCORE_MAX, SwitchTables

BP = 128          # ports per block
NLEV = 16         # quantization levels (matches tables default)


def _cong_kernel(qcur_ref, qprev_ref, trend_ref, dur_ref,
                 qnew_ref, qth_ref, tth_ref, lsc_ref, hw_ref,
                 o_qcur_ref, o_qprev_ref, o_trend_ref, o_dur_ref, o_cc_ref, *,
                 w_ql: int, w_tl: int, w_dp: int, ewma_k: int,
                 dur_shift: int, s_cong: int):
    q_old = qcur_ref[0, :]
    trend_old = trend_ref[0, :]
    dur_old = dur_ref[0, :]
    q = qnew_ref[0, :]

    # Eq. (3): shift-based EWMA of queue deltas
    delta = q - q_old
    trend = trend_old - (trend_old >> ewma_k) + (delta >> ewma_k)

    # quantize queue level: count thresholds <= q  (15 vector compares)
    q_level = jnp.zeros_like(q)
    t_level = jnp.zeros_like(q)
    for i in range(NLEV - 1):
        q_level += (qth_ref[i, :] <= q).astype(jnp.int32)
        t_level += (tth_ref[i, :] <= trend).astype(jnp.int32)

    hw = hw_ref[0, :]
    dur = jnp.where(q_level >= hw, dur_old + 1, dur_old >> 1)

    # level -> score via one-hot gather over the 16 levelScore rows
    q_score = jnp.zeros_like(q)
    t_score = jnp.zeros_like(q)
    for i in range(NLEV):
        s = lsc_ref[i, :]
        q_score = jnp.where(q_level == i, s, q_score)
        t_score = jnp.where(t_level == i, s, t_score)
    t_score = jnp.where(trend > 0, t_score, 0)
    d_score = jnp.minimum(dur >> dur_shift, SCORE_MAX)

    fused = w_ql * q_score + w_tl * t_score + w_dp * d_score
    c_cong = jnp.minimum(fused >> s_cong, SCORE_MAX)

    o_qcur_ref[0, :] = q
    o_qprev_ref[0, :] = q_old
    o_trend_ref[0, :] = trend
    o_dur_ref[0, :] = dur
    o_cc_ref[0, :] = c_cong


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def cong_update(state: CongState, queue_cells: jnp.ndarray, now_us,
                tables: SwitchTables, params: CongParams = CongParams(),
                *, interpret: bool):
    """Fleet monitor tick. state fields (N,); queue_cells (N,) int32 cells.
    Returns (new CongState, c_cong (N,) int32)."""
    n = state.queue_cur.shape[0]
    n_pad = (n + BP - 1) // BP * BP

    def pad1(x):
        return jnp.pad(x.astype(jnp.int32), (0, n_pad - n)).reshape(1, n_pad)

    # per-port trend thresholds -> (15, N); shared vectors broadcast to lanes
    tth = jnp.pad(tables.trend_thresh.astype(jnp.int32).T,
                  ((0, 1), (0, n_pad - n)))                     # (16, n_pad)
    qth = jnp.broadcast_to(
        jnp.pad(tables.q_thresh.astype(jnp.int32), (0, 1))[:, None],
        (NLEV, n_pad))
    lsc = jnp.broadcast_to(tables.level_score.astype(jnp.int32)[:, None],
                           (NLEV, n_pad))
    hw = jnp.broadcast_to(tables.high_water_level.astype(jnp.int32),
                          (1, n_pad))

    grid = (n_pad // BP,)
    row = pl.BlockSpec((1, BP), lambda i: (0, i), memory_space=pltpu.VMEM)
    tbl = pl.BlockSpec((NLEV, BP), lambda i: (0, i), memory_space=pltpu.VMEM)
    kern = functools.partial(
        _cong_kernel, w_ql=params.w_ql, w_tl=params.w_tl, w_dp=params.w_dp,
        ewma_k=params.ewma_k, dur_shift=params.dur_shift, s_cong=params.s_cong)
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[row, row, row, row, row, tbl, tbl, tbl, row],
        out_specs=[row] * 5,
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.int32)] * 5,
        interpret=interpret,
        name="cong_update",
    )(pad1(state.queue_cur), pad1(state.queue_prev), pad1(state.trend),
      pad1(state.dur_cnt), pad1(queue_cells), qth, tth, lsc, hw)

    qcur, qprev, trend, dur, cc = [o[0, :n] for o in outs]
    new_state = CongState(
        queue_cur=qcur, queue_prev=qprev, trend=trend, dur_cnt=dur,
        last_sample=jnp.broadcast_to(jnp.asarray(now_us, jnp.int32), (n,)))
    return new_state, cc
