"""Pallas TPU kernel: batched LCMP routing decisions (paper §3.4 on VPU).

The switch-ASIC decision pipeline (fuse costs -> sort m<=8 candidates ->
drop high-cost suffix -> hash inside kept set) is re-tiled for the TPU
vector unit:

- layout: candidates on the **sublane** axis (padded to 8), flows on the
  **lane** axis (blocks of 128) — a Batcher odd-even sorting network over
  8 sublane rows is 19 vectorized compare-exchanges, each a (1,128) int32
  min/max, i.e. the MXU-free VPU analogue of the ASIC's comparator tree.
- all arithmetic is int32/uint32 (adds, shifts, selects) exactly matching
  ``repro.core.select`` bit-for-bit.
- one kernel invocation decides 128 flows; the grid walks the flow axis.

VMEM budget per block: 4 inputs x (8,128) int32 + 1 flow row + out
= ~17 KiB — far under the ~16 MiB VMEM of a TPU core; the block shape is
chosen for lane alignment, not capacity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.select import SelectParams

P_PAD = 8          # candidate axis, padded (paper: m in [2,8])
BF = 128           # flows per block (lane width)
_COST_INVALID = 1 << 24
_SCORE_MAX = 255

# Batcher odd-even mergesort network for n=8 (19 comparators)
_NETWORK = [(0, 1), (2, 3), (4, 5), (6, 7),
            (0, 2), (1, 3), (4, 6), (5, 7),
            (1, 2), (5, 6),
            (0, 4), (1, 5), (2, 6), (3, 7),
            (2, 4), (3, 5),
            (1, 2), (3, 4), (5, 6)]


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _decide_kernel(fid_ref, cpath_ref, ccong_ref, valid_ref, out_ref, *,
                   alpha: int, beta: int, keep_num: int, cong_fallback: int):
    fids = fid_ref[0, :]                        # (BF,) uint32
    c_path = cpath_ref[...]                     # (8, BF) int32
    c_cong = ccong_ref[...]
    valid = valid_ref[...] != 0                 # (8, BF) bool

    cost = alpha * c_path + beta * c_cong
    cost = jnp.where(valid, cost, _COST_INVALID)
    row = jax.lax.broadcasted_iota(jnp.int32, (P_PAD, BF), 0)
    key = cost * P_PAD + row                    # embed index for stable argsort

    # --- stage 1: Batcher sorting network over the sublane axis ---------
    rows = [key[i, :] for i in range(P_PAD)]    # 8 vector registers
    for i, j in _NETWORK:
        lo = jnp.minimum(rows[i], rows[j])
        hi = jnp.maximum(rows[i], rows[j])
        rows[i], rows[j] = lo, hi
    sorted_key = jnp.stack(rows)                # (8, BF) ascending

    # --- stage 2: suffix filter + hash inside the kept set --------------
    num_valid = valid.astype(jnp.int32).sum(0)                  # (BF,)
    keep = jnp.maximum((num_valid + keep_num - 1) // keep_num, 1)
    h = _fmix32(fids)
    pick = (h % keep.astype(jnp.uint32)).astype(jnp.int32)      # (BF,)

    # fallback: all candidates highly congested -> argmin fused (rank 0)
    min_cong = jnp.where(valid, c_cong, _SCORE_MAX + 1).min(0)
    pick = jnp.where(min_cong >= cong_fallback, 0, pick)

    # one-hot row gather of the picked rank (8 rows, vectorized)
    picked = jnp.zeros((BF,), jnp.int32)
    for i in range(P_PAD):
        picked = jnp.where(pick == i, sorted_key[i, :], picked)

    choice = picked % P_PAD                     # un-embed candidate index
    out_ref[0, :] = jnp.where(num_valid > 0, choice, -1)


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def lcmp_decide(flow_ids: jnp.ndarray, c_path: jnp.ndarray, c_cong: jnp.ndarray,
                valid: jnp.ndarray, params: SelectParams = SelectParams(),
                *, interpret: bool) -> jnp.ndarray:
    """Batched LCMP decision. flow_ids (F,) uint32; c_path/c_cong/valid
    (F, P) with P <= 8. Returns (F,) int32 candidate indices (-1: none)."""
    F, P = c_path.shape
    assert P <= P_PAD, "switch candidate sets are m<=8 (paper §4)"
    f_pad = (F + BF - 1) // BF * BF

    def pad_fp(x, fill):
        x = jnp.pad(x.astype(jnp.int32), ((0, f_pad - F), (0, P_PAD - P)),
                    constant_values=fill)
        return x.T.reshape(P_PAD, f_pad)        # candidates -> sublanes

    fid = jnp.pad(flow_ids.astype(jnp.uint32), (0, f_pad - F)).reshape(1, f_pad)
    cp = pad_fp(c_path, 0)
    cc = pad_fp(c_cong, 0)
    vd = pad_fp(valid.astype(jnp.int32), 0)

    grid = (f_pad // BF,)
    kern = functools.partial(
        _decide_kernel, alpha=params.alpha, beta=params.beta,
        keep_num=params.keep_num, cong_fallback=params.cong_fallback)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BF), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((P_PAD, BF), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((P_PAD, BF), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((P_PAD, BF), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, BF), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, f_pad), jnp.int32),
        interpret=interpret,
        name="lcmp_decide",
    )(fid, cp, cc, vd)
    return out[0, :F]
