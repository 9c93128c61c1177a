"""int8 + per-block-scale wire format for cross-pod gradient buckets.

Wire layout for a flat f32 vector of N elements:
  q      (Np,)         int8   stochastically-rounded mantissas
  scales (Np/1024,)    f32    per-1024-element block scales (amax/127)
with Np = N rounded up to a 1024 multiple, so the wire carries
``N + 4*N/1024`` bytes instead of ``4*N`` — a 3.98x reduction on the
DCI long haul (DESIGN §5; the ``lcmp_int8`` train path).

Quantization runs through the Pallas kernel ``repro.kernels.qsr_int8``
via ``repro.kernels.ops`` (compiled on a TPU, interpreted elsewhere;
blockwise amax, stochastic rounding from caller-supplied counter bits,
so the wire format is deterministic and testable). Error feedback
(``encode_ef``) returns the representation residual so the caller can
fold it into the *next* step's gradient, making the compression
unbiased over time (standard EF-SGD).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.select import fmix32
from repro.kernels import ops
from repro.kernels.qsr_int8 import BLOCK


class Wire(NamedTuple):
    """One compressed bucket as it crosses the long haul."""
    q: jnp.ndarray        # (Np,) int8
    scales: jnp.ndarray   # (Np/BLOCK,) f32
    orig_len: int         # static: valid prefix of q (rest is padding)


def padded_len(n: int) -> int:
    return -(-n // BLOCK) * BLOCK


def rand_bits(n: int, seed, salt=0) -> jnp.ndarray:
    """Counter-based uint32 stream for the stochastic rounding (pure
    function of (seed, salt, position): identical across retraces)."""
    ctr = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)
    mix = fmix32(jnp.asarray(salt).astype(jnp.uint32) + jnp.uint32(1))
    return fmix32(ctr ^ jnp.asarray(seed).astype(jnp.uint32) ^ mix)


def encode(x: jnp.ndarray, *, seed=0, salt=0) -> Wire:
    """Flat f32 (N,) -> Wire. Pads with zeros up to the block size."""
    n = x.shape[0]
    np_ = padded_len(n)
    xf = x.astype(jnp.float32)
    if np_ != n:
        xf = jnp.concatenate([xf, jnp.zeros((np_ - n,), jnp.float32)])
    q, scales = ops.qsr_int8(xf, rand_bits(np_, seed, salt))
    return Wire(q=q, scales=scales, orig_len=n)


def decode(w: Wire) -> jnp.ndarray:
    return ops.qsr_dequant(w.q, w.scales)[: w.orig_len]


def wire_bytes(w: Wire) -> int:
    return int(w.q.size) + 4 * int(w.scales.size)


def encode_ef(x: jnp.ndarray, residual: jnp.ndarray, *, seed=0,
              salt=0) -> tuple:
    """Error-feedback encode: compress ``x + residual`` and return the
    new residual ``(x + residual) - decode(wire)`` to carry forward."""
    y = x.astype(jnp.float32) + residual.astype(jnp.float32)
    w = encode(y, seed=seed, salt=salt)
    return w, y - decode(w)
