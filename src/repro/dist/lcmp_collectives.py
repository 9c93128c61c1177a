"""LCMP-scheduled cross-pod collectives: the paper's router applied to
gradient buckets on the inter-datacenter long haul.

The inter-pod fabric is modeled as ``NUM_ROUTES`` candidate *route
programs* (direct DCI, fallback DCI, transit-pod detour) with a static
path-quality score per route (``repro.core.pathq`` semantics:
delay-biased, fat-link-friendly, host-side integer mirror) and a
telemetry register file mirroring the on-switch congestion estimator of
``repro.core.cong`` — Q/T/D registers fed with observed per-step wall
times, so a persistently slow route (straggler trend) scores high and
gets demoted for *future* buckets.

``lcmp_pod_reduce`` chops the flat gradient vector into fixed-size
buckets and binds each bucket to a route with the exact two-stage LCMP
selection (fused cost C = alpha*C_path + beta*C_cong, keep the
lower-cost half of the *live* routes, fmix32-hash inside the kept set —
dead routes are skipped entirely: the lazy fast-failover of paper
§3.4). The reduction itself executes as ONE fused shard-map-safe
reduce-scatter / all-gather mean over the named ``pod`` mesh axis (wire
bytes identical to per-bucket collectives, but the traced program stays
O(1) in bucket count — a billion-parameter gradient doesn't unroll into
tens of thousands of collectives). Optionally int8-compressed on the
wire (``repro.dist.compress`` over the ``kernels.qsr_int8`` Pallas
kernel: quantize -> all_to_all -> partial-mean -> re-quantize ->
all_gather, <= 2 quantization steps of error end to end).

Route binding is metadata in this single-process reproduction — every
bucket ultimately shares the same XLA collective — but it is recorded
per bucket/route in ``_TELEMETRY.route_bytes`` at trace time so
examples and tests can observe the scheduling decisions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import compress as comp
from repro.kernels import ops
from repro.kernels.qsr_int8 import BLOCK

# Candidate inter-pod route programs (one-way propagation us, capacity
# Gbps): direct DCI, fallback DCI, transit-pod detour.
NUM_ROUTES = 3
ROUTE_PROP_US = np.array([5_000, 20_000, 45_000], np.int64)
ROUTE_CAP_GBPS = np.array([400, 200, 100], np.int64)
ALPHA, BETA = 3, 1            # paper §5/§7 fused-cost weights
BUCKET_ELEMS = 1 << 16        # 256 KiB f32 buckets on the wire


def _fmix32_host(x: np.ndarray) -> np.ndarray:
    """MurmurHash3 finalizer over uint32 (host-side twin of
    ``repro.core.select.fmix32``)."""
    x = np.asarray(x, np.uint32).copy()
    x ^= x >> np.uint32(16)
    x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
    x ^= x >> np.uint32(13)
    x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
    x ^= x >> np.uint32(16)
    return x


def _route_cpath() -> np.ndarray:
    """Static per-route C_path, integer mirror of ``core.pathq`` Eq. 2:
    delayScore = min(us >> 8, 255); capacity classes of 40 Gbps, fatter
    link -> lower cost; fused with (w_dl, w_lc) = (3, 1), >> 2."""
    d = np.minimum(ROUTE_PROP_US >> 8, 255)
    cls = np.minimum(ROUTE_CAP_GBPS // 40, 10)
    lc_score = ((10 - cls) * 255) // 10
    return np.minimum((3 * d + lc_score) >> 2, 255)


C_PATH = _route_cpath()


class RouteTelemetry:
    """Host-side per-route register file (the 24 B/port registers of
    ``core.cong``, §3.3): EWMA trend (Eq. 3), level and persistence,
    driven by per-step wall-time observations from the launcher."""

    EWMA_K = 3          # Eq. 3 shift
    HIGH_MS = 512       # wall-time level treated as "congested"

    def __init__(self, n: int = NUM_ROUTES):
        self.n = n
        self.reset()

    def reset(self):
        self.cur = np.zeros(self.n, np.int64)
        self.trend = np.zeros(self.n, np.int64)
        self.dur = np.zeros(self.n, np.int64)
        self.last_step = -1
        self.alive = np.ones(self.n, bool)
        self.route_bytes = np.zeros(self.n, np.int64)

    def observe(self, ms, step: int):
        """Feed one per-route wall-time sample (ms) at train ``step``."""
        ms = np.asarray(ms, np.int64)
        delta = ms - self.cur
        self.trend = (self.trend - (self.trend >> self.EWMA_K)
                      + (delta >> self.EWMA_K))
        self.cur = ms
        self.dur = np.where(ms >= self.HIGH_MS, self.dur + 1, self.dur >> 1)
        self.last_step = int(step)

    def observe_measured(self, bucket_ms, bucket_routes, step: int):
        """Feed *externally measured* per-bucket wall times (ms) into the
        Q/T/D registers — the co-simulation seam (``repro.cosim``): bucket
        times come from the netsim engines instead of the launcher's
        synthetic wall clock. ``bucket_routes`` is the route each bucket
        was bound to (``schedule_buckets`` output; -1 = unrouted, the
        sample is dropped). A route's sample is the MAX over its buckets
        (barrier semantics — the straggler bucket is what the step
        waits on); a route with no bucket this step holds its current
        level, so its delta is 0 and the trend register decays exactly as
        an idle port's would."""
        bucket_ms = np.asarray(bucket_ms, np.int64).reshape(-1)
        routes = np.asarray(bucket_routes, np.int64).reshape(-1)
        if bucket_ms.shape != routes.shape:
            raise ValueError(f"bucket_ms {bucket_ms.shape} and "
                             f"bucket_routes {routes.shape} must align")
        ok = (routes >= 0) & (routes < self.n)
        # a sampled route's level is its straggler bucket, even when that
        # is *below* the held level (recovery must be observable too)
        slow = np.full(self.n, -(1 << 60), np.int64)
        np.maximum.at(slow, routes[ok], bucket_ms[ok])
        self.observe(np.where(slow > -(1 << 60), slow, self.cur), step)

    def cong_scores(self) -> np.ndarray:
        """C_cong per route in [0, 255] (Eqs. 4-5 shape: (2Q+T+D) >> 2)."""
        q = np.minimum(self.cur >> 2, 255)
        t = np.minimum(np.maximum(self.trend, 0), 255)
        d = np.minimum(self.dur, 255)
        return np.minimum((2 * q + t + d) >> 2, 255).astype(np.int64)


_TELEMETRY = RouteTelemetry()


def set_route_liveness(alive) -> None:
    """Control-plane liveness update (route withdrawal / fast-failover)."""
    alive = np.asarray(alive, bool).copy()
    assert alive.shape == (_TELEMETRY.n,), alive.shape
    _TELEMETRY.alive = alive


def schedule_buckets(bucket_ids: np.ndarray) -> np.ndarray:
    """Two-stage LCMP selection over routes for a batch of bucket ids
    (``core.select.select_egress`` semantics, host-side): fused cost,
    keep the lower-cost half of live routes (>= 1), fmix32-hash each
    bucket id inside the kept set. Returns -1 when no route is live."""
    ids = np.asarray(bucket_ids, np.uint32)
    cost = ALPHA * C_PATH + BETA * _TELEMETRY.cong_scores()
    live = np.nonzero(_TELEMETRY.alive)[0]
    if live.size == 0:
        return np.full(ids.shape, -1, np.int64)
    order = live[np.argsort(cost[live], kind="stable")]
    keep = order[: max(1, (live.size + 1) // 2)]
    return keep[_fmix32_host(ids) % np.uint32(len(keep))].astype(np.int64)


# ----------------------------------------------------------------- reduce
def _axis_size_or_none(axis):
    """Size of a bound named axis, or None outside shard_map/pmap (the
    1-device no-op path)."""
    if axis is None:
        return None
    try:
        return jax.lax.psum(1, axis)
    except NameError:
        return None


def _reduce_flat_f32(seg: jnp.ndarray, axis, n: int) -> jnp.ndarray:
    """Exact flat-vector mean over ``axis``: reduce-scatter + all-gather."""
    m = seg.shape[0]
    pad = (-m) % n
    if pad:
        seg = jnp.concatenate([seg, jnp.zeros((pad,), seg.dtype)])
    y = jax.lax.psum_scatter(seg, axis, scatter_dimension=0, tiled=True) / n
    return jax.lax.all_gather(y, axis, tiled=True)[:m]


def _reduce_flat_int8(seg: jnp.ndarray, axis, n: int,
                      seed: int) -> jnp.ndarray:
    """Compressed flat-vector mean: local quantize -> all_to_all (the
    reduce-scatter leg) -> dequant + partial mean -> re-quantize ->
    all_gather. Both wire legs carry int8 + per-1024 f32 scales."""
    m = seg.shape[0]
    chunk = -(-m // n)                  # per-pod chunk ...
    chunk = -(-chunk // BLOCK) * BLOCK  # ... rounded up to the scale block
    mp = n * chunk
    if mp != m:
        seg = jnp.concatenate([seg, jnp.zeros((mp - m,), jnp.float32)])
    me = jax.lax.axis_index(axis)

    q, s = ops.qsr_int8(seg, comp.rand_bits(mp, seed, salt=me))
    q2 = jax.lax.all_to_all(q.reshape(n, chunk), axis,
                            split_axis=0, concat_axis=0, tiled=True)
    s2 = jax.lax.all_to_all(s.reshape(n, chunk // BLOCK), axis,
                            split_axis=0, concat_axis=0, tiled=True)
    part = ops.qsr_dequant(q2.reshape(-1), s2.reshape(-1)).reshape(n, chunk)
    mean_chunk = part.mean(0)

    qm, sm = ops.qsr_int8(mean_chunk,
                          comp.rand_bits(chunk, seed ^ 0x5851F42D, salt=me))
    qg = jax.lax.all_gather(qm, axis, tiled=True)
    sg = jax.lax.all_gather(sm, axis, tiled=True)
    return ops.qsr_dequant(qg, sg)[:m]


def lcmp_pod_reduce(tree, axis, compress: bool = False):
    """Mean-reduce a gradient pytree over the named ``axis`` (== pmean),
    as LCMP-scheduled fixed-size buckets. No-op when ``axis`` is None or
    unbound (single-pod / 1-device runs).

    Must be called under shard_map/pmap with ``axis`` in scope; with
    ``compress=True`` the wire is int8 (4x fewer bytes, error bounded by
    2 quantization steps — see tests/test_dist.py)."""
    n = _axis_size_or_none(axis)
    if n is None or n == 1:
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    total = int(flat.shape[0])

    # bucket->route binding + wire accounting (host metadata; the traced
    # reduction below is one fused collective regardless of bucket count)
    nb = -(-total // BUCKET_ELEMS)
    ids = _fmix32_host(np.arange(nb, dtype=np.uint32) + np.uint32(1))
    routes = schedule_buckets(ids)
    for b in range(nb):
        blen = min((b + 1) * BUCKET_ELEMS, total) - b * BUCKET_ELEMS
        wire = blen + 4 * (-(-blen // BLOCK)) if compress else 4 * blen
        if routes[b] >= 0:
            _TELEMETRY.route_bytes[int(routes[b])] += wire

    if compress:
        out = _reduce_flat_int8(flat, axis, n, seed=int(ids[0]))
    else:
        out = _reduce_flat_f32(flat, axis, n)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    new_leaves = [out[offs[i]:offs[i + 1]].reshape(shapes[i]).astype(dtypes[i])
                  for i in range(len(leaves))]
    return jax.tree.unflatten(treedef, new_leaves)
