"""Debug-mode physics-invariant sanitizer for both simulation engines.

Every headline comparison this repo produces rests on the engines being
*physically right*: bytes conserved, queues non-negative and lossless,
congestion signals never fresher than backward propagation, PFC pauses
actually honored. This module makes those properties machine-checked at
runtime via ``jax.experimental.checkify``, threaded through the scan of
both ``fluid.py`` and ``packet.py``.

Off by default and **bit-for-bit free when off**: the engines consult
``enabled(cfg)`` at trace time (a Python gate on the static
``SimConfig.checks`` flag, same pattern as ``wants_redecide``), so the
unchecked program contains no extra ops (asserted for both engines in
``tests/test_sanitize.py``). Enable per experiment via
``ExpSpec(checks=1)`` or globally with ``REPRO_CHECKS=1`` in the
environment; a failed invariant raises ``checkify.JaxRuntimeError``
naming the invariant. ``benchmarks/perf.py`` records the checked-scan
overhead so this stays a debug mode, not a tax.

Three registries tie the module to the static analyzer
(reprolint INV001/INV002, ``repro.analysis.invariants``):

- ``INVARIANTS``          — invariant name -> per-step check function
- ``INVARIANT_COVERAGE``  — state field -> invariant names constraining
  it; every ``SimState``/``PacketState`` field mutated inside the scan
  must appear here or in
- ``COVERAGE_EXEMPT``     — field -> why no runtime check applies.

``tests/mutations`` installs one seeded physics bug per invariant
through the ``_MUTATION`` hook and proves each check fires on both
engines.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import checkify

from repro.netsim.engine import HIST, SimArrays, SimConfig

# test seam: (t, state) -> corrupted state, applied before the checks so
# a seeded physics bug flows onward through the scan exactly like a real
# one. None in production.
_MUTATION: Optional[Callable[[Any, Any], Any]] = None

# relative slack for f32 accumulation (per-flow byte accounting crosses
# thousands of rounded adds on ~MB quantities)
_REL_EPS = 1e-3


def enabled(cfg: SimConfig) -> bool:
    """Trace-time gate: True iff this cfg wants the checked program."""
    return bool(cfg.checks)


def host_checks_enabled() -> bool:
    """Gate for host-side (numpy) accounting checks in ``metrics`` /
    ``cosim.iterate`` — env-only, they run outside any trace."""
    return os.environ.get("REPRO_CHECKS") == "1"


def host_check(ok: bool, msg: str) -> None:
    """Host-side analogue of ``checkify.check`` (plain raise)."""
    if not ok:
        raise AssertionError(f"sanitize: {msg}")


# ------------------------------------------------------------ invariants
def _check_queue_nonneg(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """Link queues and served-byte counters never go negative (the fluid
    engine clamps at 0, the packet engine only moves existing bytes)."""
    checkify.check(jnp.all(st.q_bytes >= -1e-3),
                   "queue_nonneg: negative link queue bytes")
    checkify.check(jnp.all(st.serv_bytes >= -1e-3),
                   "queue_nonneg: negative served-bytes counter")
    if hasattr(st, "fq"):
        checkify.check(jnp.all(st.fq >= -1e-3),
                       "queue_nonneg: negative per-hop flow queue")


def _check_buffer_bound(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """Lossless RDMA: queue depth never exceeds the (scaled) long-haul
    buffer — the fluid clamp and the packet acceptance factors both
    enforce it, up to f32 rounding and one packet of quantization."""
    buf = float(cfg.buffer_bytes * cfg.cap_scale)
    slack = 1e-4 * buf + 2.0 * float(cfg.mtu_bytes)
    checkify.check(jnp.all(st.q_bytes <= buf + slack),
                   "buffer_bound: link queue exceeds the lossless buffer")


def _check_byte_conservation(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """Per routed flow in the window, bytes are conserved. Fluid:
    remaining only ever moves from f_size toward 0. Packet: injected =
    queued + delivered, i.e. remaining + fq.sum + delivered == f_size —
    the identity survives go-back-N failover because stranded queue
    bytes return to ``remaining`` (see ``packet._reroute_dead_packet``)."""
    routed = st.slot_path >= 0
    size = ar.f_size[jnp.maximum(st.slot_flow, 0)]
    if hasattr(st, "fq"):
        total = st.remaining + st.fq.sum(-1) + st.delivered
        slack = _REL_EPS * size + 2.0 * float(cfg.mtu_bytes)
        ok = jnp.abs(total - size) <= slack
    else:
        slack = _REL_EPS * size + 1.0
        ok = (st.remaining >= -1e-3) & (st.remaining <= size + slack)
    checkify.check(jnp.all(jnp.where(routed, ok, True)),
                   "byte_conservation: flow byte accounting broken")


def _check_ring_head(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """The history rings' slot ``t`` holds exactly this step's state —
    an off-by-one ring slot (the classic silent-staleness bug) breaks
    the head equality immediately."""
    slot = jnp.asarray(t % HIST, jnp.int32)
    checkify.check(jnp.all(st.hist_q[:, slot] == st.q_bytes),
                   "ring_head: hist_q slot t != q_bytes (ring slot skew)")
    checkify.check(jnp.all(st.hist_c[:, slot] == st.c_cong),
                   "ring_head: hist_c slot t != c_cong (ring slot skew)")
    if hasattr(st, "hist_pause"):
        checkify.check(jnp.all(st.hist_pause[:, slot] == st.pfc_pause),
                       "ring_head: hist_pause slot t != pfc_pause")


def _check_clock_monotone(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """Causality of the per-flow clocks: routing/decision timestamps
    never sit in the future, RTTs are at least one step."""
    routed = st.slot_path >= 0
    checkify.check(jnp.all(jnp.where(routed, st.route_step <= t, True)),
                   "clock_monotone: route_step in the future")
    checkify.check(jnp.all(st.last_dec <= t),
                   "clock_monotone: last CC decrease in the future")
    checkify.check(jnp.all(st.rtt_steps >= 1),
                   "clock_monotone: rtt_steps < 1")
    if hasattr(st, "last_tx"):
        checkify.check(
            jnp.all((st.last_tx <= t) | (st.last_tx == (1 << 20))),
            "clock_monotone: last_tx in the future")


def _check_signal_causality(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """Routing-signal staleness offsets are non-negative (reads are
    never fresher than backward propagation delivers — paper §3) and
    inside the ring capacity the build() guard promised."""
    checkify.check(jnp.all(ar.path_sig_delay >= 0),
                   "signal_causality: negative signal delay would read "
                   "future congestion")
    checkify.check(jnp.all(ar.path_sig_delay < HIST),
                   "signal_causality: signal delay outruns the ring")


def _check_cc_rate_bounds(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """Active flows send at a positive rate bounded by line rate, the
    DCTCP EWMA stays a probability, targets stay within line rate."""
    line_max = ar.path_cap.max() * 1.001
    act = st.active
    checkify.check(
        jnp.all(jnp.where(act, (st.rate > 0.0) & (st.rate <= line_max),
                          True)),
        "cc_rate_bounds: active flow rate outside (0, line]")
    checkify.check(
        jnp.all(jnp.where(act, (st.cc_target >= 0.0)
                          & (st.cc_target <= line_max), True)),
        "cc_rate_bounds: CC target outside [0, line]")
    checkify.check(jnp.all((st.cc_alpha >= 0.0) & (st.cc_alpha <= 1.0)),
                   "cc_rate_bounds: DCTCP alpha outside [0, 1]")


def _check_cong_quantized(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """Quantized switch registers stay in their wire ranges: C_cong and
    C_path in [0, 255] (the 8-bit score the paper's registers carry),
    RedTE weights in [0, 256], the utilization EWMA in [0, 1]."""
    checkify.check(jnp.all((st.c_cong >= 0) & (st.c_cong <= 255)),
                   "cong_quantized: C_cong outside [0, 255]")
    checkify.check(jnp.all((st.c_path >= 0) & (st.c_path <= 255)),
                   "cong_quantized: C_path outside [0, 255]")
    checkify.check(jnp.all((st.redte_w >= 0) & (st.redte_w <= 256)),
                   "cong_quantized: RedTE weight outside [0, 256]")
    checkify.check(jnp.all((st.u_ewma >= 0.0) & (st.u_ewma <= 1.0 + 1e-5)),
                   "cong_quantized: utilization EWMA outside [0, 1]")


def _check_completion_identity(t, st, ar: SimArrays,
                               cfg: SimConfig) -> None:
    """A flow is never both done and active (a finished flow has left
    the window), and every completed flow carries a positive FCT (fct
    >= one slot past its arrival)."""
    done_in_window = st.done[jnp.maximum(st.slot_flow, 0)]
    checkify.check(jnp.all(~(done_in_window & st.active)),
                   "completion_identity: flow both done and active")
    checkify.check(jnp.all(jnp.where(st.done, st.fct_us > 0.0, True)),
                   "completion_identity: completed flow with FCT <= 0")


def _check_pfc_lossless(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """PFC XOFF => no upstream forward. The hop loop's gate cannot be
    observed post-step, so this invariant is checked inline where the
    forward happens (``check_pfc`` below, called from
    ``packet.make_step`` when checks are on); registered here so the
    coverage table can reference it."""


def _check_window_slots(t, st, ar: SimArrays, cfg: SimConfig) -> None:
    """The window's bookkeeping: an active slot holds a flow, and each
    occupied slot's path is the path its flow's answer records (both
    are written together at admission, failover and re-decision)."""
    sf = st.slot_flow
    checkify.check(jnp.all(jnp.where(st.active, sf >= 0, True)),
                   "window_slots: active slot holds no flow")
    answer = st.flow_path[jnp.maximum(sf, 0)]
    checkify.check(jnp.all(jnp.where(sf >= 0, answer == st.slot_path, True)),
                   "window_slots: slot path differs from its flow's answer")


INVARIANTS: Dict[str, Callable] = {
    "queue_nonneg": _check_queue_nonneg,
    "buffer_bound": _check_buffer_bound,
    "byte_conservation": _check_byte_conservation,
    "ring_head": _check_ring_head,
    "clock_monotone": _check_clock_monotone,
    "signal_causality": _check_signal_causality,
    "cc_rate_bounds": _check_cc_rate_bounds,
    "cong_quantized": _check_cong_quantized,
    "completion_identity": _check_completion_identity,
    "pfc_lossless": _check_pfc_lossless,
    "window_slots": _check_window_slots,
}

# state field -> invariant names that constrain it (reprolint INV001
# requires every field mutated in the scan to appear here or in
# COVERAGE_EXEMPT; INV002 cross-validates the names both ways)
INVARIANT_COVERAGE: Dict[str, Tuple[str, ...]] = {
    "slot_flow": ("window_slots", "byte_conservation",
                  "completion_identity"),
    "slot_path": ("byte_conservation", "clock_monotone", "window_slots"),
    "flow_path": ("window_slots",),
    "remaining": ("byte_conservation",),
    "rate": ("cc_rate_bounds",),
    "active": ("completion_identity", "cc_rate_bounds"),
    "done": ("completion_identity",),
    "fct_us": ("completion_identity",),
    "rtt_steps": ("clock_monotone",),
    "route_step": ("clock_monotone",),
    "last_dec": ("clock_monotone",),
    "cc_alpha": ("cc_rate_bounds",),
    "cc_target": ("cc_rate_bounds",),
    "q_bytes": ("queue_nonneg", "buffer_bound", "ring_head"),
    "hist_q": ("ring_head",),
    "hist_c": ("ring_head", "cong_quantized"),
    "u_ewma": ("cong_quantized",),
    "serv_bytes": ("queue_nonneg",),
    "c_cong": ("cong_quantized", "ring_head"),
    "c_path": ("cong_quantized",),
    "redte_w": ("cong_quantized",),
    # packet engine
    "fq": ("byte_conservation", "queue_nonneg"),
    "delivered": ("byte_conservation",),
    "last_tx": ("clock_monotone",),
    "pfc_pause": ("pfc_lossless", "ring_head"),
    "hist_pause": ("pfc_lossless", "ring_head"),
}

# state field -> why no runtime invariant applies
COVERAGE_EXEMPT: Dict[str, str] = {
    "extra_wait": "FCT wait estimate derived from q_bytes/link_cap, both "
                  "already range-checked; any non-negative estimate is a "
                  "legal model output",
    "route_nonce": "hash salt for re-decision keys — every value is a "
                   "valid (deterministic) decision key",
    "prev_delay": "TIMELY gradient memory; no physical bound beyond "
                  "finiteness (it stores a delay sample or 0)",
    "hist_u": "telemetry ring; offered/cap utilization legitimately "
              "exceeds 1 under overload, so no range bound exists",
    "link_alive": "boolean liveness mask written directly from the "
                  "failure schedule comparison",
    "cong": "core register-pipeline internals (Q/T/D EWMAs); the "
            "quantized output c_cong is range-checked instead",
    "credit": "pacing accumulator bounded by the rate-BDP window of the "
              "rate at injection time; the same step's CC update may "
              "shrink that window, so no post-step bound holds",
    "live_flow_steps": "work counter (flows each step moves, summed); "
                       "no answer reads it",
    "live_flow_peak": "work counter (most flows one step moves); no "
                      "answer reads it",
    "window_overflow": "sticky flag the host reads after the run: a set "
                       "flag makes it run again on a wider window, so no "
                       "answer of an overflowed run is kept",
}


# --------------------------------------------------------- step plumbing
def step_check(t, st, ar: SimArrays, cfg: SimConfig):
    """Run every registered invariant against the end-of-step state.

    Called by both engines' step functions (only when ``enabled(cfg)``,
    so the unchecked trace is untouched). The mutation seam applies
    first and its corruption flows onward through the scan — exactly how
    a real physics bug would propagate."""
    if _MUTATION is not None:
        st = _MUTATION(t, st)
    for check in INVARIANTS.values():
        check(t, st, ar, cfg)
    return st


def pfc_gate(ok_hop, paused_next):
    """The packet engine's per-hop PFC send gate (checked mode only).
    Identity in production; the pfc_lossless mutation patches this to
    ignore the pause signal, proving ``check_pfc`` catches a broken
    gate."""
    return ok_hop & ~paused_next


def check_pfc(fwd, paused_next) -> None:
    """Inline pfc_lossless check at the forward site: no bytes may be
    forwarded into a queue whose pause signal says XOFF."""
    checkify.check(jnp.all(jnp.where(paused_next, fwd <= 0.0, True)),
                   "pfc_lossless: bytes forwarded into a paused queue")


# ------------------------------------------------------------ run entry
@functools.lru_cache(maxsize=32)
def _checked_runner(run_impl: Callable, cfg: SimConfig) -> Callable:
    """jit(checkify(run_impl)) with cfg closed over (checkify's wrapper
    obscures the signature, so static_argnames cannot be used; the cache
    keys on the hashable frozen cfg instead)."""
    def run_cfg(arrs, state):
        return run_impl(arrs, state, cfg)
    return jax.jit(checkify.checkify(run_cfg,
                                     errors=checkify.user_checks))


def run_with_checks(run_impl: Callable, arrs, state, cfg: SimConfig):
    """Checked single-experiment entry: run the scan under checkify and
    throw ``checkify.JaxRuntimeError`` if any invariant failed."""
    err, final = _checked_runner(run_impl, cfg)(arrs, state)
    err.throw()
    return final


def checked_call(fn: Callable) -> Callable:
    """``jit(checkify(fn))`` with the error thrown on return — the sweep
    engine's group runner routes through this when ``cfg.checks`` is
    set, so batched cells are sanitized too."""
    checked = jax.jit(checkify.checkify(fn, errors=checkify.user_checks))

    def wrapper(*args: Any) -> Any:
        err, out = checked(*args)
        err.throw()
        return out
    return wrapper
