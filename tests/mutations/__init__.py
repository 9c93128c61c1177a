"""Seeded physics bugs, one per sanitizer invariant.

Each entry is a ``(t, state) -> state`` corruptor installed on
``repro.netsim.sanitize._MUTATION``. The sanitizer applies it at the top
of ``step_check`` and the corrupted state flows onward through the scan
— exactly how a real engine bug would propagate — so a passing
``tests/test_sanitize.py`` proves every invariant actually fires, on
both engines, from inside the jitted checkify program.

Two invariants have no step-state corruptor here: ``signal_causality``
is seeded by corrupting ``SimArrays.path_sig_delay`` before the run, and
``pfc_lossless`` by patching the ``sanitize.pfc_gate`` seam to ignore
the pause signal (see test_sanitize.py).
"""
import dataclasses

import jax.numpy as jnp


def _queue_nonneg(t, st):
    return dataclasses.replace(st, q_bytes=st.q_bytes - 1.0)


def _buffer_bound(t, st):
    return dataclasses.replace(st, q_bytes=st.q_bytes + 1e12)


def _byte_conservation(t, st):
    return dataclasses.replace(
        st, remaining=jnp.where(st.slot_path >= 0,
                                st.remaining + 1e9, st.remaining))


def _ring_head(t, st):
    return dataclasses.replace(st, hist_q=st.hist_q + 1.0)


def _clock_monotone(t, st):
    return dataclasses.replace(
        st, route_step=jnp.where(st.slot_path >= 0,
                                 t + 10, st.route_step))


def _cc_rate_bounds(t, st):
    return dataclasses.replace(st, rate=jnp.where(st.active, -1.0, st.rate))


def _cong_quantized(t, st):
    return dataclasses.replace(st, c_path=jnp.full_like(st.c_path, 999))


def _completion_identity(t, st):
    at = jnp.where(st.active, st.slot_flow, st.done.shape[0])
    return dataclasses.replace(st, done=st.done.at[at].set(True,
                                                           mode="drop"))


def _window_slots(t, st):
    return dataclasses.replace(st, flow_path=st.flow_path + 1)


MUTATIONS = {
    "queue_nonneg": _queue_nonneg,
    "buffer_bound": _buffer_bound,
    "byte_conservation": _byte_conservation,
    "ring_head": _ring_head,
    "clock_monotone": _clock_monotone,
    "cc_rate_bounds": _cc_rate_bounds,
    "cong_quantized": _cong_quantized,
    "completion_identity": _completion_identity,
    "window_slots": _window_slots,
}
