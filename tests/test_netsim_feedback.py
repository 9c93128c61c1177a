"""Unit test for the per-flow delayed-feedback gate in the fluid sim's
CC update (regression: the gate used to be ``t > rtt_steps`` — global —
so a flow arriving late immediately read congestion history recorded
*before* it was routed)."""
import jax.numpy as jnp
import numpy as np

from repro.core.cong import CongState
from repro.netsim import fluid


def _state_two_flows(t, rtt):
    """Two identical line-rate flows on link 0, in slots 0 and 1 of the
    window; flow 0 routed long ago, flow 1 routed just now. The history
    ring carries heavy congestion at the delayed-read slot (t - rtt)."""
    hist_q = np.zeros((1, fluid.HIST), np.float32)
    hist_q[0, (t - rtt) % fluid.HIST] = 1e9
    z = jnp.zeros((2,), jnp.float32)
    return fluid.SimState(
        slot_flow=jnp.arange(2, dtype=jnp.int32),
        slot_path=jnp.zeros(2, jnp.int32),
        remaining=jnp.ones(2, jnp.float32) * 1e9,
        rate=jnp.full((2,), 100.0, jnp.float32),
        active=jnp.ones(2, bool),
        extra_wait=z,
        rtt_steps=jnp.full((2,), rtt, jnp.int32),
        route_step=jnp.asarray([0, t - 1], jnp.int32),
        route_nonce=jnp.zeros(2, jnp.int32),
        last_dec=jnp.full((2,), -(1 << 20), jnp.int32),
        cc_alpha=z,
        cc_target=jnp.full((2,), 100.0, jnp.float32),
        prev_delay=z,
        flow_path=jnp.zeros(2, jnp.int32),
        done=jnp.zeros(2, bool),
        fct_us=z,
        q_bytes=jnp.zeros((1,), jnp.float32),
        hist_q=jnp.asarray(hist_q),
        hist_u=jnp.zeros((1, fluid.HIST), jnp.float32),
        hist_c=jnp.zeros((1, fluid.HIST), jnp.int32),
        u_ewma=jnp.zeros((1,), jnp.float32),
        link_alive=jnp.ones((1,), bool),
        serv_bytes=jnp.zeros((1,), jnp.float32),
        cong=CongState.init(1),
        c_cong=jnp.zeros((1,), jnp.int32),
        c_path=jnp.zeros((1,), jnp.int32),
        redte_w=jnp.ones((1, 1), jnp.int32),
        live_flow_steps=jnp.int32(0),
        live_flow_peak=jnp.int32(0),
        window_overflow=jnp.asarray(False),
    )


def _arrays():
    return fluid.SimArrays(
        link_cap=jnp.asarray([125.0], jnp.float32),
        link_cap_gbps=None, path_links=None, path_prop=None,
        path_cap=jnp.asarray([100.0], jnp.float32),
        path_cap_gbps=None, path_first=None, pair_cand=None,
        arrivals=None, f_arr_us=None, f_size=None, f_pair=None,
        f_id=jnp.asarray([1, 2], jnp.uint32), tables=None)


def test_feedback_gated_on_flows_own_route_step():
    cfg = fluid.SimConfig(cc="dcqcn")
    t, rtt = 5000, 4
    st = _state_two_flows(t, rtt)
    out = fluid._cc_update(t, st, _arrays(), cfg,
                           path_of_flow=jnp.zeros(2, jnp.int32),
                           links_f=jnp.zeros((2, 1), jnp.int32),
                           links_ok=jnp.ones((2, 1), bool))
    # established flow: sees the RTT-delayed congestion signal -> MD
    assert float(out.rate[0]) < 100.0
    # flow routed one step ago: that history predates its routing; it
    # must NOT react to it (no feedback for its first RTT on the path)
    assert float(out.rate[1]) >= 100.0


def test_feedback_arrives_after_one_rtt_on_own_path():
    cfg = fluid.SimConfig(cc="dcqcn")
    t, rtt = 5000, 4
    st = _state_two_flows(t, rtt)
    # re-route flow 1 exactly rtt+1 steps before t: feedback now exists
    st = __import__("dataclasses").replace(
        st, route_step=jnp.asarray([0, t - rtt - 1], jnp.int32))
    out = fluid._cc_update(t, st, _arrays(), cfg,
                           path_of_flow=jnp.zeros(2, jnp.int32),
                           links_f=jnp.zeros((2, 1), jnp.int32),
                           links_ok=jnp.ones((2, 1), bool))
    assert float(out.rate[1]) < 100.0
