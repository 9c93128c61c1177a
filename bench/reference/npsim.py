"""The plain reference simulator: the step semantics of the fluid and
packet engines as a NumPy loop over time steps, on the host.

It is written from the model, not from the program: no JAX, no XLA, no
batching or padding. Each step works on the flows in flight only (the
flows arrived and not done), and the link sums are ``np.bincount``. It
models what the benchmark's cells run: the ``lcmp``, ``lcmp_w``,
``ecmp``, ``ucmp``, ``wcmp`` and ``redte`` policies, DCQCN rate control,
and silent capacity degradation; a configuration that asks for anything
else is refused.

The model of one step ``t`` (``dt`` = 200 us):

1. Switch monitor: every link's queue in 1 KiB cells feeds the LCMP
   registers (trend EWMA with shift 3, a persistence counter armed above
   the high-water queue level), whose quantized ``C_cong`` lands in a
   history ring.
2. Control plane: every ``ctrl_period_us`` the path scores ``C_path``
   are recomputed from the links' effective capacities.
3. Arrivals: the flows arriving in this step are routed together. A
   path's congestion is the largest ``C_cong`` over its hops, each read
   as late as its one-way propagation back to the ingress. WCMP and
   RedTE pick by a weighted flow hash: candidate ``i`` where the flow's
   hash, modulo the weights' sum, first falls below their running sum;
   WCMP's weights are the candidates' provisioned Gbps, fixed.
4. Data plane. Fluid: each link scales the flows through it by
   ``min(1, cap / offered)``, integrates its queue, and a flow drains at
   its rate times its path's smallest factor. Packet: flows inject whole
   MTU packets paced by their rate and bounded by the rate x RTT window,
   and the bytes move hop by hop under per-link budgets, PFC pause read
   one link propagation late and buffer acceptance.
5. DCQCN: each flow reads its path's largest queue one RTT late, is
   marked RED-style between Kmin and Kmax by a per-flow hash, and cuts
   by 0.7 on a reaction timer or recovers halfway to its target per RTT.
6. Completion: the FCT is the completion step's end less the arrival,
   plus the path's propagation (and, in the fluid engine, half the queue
   wait at arrival and at completion).
7. RedTE: every ``redte_period_us`` the split weights become each
   candidate's first-link headroom.

``precision="float64"`` is the reference. ``precision="bfloat16"`` is
the control: float32 arithmetic with every floating-point array of the
world and of the state rounded to bfloat16 after each step.
"""
from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import ml_dtypes
import numpy as np

HIST = 8192            # history ring length (steps)
CELL_BYTES = 1024      # switch queue accounting cell
SCORE_MAX = 255
NUM_LEVELS = 16        # queue and trend levels
NUM_CLASSES = 10       # capacity classes
MAX_CAPACITY_GBPS = 400
HIGH_WATER_LEVEL = int(0.625 * (NUM_LEVELS - 1))

# LCMP's integer weights and shifts (the paper's recommended settings)
W_QL, W_TL, W_DP, EWMA_K, DUR_SHIFT, S_CONG = 2, 1, 1, 3, 2, 2
W_DL, W_LC, D_SHIFT, S_PATH = 3, 1, 8, 2
ALPHA, BETA, KEEP_NUM, CONG_FALLBACK = 3, 1, 2, 230
COST_INVALID = 1 << 24
UCMP_INVALID = 1 << 30

# the simulator's fixed settings
DT_US = 200                    # step
BUFFER_BYTES = 6e9             # long-haul switch buffer, before cap_scale
ECN_KMIN_BYTES = 4e5           # RED marking from Kmin to 10 x Kmin
ECN_KMAX_FACTOR = 10.0
AI_FRAC = 0.002                # additive increase per RTT, share of line
MD_FACTOR = 0.7                # multiplicative decrease
CC_DEC_PERIOD_US = 1_600       # DCQCN reaction timer
REDTE_PERIOD_US = 100_000
CTRL_PERIOD_US = 100_000       # C_path re-install
MTU_BYTES = 1024
PFC_XOFF_FRAC, PFC_XON_FRAC = 0.7, 0.5

POLICIES = ("lcmp", "lcmp_w", "ecmp", "ucmp", "wcmp", "redte")


@dataclasses.dataclass(frozen=True)
class Config:
    """What differs between cells."""
    engine: str
    policy: str
    horizon_us: int
    cap_scale: float
    degrade_sched: tuple = ()
    fail_sched: tuple = ()
    dt_us: int = DT_US

    @property
    def num_steps(self) -> int:
        return self.horizon_us // self.dt_us


def fmix32(x) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer (the flows' hash)."""
    x = np.atleast_1d(np.asarray(x, np.uint32))
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16, kept in float32."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def level_scores(n: int) -> np.ndarray:
    return np.arange(n, dtype=np.int64) * SCORE_MAX // (n - 1)


class Reference:
    """One policy cell of one engine, stepped on the host."""

    def __init__(self, table, flows, cfg: Config, precision: str):
        if cfg.policy not in POLICIES:
            raise ValueError(f"the reference does not model {cfg.policy!r}")
        if cfg.fail_sched:
            raise ValueError("the reference does not model link failures")
        if precision == "float64":
            self.ft, self.r = np.float64, (lambda x: x)
        elif precision == "bfloat16":
            self.ft, self.r = np.float32, to_bfloat16
        else:
            raise ValueError(f"precision {precision!r}")
        ft, r = self.ft, self.r
        self.cfg = cfg
        dt = cfg.dt_us
        T = cfg.num_steps

        # ---- world
        gbps = np.asarray(table._link_caps, np.float64)
        self.L = L = len(gbps)
        self.link_gbps = gbps.astype(np.int64)
        self.link_cap = r(np.asarray(gbps * 125.0 * cfg.cap_scale, ft))
        self.link_delay = np.asarray(table._link_delays, np.int64)
        self.path_links = np.asarray(table.path_links, np.int64)
        self.H = self.path_links.shape[1]
        self.path_len = np.asarray(table.path_len, np.int64)
        self.path_prop = np.asarray(table.path_prop_us, np.int64)
        self.path_gbps = np.asarray(table.path_cap).astype(np.int64)
        self.path_cap = r(np.asarray(
            np.asarray(table.path_cap, np.float64) * 125.0 * cfg.cap_scale,
            ft))
        self.path_first = np.asarray(table.path_first, np.int64)
        self.pair_cand = np.asarray(table.pair_cand, np.int64)
        pl = self.path_links
        hop_delay = np.where(pl >= 0, self.link_delay[np.maximum(pl, 0)], 0)
        upstream = np.concatenate([np.zeros((pl.shape[0], 1), np.int64),
                                   np.cumsum(hop_delay, 1)[:, :-1]], 1)
        self.sig_delay = upstream // dt
        if max(int(np.max(2 * self.path_prop // dt)),
               int(self.sig_delay.max())) >= HIST:
            raise ValueError("a path's RTT exceeds the history ring")

        # ---- switch tables (paper sec. 3.1.2), scaled with the capacities
        self.level_score = level_scores(NUM_LEVELS)
        self.class_score = level_scores(NUM_CLASSES)
        self.cap_thresh = (np.arange(1, NUM_CLASSES) * MAX_CAPACITY_GBPS
                           // NUM_CLASSES)
        buf_cells = max(max(int(BUFFER_BYTES * cfg.cap_scale), 1 << 20)
                        // CELL_BYTES, NUM_LEVELS)
        self.q_thresh = np.array([max(buf_cells >> (NUM_LEVELS - 1 - i), 1)
                                  for i in range(1, NUM_LEVELS)], np.int64)
        rows = []
        for g in gbps:
            rate = max(int(g * cfg.cap_scale), 1)
            cells = ((rate * 10**9 // 8) * dt // 1_000_000) // CELL_BYTES
            rows.append([(i * (cells // 2)) // (NUM_LEVELS - 1)
                         for i in range(1, NUM_LEVELS)])
        self.trend_thresh = np.array(rows, np.int64)

        # ---- degradation schedule
        self.deg_step = np.full(L, 1 << 30, np.int64)
        self.deg_factor = np.ones(L, np.float64)
        for li, at_us, fac in cfg.degrade_sched:
            self.deg_step[li] = int(at_us) // dt
            self.deg_factor[li] = float(fac)
        self.deg_factor = r(self.deg_factor.astype(ft))

        # ---- flows
        F = flows.num_flows
        self.f_arr = r(np.asarray(flows.arrival_us, ft))
        self.f_size = r(np.asarray(flows.size_bytes, ft))
        self.f_pair = np.asarray(flows.pair_id, np.int64)
        self.f_id = np.asarray(flows.flow_id, np.uint32)
        step = np.minimum(np.asarray(flows.arrival_us, np.int64) // dt,
                          T - 1)
        order = np.argsort(step, kind="stable")
        bounds = np.searchsorted(step[order], np.arange(T + 1))
        self.arrivals = [order[bounds[i]:bounds[i + 1]] for i in range(T)]

        # ---- state
        self.flow_path = np.full(F, -1, np.int64)
        self.remaining = np.zeros(F, ft)
        self.rate = np.zeros(F, ft)
        self.cc_target = np.zeros(F, ft)
        self.extra_wait = np.zeros(F, ft)
        self.fct = np.zeros(F, ft)
        self.active = np.zeros(F, bool)
        self.done = np.zeros(F, bool)
        self.rtt = np.ones(F, np.int64)
        self.route_step = np.full(F, 1 << 20, np.int64)
        self.last_dec = np.full(F, -(1 << 20), np.int64)
        self.q = np.zeros(L, ft)
        self.hist_q = np.zeros((L, HIST), ft)
        self.hist_u = np.zeros((L, HIST), ft)
        self.hist_c = np.zeros((L, HIST), np.int64)
        self.u_ewma = np.zeros(L, ft)
        self.serv = np.zeros(L, ft)
        self.queue_cur = np.zeros(L, np.int64)
        self.trend = np.zeros(L, np.int64)
        self.dur = np.zeros(L, np.int64)
        self.c_path = self.path_quality(self.path_prop, self.path_gbps)
        self.redte_w = np.ones(self.pair_cand.shape, np.int64)
        if cfg.engine == "packet":
            self.fq = np.zeros((F, self.H), ft)
            self.credit = np.zeros(F, ft)
            self.pause = np.zeros(L, bool)
            self.hist_pause = np.zeros((L, HIST), bool)
        elif cfg.engine != "fluid":
            raise ValueError(f"engine {cfg.engine!r}")

    # ------------------------------------------------------------ planes
    def path_quality(self, delay_us, cap_gbps) -> np.ndarray:
        """C_path (paper Eq. 2): a shifted delay score and an inverted
        capacity-class score, fused and normalised to 8 bits."""
        ds = np.minimum(np.asarray(delay_us, np.int64) >> D_SHIFT, SCORE_MAX)
        cls = np.searchsorted(self.cap_thresh, cap_gbps, side="right")
        lc = self.class_score[NUM_CLASSES - 1 - cls]
        return np.minimum((W_DL * ds + W_LC * lc) >> S_PATH, SCORE_MAX)

    def monitor(self, t: int) -> None:
        qc = np.floor(self.q / CELL_BYTES).astype(np.int64)
        self.trend = (self.trend - (self.trend >> EWMA_K)
                      + ((qc - self.queue_cur) >> EWMA_K))
        qlev = (self.q_thresh[None, :] <= qc[:, None]).sum(1)
        self.dur = np.where(qlev >= HIGH_WATER_LEVEL, self.dur + 1,
                            self.dur >> 1)
        self.queue_cur = qc
        tlev = (self.trend_thresh <= self.trend[:, None]).sum(1)
        q_score = self.level_score[qlev]
        t_score = np.where(self.trend > 0, self.level_score[tlev], 0)
        d_score = np.minimum(self.dur >> DUR_SHIFT, SCORE_MAX)
        self.hist_c[:, t % HIST] = np.minimum(
            (W_QL * q_score + W_TL * t_score + W_DP * d_score) >> S_CONG,
            SCORE_MAX)

    def degrade(self, t: int) -> np.ndarray:
        return np.where(t >= self.deg_step, self.deg_factor,
                        self.ft(1.0)).astype(self.ft)

    def control(self, t: int) -> None:
        cfg = self.cfg
        if not cfg.degrade_sched or t % (CTRL_PERIOD_US // cfg.dt_us):
            return
        eff = (self.link_gbps * self.degrade(t)).astype(np.int64)
        pl = self.path_links
        hop_ok = np.arange(self.H)[None, :] < self.path_len[:, None]
        cap = np.where(hop_ok, eff[np.maximum(pl, 0)],
                       np.iinfo(np.int64).max).min(1)
        self.c_path = self.path_quality(self.path_prop, cap)

    def redte_tick(self, t: int) -> None:
        cfg = self.cfg
        if cfg.policy != "redte" or t % (REDTE_PERIOD_US // cfg.dt_us):
            return
        uq = np.clip(self.u_ewma * 256, 0, 255).astype(np.int64)
        first = self.path_first[np.maximum(self.pair_cand, 0)]
        head = np.maximum(256 - uq[first], 1)
        self.redte_w = np.where(self.pair_cand >= 0, head, 0)

    # ----------------------------------------------------------- routing
    def decide(self, t: int, fid: np.ndarray, pair: np.ndarray) -> np.ndarray:
        """The chosen path of each flow of a batch, -1 where none."""
        cand = self.pair_cand[pair]                          # (n, K)
        valid = cand >= 0
        cp = np.maximum(cand, 0)
        n, K = cand.shape
        h = fmix32(fid)
        pol = self.cfg.policy
        if pol in ("lcmp", "lcmp_w"):
            hop = self.path_links[cp]                        # (n, K, H)
            slot = (t - self.sig_delay[cp]) % HIST
            seen = np.where(hop >= 0, self.hist_c[np.maximum(hop, 0), slot],
                            0)
            c_cong = seen.max(-1)
            cost = ALPHA * self.c_path[cp] + BETA * c_cong
            cost = np.where(valid, cost, COST_INVALID)
            order = np.argsort(cost * K + np.arange(K)[None, :], axis=1)
            nv = valid.sum(1)
            keep = np.maximum((nv + KEEP_NUM - 1) // KEEP_NUM, 1)
            if pol == "lcmp":
                rank = (h % keep.astype(np.uint32)).astype(np.int64)
            else:
                w = np.take_along_axis(self.path_gbps[cp], order, 1)
                w = np.where(np.arange(K)[None, :] < keep[:, None],
                             np.maximum(w, 1), 0)
                cum = np.cumsum(w, 1)
                hv = (h >> np.uint32(1)).astype(np.int64) % np.maximum(
                    cum[:, -1], 1)
                rank = (cum <= hv[:, None]).sum(1)
            k = order[np.arange(n), rank]
            all_bad = np.where(valid, c_cong, SCORE_MAX + 1).min(1) \
                >= CONG_FALLBACK
            k = np.where(all_bad, order[:, 0], k)
            k = np.where(nv > 0, k, -1)
        elif pol == "ecmp":
            nv = valid.sum(1)
            order = np.argsort(np.where(valid, 0, 1) * K
                               + np.arange(K)[None, :], axis=1)
            rank = (h % np.maximum(nv, 1).astype(np.uint32)).astype(np.int64)
            k = np.where(nv > 0, order[np.arange(n), rank], -1)
        elif pol == "ucmp":
            cost = 1_000_000 // np.maximum(self.path_gbps[cp], 1)
            cost = np.where(valid, cost, UCMP_INVALID)
            rot = (h % np.uint32(K)).astype(np.int64)
            idx = (np.arange(K)[None, :] + rot[:, None]) % K
            best = np.argmin(np.take_along_axis(cost, idx, 1), axis=1)
            k = np.where(valid.any(1), idx[np.arange(n), best], -1)
        else:                                                # wcmp, redte
            w = self.path_gbps[cp] if pol == "wcmp" else self.redte_w[pair]
            w = np.where(valid, np.maximum(w, 1), 0)
            cum = np.cumsum(w, 1)
            total = cum[:, -1]
            hv = (h >> np.uint32(1)).astype(np.int64) % np.maximum(total, 1)
            k = np.where(total > 0, (cum <= hv[:, None]).sum(1), -1)
        return np.where(k >= 0, cand[np.arange(n), np.maximum(k, 0)], -1)

    def route(self, t: int) -> None:
        idx = self.arrivals[t]
        if not len(idx):
            return
        chosen = self.decide(t, self.f_id[idx], self.f_pair[idx])
        ok = chosen >= 0
        idx, ch = idx[ok], chosen[ok]
        hop = self.path_links[ch]
        hl = np.maximum(hop, 0)
        self.flow_path[idx] = ch
        self.remaining[idx] = self.f_size[idx]
        self.rate[idx] = self.path_cap[ch]
        self.cc_target[idx] = self.path_cap[ch]
        self.active[idx] = True
        self.extra_wait[idx] = self.r(np.where(
            hop >= 0, self.q[hl] / self.link_cap[hl], 0).sum(1).astype(
                self.ft))
        self.rtt[idx] = np.maximum(2 * self.path_prop[ch] // self.cfg.dt_us,
                                   1)
        self.route_step[idx] = t

    # ---------------------------------------------------------------- CC
    def dcqcn(self, t: int, a, li, ok, rate):
        """DCQCN from the RTT-delayed queue of each flow's path: the new
        rate and target of the flows ``a``."""
        cfg, ft = self.cfg, self.ft
        rtt = self.rtt[a]
        slot = (t - rtt) % HIST
        q_sig = np.where(ok, self.hist_q[li, slot[:, None]], 0).max(1)
        q_sig = np.where((t - self.route_step[a]) > rtt, q_sig, 0)
        line = self.path_cap[self.flow_path[a]]
        inv = (1.0 / rtt).astype(ft)
        ai = AI_FRAC * line * inv
        dec_gap = np.minimum(rtt, np.maximum(CC_DEC_PERIOD_US // cfg.dt_us,
                                             rtt // 8))
        can_dec = (t - self.last_dec[a]) >= dec_gap
        kmin = ECN_KMIN_BYTES * cfg.cap_scale
        kmax = ECN_KMAX_FACTOR * kmin
        p_mark = np.clip((q_sig - kmin) / (kmax - kmin), 0.0, 1.0)
        u01 = fmix32(self.f_id[a] ^ np.uint32(t)).astype(ft) * ft(2.0**-32)
        dec = (u01 < p_mark) & can_dec
        target = np.maximum(self.cc_target[a], 0.05 * line)
        target = np.where(dec, rate, target)
        recover = rate + (target - rate) * 0.5 * inv
        probe = np.where(rate >= 0.95 * target, ai, 0.0)
        new_rate = np.where(dec, rate * MD_FACTOR, recover + probe)
        target = np.where(dec, target, target + probe)
        self.last_dec[a] = np.where(dec, t, self.last_dec[a])
        new_rate = np.clip(new_rate, 0.001 * line, line).astype(ft)
        target = np.clip(target, 0.001 * line, line).astype(ft)
        self.rate[a] = self.r(new_rate)
        self.cc_target[a] = self.r(target)
        return self.rate[a]

    # ------------------------------------------------------------- steps
    def links(self, t: int, q, util, served_bytes) -> None:
        r, slot = self.r, t % HIST
        self.q = r(q.astype(self.ft))
        self.hist_q[:, slot] = self.q
        self.hist_u[:, slot] = r(util.astype(self.ft))
        self.u_ewma = r((self.u_ewma * 0.99
                         + 0.01 * np.minimum(util, 1.0)).astype(self.ft))
        self.serv = r((self.serv + served_bytes).astype(self.ft))

    def finish(self, a, done_now, fct) -> None:
        fin = a[done_now]
        self.active[fin] = False
        self.done[fin] = True
        self.fct[fin] = self.r(fct[done_now].astype(self.ft))

    def fluid_step(self, t: int) -> None:
        cfg, ft, L, dt = self.cfg, self.ft, self.L, float(self.cfg.dt_us)
        a = np.flatnonzero(self.active)
        pf = self.flow_path[a]
        lk = self.path_links[pf]
        ok = lk >= 0
        li = np.maximum(lk, 0)
        rate = self.rate[a]
        offered = np.bincount(li[ok], weights=np.broadcast_to(
            rate[:, None], lk.shape)[ok], minlength=L).astype(ft)
        cap = self.link_cap * self.degrade(t)
        factor = np.minimum(1.0, cap / np.maximum(offered, 1e-9))
        q = np.clip(self.q + (offered - cap) * dt, 0.0,
                    BUFFER_BYTES * cfg.cap_scale)
        self.links(t, q, offered / cap, np.minimum(offered, cap) * dt)

        rate = self.dcqcn(t, a, li, ok, rate)
        f_factor = np.where(ok, factor[li], 1.0).min(1)
        rem = self.remaining[a] - rate * f_factor * dt
        done_now = rem <= 0
        qw = np.where(ok, self.q[li] / self.link_cap[li], 0.0).sum(1)
        fct = ((t + 1) * dt - self.f_arr[a] + self.path_prop[pf]
               + 0.5 * (self.extra_wait[a] + qw))
        self.remaining[a] = self.r(np.maximum(rem, 0.0).astype(ft))
        self.finish(a, done_now, fct)

    def packet_step(self, t: int) -> None:
        cfg, ft, L, H = self.cfg, self.ft, self.L, self.H
        r, dt, mtu = self.r, float(cfg.dt_us), float(MTU_BYTES)
        buf = BUFFER_BYTES * cfg.cap_scale

        def seg(idx, vals):
            return np.bincount(idx, weights=vals, minlength=L).astype(ft)

        # PFC XOFF/XON on the queue, into the pause ring
        self.pause = np.where(self.q > PFC_XOFF_FRAC * buf, True,
                              np.where(self.q < PFC_XON_FRAC * buf,
                                       False, self.pause))
        self.hist_pause[:, t % HIST] = self.pause

        a = np.flatnonzero(self.active)
        pf = self.flow_path[a]
        lk = self.path_links[pf]
        ok = lk >= 0
        li = np.maximum(lk, 0)
        rate = self.rate[a]
        fq = self.fq[a]

        # paced injection of whole packets under the rate x RTT window
        win = np.maximum(rate * self.rtt[a].astype(ft) * dt, mtu)
        credit = np.minimum(self.credit[a] + rate * dt, win)
        avail = np.minimum(credit, np.clip(win - fq.sum(1), 0.0, None))
        l0 = li[:, 0]
        avail = np.where(self.pause[l0], 0.0, avail)
        rem = self.remaining[a]
        inject = np.where(rem <= avail, rem, np.floor(avail / mtu) * mtu)
        space = np.clip(buf - self.q, 0.0, None)
        f_inj = np.minimum(1.0, space / np.maximum(seg(l0, inject), 1e-9))
        scaled = inject * f_inj[l0]
        inject = np.where(scaled < inject, np.floor(scaled / mtu) * mtu,
                          inject).astype(ft)
        rem = r((rem - inject).astype(ft))
        credit = r((credit - inject).astype(ft))

        # hop by hop under per-link budgets
        budget = self.link_cap * self.degrade(t) * dt
        fq[:, 0] += inject
        served = np.zeros(L, ft)
        in_l = seg(l0, inject)
        q_now = self.q + in_l
        for h in range(H):
            lh = li[:, h]
            if h + 1 < H:
                nxt = lk[:, h + 1]
                has_next = nxt >= 0
                nc = np.maximum(nxt, 0)
                back = (t - self.link_delay[lh] // cfg.dt_us) % HIST
                paused = self.hist_pause[nc, back] & has_next
            else:
                has_next = np.zeros(len(a), bool)
                nc = lh
                paused = has_next
            send = np.where(ok[:, h] & ~paused, fq[:, h], 0.0)
            f_serv = np.minimum(1.0, np.clip(budget - served, 0.0, None)
                                / np.maximum(seg(lh, send), 1e-9))
            out = send * f_serv[lh]
            f_in = np.minimum(1.0, np.clip(buf - q_now, 0.0, None)
                              / np.maximum(seg(nc, np.where(
                                  has_next, out, 0.0)), 1e-9))
            out = (out * np.where(has_next, f_in[nc], 1.0)).astype(ft)
            fwd = np.where(has_next, out, 0.0).astype(ft)
            fq[:, h] -= out
            if h + 1 < H:
                fq[:, h + 1] += fwd
            s_out, s_fwd = seg(lh, out), seg(nc, fwd)
            served += s_out
            in_l += s_fwd
            q_now = q_now - s_out + s_fwd
        fq = r(fq)
        q = np.bincount(li[ok], weights=fq[ok], minlength=L)
        util = (self.q + in_l) / np.maximum(budget, 1e-9)
        self.links(t, q, util, served)

        rate = self.dcqcn(t, a, li, ok, rate)
        done_now = (rem <= 0.0) & (fq.sum(1) <= 0.0)
        fct = (t + 1) * dt - self.f_arr[a] + self.path_prop[pf]
        self.fq[a] = fq
        self.credit[a] = credit
        self.remaining[a] = rem
        self.finish(a, done_now, fct)

    def run(self) -> SimpleNamespace:
        step = (self.fluid_step if self.cfg.engine == "fluid"
                else self.packet_step)
        for t in range(self.cfg.num_steps):
            self.monitor(t)
            self.control(t)
            self.route(t)
            step(t)
            self.redte_tick(t)
        return SimpleNamespace(done=self.done.copy(),
                               fct_us=self.fct.astype(np.float64),
                               flow_path=self.flow_path.astype(np.int32),
                               serv_bytes=self.serv.astype(np.float64))

    def link_arrays(self) -> SimpleNamespace:
        """What the utilisation score reads of the world."""
        return SimpleNamespace(
            link_cap=self.link_cap, link_fail_step=np.full(self.L, 1 << 30),
            link_deg_step=self.deg_step, link_deg_factor=self.deg_factor)
