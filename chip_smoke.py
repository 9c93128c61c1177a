#!/usr/bin/env python3
"""Chip smoke test: the netsim main path on a TPU, with links at real rates.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: only the paths across chips

One chip runs two phases in this one process:

- main: the ``fig_large`` design point (24-DC ``wan2000`` WAN, main-pair
  foreground at load 0.5 over 0.15 background, seed 9) at ``cap_scale=1.0``
  with 100 ms of traffic (the suite runs 300 ms; on one v5e the engines
  take 23 ms (fluid) and 52 ms (packet) per step there, which puts the
  phase past the script's time budget). Policies lcmp and ecmp on the
  fluid and the packet engine go through ``run_sweep``: 4 cells of ~41k
  flows in 2 compiled groups, batched as a ``lax.map`` over cells. Each
  batched cell must equal ``run_sweep(..., sequential=True)`` bit for bit
  on ``done``, ``fct_us`` and ``flow_path``, complete some flows, and give
  finite FCTs.
- host: the integer decision path (``core.select.select_egress`` and the
  ``core.cong`` monitor) must give the same bits on the TPU as on the
  host CPU; a ``testbed8`` cell (100 ms) on both must agree on completed
  flows and FCT-slowdown p50/p99 within ``HOST_PCT_RTOL``.

``--chips 4`` runs only what spans chips: an 8-cell real-rate grid through
``run_sweep(use_mesh=True, devices=4)`` against the same grid unsharded
(bit for bit), and ``lcmp_pod_reduce`` over a 4-device ``pod`` axis, f32
and int8-compressed, against the exact mean, with the compiled program
checked for the Pallas kernels (``tpu_custom_call``).

The seconds printed are host-clock readings, each ending in
``block_until_ready``; they are informational and claim no speed. The
last stdout line is ``{"ok": true, "device": {...}}``. Without a TPU, or
with any phase failed, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

# the fig_large world: degradation of the fattest main haul at a third of
# the traffic window
DURATION_US = 100_000
TOPOLOGY = (f"wan2000:dcs=24,segs=2,chords=12,deg_ms={DURATION_US // 3000},"
            "deg_factor=0.25")

# chip against host on the testbed8 cell: the completed-flow counts must
# be equal and the FCT-slowdown p50/p99 within this relative difference.
# On a v5e the two gave bit-identical FCT arrays; f32 arithmetic that
# XLA orders differently per backend moved fig_large's p50 by 1e-7
# relative between a chip run and a CPU run, so 1e-3 leaves room for
# such reordering while still catching a wrong result.
HOST_PCT_RTOL = 1e-3


def fig_large(policy, engine, seed=9):
    from repro.netsim.experiment import ExpSpec
    return ExpSpec(topology=TOPOLOGY, policy=policy, engine=engine,
                   duration_us=DURATION_US, pairs="main", load=0.5,
                   bg_load=0.15, seed=seed, cap_scale=1.0)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _same_cells(a, b, what):
    """Bit-for-bit equality of two sweep results, cell by cell."""
    import numpy as np
    for ra, rb in zip(a.results, b.results):
        for field in ("done", "fct_us", "flow_path"):
            _check(np.array_equal(getattr(ra.final, field),
                                  getattr(rb.final, field)),
                   f"{what}: {ra.spec.engine}/{ra.spec.policy}/seed "
                   f"{ra.spec.seed}: {field} differs")


def _cell_line(r):
    import numpy as np
    done = r.final.done
    fct = r.final.fct_us[done]
    _check(done.any(), f"{r.spec.engine}/{r.spec.policy}: no completed flow")
    _check(bool(np.isfinite(fct).all()),
           f"{r.spec.engine}/{r.spec.policy}: non-finite FCT")
    fg = r.stats_fg
    return (f"engine={r.spec.engine} policy={r.spec.policy} "
            f"seed={r.spec.seed} flows={r.flows.num_flows} "
            f"completion={r.stats.completion_rate:.6f} "
            f"fg_p50={fg.p50:.6f} fg_p99={fg.p99:.6f}")


def _stages(rep):
    """A sweep's host seconds per stage, as ``lower:1.234,run:5.678``."""
    return ",".join(f"{k}:{v:.3f}" for k, v in rep.stages.items())


def main_phase(specs_by_engine):
    """Batched twice (first call compiles), then sequential; compare."""
    from repro.netsim.sweep import run_sweep
    for engine, specs in specs_by_engine.items():
        first = run_sweep(specs)
        second = run_sweep(specs)
        t0 = time.perf_counter()
        seq = run_sweep(specs, sequential=True)
        seq_s = time.perf_counter() - t0
        _same_cells(first, seq, "batched vs sequential")
        _same_cells(first, second, "batched, first vs second call")
        for r in first.results:
            print(f"main {_cell_line(r)} group_cells={len(specs)} "
                  f"first_stages={_stages(first)} "
                  f"second_stages={_stages(second)} "
                  f"sequential_s={seq_s:.3f}", flush=True)
    print("main: batched == sequential bit for bit on every cell",
          flush=True)


def _on(device, fn, *args):
    import jax
    import numpy as np
    out = jax.jit(fn)(*jax.device_put(args, device))
    return jax.tree.map(np.asarray, out)


def decision_path_bits(tpu, cpu):
    """select_egress and the cong monitor, same bits on chip and host."""
    import jax
    import numpy as np

    from repro.core import cong, select
    from repro.core.tables import bootstrap_tables
    rng = np.random.default_rng(0)
    F, P = 4096, 8
    args = (rng.integers(0, 1 << 32, F, dtype=np.uint32),
            rng.integers(0, 256, (F, P)).astype(np.int32),
            rng.integers(0, 256, (F, P)).astype(np.int32),
            rng.random((F, P)) < 0.85)
    want = _on(cpu, select.select_egress, *args)
    got = _on(tpu, select.select_egress, *args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _check(np.array_equal(a, b), "select_egress: chip != host")

    n = 1024
    tables = bootstrap_tables([int(r) for r in rng.choice([25, 100, 400], n)],
                              buffer_bytes=6 * 10**9)

    def ticks(queues, tables):
        st = cong.CongState.init(n)
        out = []
        for t, q in enumerate(queues):
            st = cong.monitor_update(st, q, t * 200, tables)
            out.append((st, cong.calc_cong_cost(st, tables)))
        return out
    queues = rng.integers(0, 6 * 10**6, (8, n)).astype(np.int32)
    want = _on(cpu, ticks, queues, tables)
    got = _on(tpu, ticks, queues, tables)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _check(np.array_equal(a, b), "cong monitor: chip != host")
    print(f"host select_egress {F}x{P} and cong monitor {n} ports x 8 "
          "ticks: chip == host bit for bit", flush=True)


def host_phase(specs):
    """A small cell on the chip and on the host CPU, same process."""
    import jax
    import numpy as np

    from repro.netsim.sweep import run_sweep
    tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
    decision_path_bits(tpu, cpu)
    chip = run_sweep(specs)
    with jax.default_device(cpu):
        host = run_sweep(specs)
    for rc, rh in zip(chip.results, host.results):
        name = f"{rc.spec.engine}/{rc.spec.policy}"
        bits = all(np.array_equal(getattr(rc.final, f), getattr(rh.final, f))
                   for f in ("done", "fct_us", "flow_path"))
        sc, sh = rc.stats, rh.stats
        rel = {k: abs(a - b) / abs(b) for k, a, b in (
            ("p50", sc.p50, sh.p50), ("p99", sc.p99, sh.p99))}
        print(f"host {name} flows={rc.flows.num_flows} "
              f"completed chip={sc.completed} host={sh.completed} "
              f"p50 chip={sc.p50:.6f} host={sh.p50:.6f} "
              f"p99 chip={sc.p99:.6f} host={sh.p99:.6f} "
              f"rel_diff={json.dumps(rel)} fct_bit_identical={bits}",
              flush=True)
        _check(rel["p50"] <= HOST_PCT_RTOL and rel["p99"] <= HOST_PCT_RTOL,
               f"{name}: chip and host FCT percentiles differ by more "
               f"than {HOST_PCT_RTOL}")
        _check(sc.completed == sh.completed,
               f"{name}: chip and host completed counts differ")


def sharded_phase(specs, ndev):
    """The grid sharded over ``ndev`` chips == the same grid on one."""
    from repro.netsim.sweep import run_sweep
    sharded = run_sweep(specs, use_mesh=True, devices=ndev)
    plain = run_sweep(specs)
    _same_cells(sharded, plain, f"sharded over {ndev} vs unsharded")
    for r in sharded.results:
        print(f"sharded {_cell_line(r)} "
              f"sharded_stages={_stages(sharded)} "
              f"unsharded_stages={_stages(plain)}", flush=True)
    print(f"sharded: {len(specs)} cells over {ndev} devices == unsharded "
          "bit for bit", flush=True)


def pod_reduce_phase(ndev):
    """lcmp_pod_reduce over a ``pod`` axis against the exact mean."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.dist import lcmp_collectives as lc
    mesh = jax.make_mesh((ndev,), ("pod",), devices=jax.devices()[:ndev])
    rng = np.random.default_rng(0)
    tree = {"w": rng.standard_normal((ndev, 3 * lc.BUCKET_ELEMS + 1000)),
            "b": rng.standard_normal((ndev, lc.BUCKET_ELEMS // 2)) * 3.0}
    tree = {k: v.astype(np.float32) for k, v in tree.items()}
    amax = max(float(np.abs(v).max()) for v in tree.values())
    eps = float(np.finfo(np.float32).eps)
    for compress in (False, True):
        f = jax.jit(jax.shard_map(
            lambda t, c=compress: lc.lcmp_pod_reduce(t, "pod", compress=c),
            mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
            check_vma=False))
        hlo = f.lower(tree).compile().as_text()
        kernels = "tpu_custom_call" in hlo
        out = jax.tree.map(np.asarray, f(jax.tree.map(jnp.asarray, tree)))
        worst = 0.0
        for k, x in tree.items():
            mean = x.astype(np.float64).mean(0)
            err = np.abs(out[k] - mean[None])
            if compress:
                # two quantization steps (tests/test_dist.py's bound)
                bound = 2.1 * amax / 127
            else:
                bound = 4 * eps * np.abs(x).astype(np.float64).mean(0)[None]
            _check(bool((err <= bound).all()),
                   f"pod reduce compress={compress}: {k} off the mean")
            worst = max(worst, float(err.max()))
        print(f"pod_reduce compress={compress} devices={ndev} "
              f"elems/device={sum(v[0].size for v in tree.values())} "
              f"max_abs_err={worst:.3e} tpu_custom_call={kernels}",
              flush=True)
        if compress:
            _check(kernels, "compressed pod reduce: no compiled Pallas "
                   "kernel (tpu_custom_call) in the program")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # the chip-against-host phase needs the CPU backend beside the TPU
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform}); "
              "this script only runs on the chip", file=sys.stderr)
        return 1
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import compile_cache
    from repro.kernels import ops
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    _check(not ops.interpret(), "Pallas kernels would run interpreted")

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase([fig_large(p, "fluid", seed=s)
                       for p in ("lcmp", "ecmp", "wcmp", "ucmp")
                       for s in (9, 10)], 4)
        pod_reduce_phase(4)
    else:
        main_phase({e: [fig_large(p, e) for p in ("lcmp", "ecmp")]
                    for e in ("fluid", "packet")})
        from repro.netsim.experiment import ExpSpec
        host_phase([ExpSpec(topology="testbed8", engine=e,
                            duration_us=100_000) for e in ("fluid", "packet")])
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
