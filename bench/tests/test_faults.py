"""A whole run of each cell, past the harness's look for a chip, on the
CPU at a tiny size: ``correct`` holds on the program as it is, and comes
out false with the timed path broken underneath in each way a cell of
this benchmark can break, a cell on several chips also in the way only
it can."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro import compile_cache
from repro.netsim import fluid, packet, sweep

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]
V5E = harness.load_json(harness.BENCH / "peaks.json")["devices"]["TPU v5 lite"]


class NoCompileCount(harness.CompileCounter):
    """Without a persistent cache on the CPU every unit's new jit
    compiles; the count inside the window is checked on the chip."""

    def snapshot(self):
        return (0, 0, 0)


def _run(monkeypatch, capsys, name):
    monkeypatch.setattr(harness, "chips_or_none",
                        lambda want: jax.devices()[:1])
    monkeypatch.setattr(harness, "device_peaks", lambda kind: V5E)
    monkeypatch.setattr(harness, "CompileCounter", NoCompileCount)
    monkeypatch.setattr(compile_cache, "enable", lambda: "off")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        rc = harness.main(["--workload", name, "--seed", str(2**31 + 7),
                           "--seconds", "0.01", "--trace", "0"],
                          time.perf_counter())
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1])


def _state_unchanged(monkeypatch, cell):
    mod = {"fluid": fluid, "packet": packet}[cell.params["engine"]]
    monkeypatch.setattr(mod, "make_step",
                        lambda ar, cfg: (lambda st, t: (st, None)))


def _wrap_runner(monkeypatch, before=None, after=None):
    orig = sweep._group_runner

    def runner(shared, cfg, mesh=None, mode="vmap"):
        run = orig(shared, cfg, mesh, mode)

        def call(cells, states):
            if before is not None:
                cells = before(cells)
            out = run(cells, states)
            return after(out) if after is not None else out
        return call
    monkeypatch.setattr(sweep, "_group_runner", runner)


def _half_the_flows(monkeypatch, cell):
    """Every odd flow left out of the batch: it never arrives."""
    def drop(cells):
        a = cells.arrivals
        return cells.__class__(**{**vars(cells), "arrivals": jnp.where(
            a % 2 == 1, -1, a)})
    _wrap_runner(monkeypatch, before=drop)


def _answer_altered(monkeypatch, cell):
    """The first cell's completion times 2% late where they are made."""
    def late(final):
        fct = final.fct_us.at[0].multiply(1.02)
        return final.__class__(**{**vars(final), "fct_us": fct})
    _wrap_runner(monkeypatch, after=late)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_flows": _half_the_flows,
          "answer_altered": _answer_altered}
SHARDED = [w["name"] for w in harness.load_benchmark()["workloads"]
           if w["chips"] > 1]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(monkeypatch, capsys, tiny_cells, name):
    line = _run(monkeypatch, capsys, name)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"sim_rate", "setup_s"}
    assert line["metrics"]["sim_rate"]["value"] > 0


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(monkeypatch, capsys, tiny_cells, name,
                                    fault):
    FAULTS[fault](monkeypatch, tiny_cells(name))
    line = _run(monkeypatch, capsys, name)
    assert line["correct"] is False, line["checks"]
    assert line["failed"] > 0


MESH_RUN = """
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import harness
from bench.tests import test_faults
from bench.tests.conftest import tiny
from repro import compile_cache
from repro.netsim import sweep

name, fault = sys.argv[2], sys.argv[3]
load = harness.load_cell
harness.load_cell = lambda *a, **k: tiny(load(*a, **k))
harness.chips_or_none = lambda want: (
    jax.devices()[:want] if len(jax.devices()) >= want else None)
harness.device_peaks = lambda kind: test_faults.V5E
harness.CompileCounter = test_faults.NoCompileCount
compile_cache.enable = lambda: "off"
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
SHARDS = []


def first_chip(x):
    \"\"\"Every chip's block of the sharded answer replaced by the first
    chip's, on its own device: the others' answers never come back.\"\"\"
    shards = sorted(x.addressable_shards, key=lambda sh: sh.index[0].start)
    SHARDS.append(len(shards))
    if fault == "sound":
        return x
    return jax.make_array_from_single_device_arrays(
        x.shape, x.sharding,
        [jax.device_put(shards[0].data, sh.device) for sh in shards])


orig = sweep._group_runner


def runner(shared, cfg, mesh=None, mode="vmap"):
    run = orig(shared, cfg, mesh, mode)
    return lambda cells, states: jax.tree_util.tree_map(
        first_chip, run(cells, states))


sweep._group_runner = runner
rc = harness.main(["--workload", name, "--seed", str(2**31 + 7),
                   "--seconds", "0.01", "--trace", "0"], time.perf_counter())
print(json.dumps({"rc": rc, "shards": sorted(set(SHARDS))}))
"""


@pytest.mark.parametrize("fault", ["sound", "exchange_left_out"])
@pytest.mark.parametrize("name", SHARDED)
def test_broken_exchange_is_not_correct(name, fault):
    """A whole run of a cell on several chips, on as many virtual CPU
    devices, with its grid sharded over them: ``correct`` holds as it
    is, and comes out false where every chip's block of the answer is
    the first chip's."""
    chips = harness.load_cell(name).chips
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    proc = subprocess.run(
        [sys.executable, "-c", MESH_RUN, str(harness.ROOT), name, fault],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    *_, line, end = proc.stdout.strip().splitlines()
    line, end = json.loads(line), json.loads(end)
    assert end == {"rc": 0, "shards": [chips]}
    assert line["device"]["count"] == chips
    assert line["correct"] is (fault == "sound"), line["checks"]
    assert (line["failed"] > 0) is (fault != "sound")
