"""A cell is data alone: every key of its configuration and traffic that
is a field of the program's ``ExpSpec`` reaches each spec of its grid,
the grid runs over policies and seeds, a configuration may name its own
reference, and a cell on several chips shards its grid over them."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

import bench.reference
from bench import harness
from bench.reference import sim

BENCH = harness.load_benchmark()
SEED = 2**31 + 17
WAN = "wan2000:dcs=24,segs=2,chords=12,deg_ms=33,deg_factor=0.25"

# every ExpSpec field, as the program's defaults had it when the three
# cells below were pinned
DEFAULTS = {
    "topology": "testbed8", "workload": "websearch", "load": 0.3,
    "policy": "lcmp", "cc": "dcqcn", "engine": "fluid",
    "duration_us": 1500000, "seed": 0, "pairs": "main", "bg_load": 0.0,
    "load_sched": "", "cap_scale": 0.125, "sig_delay_scale": 1.0,
    "ctrl_period_us": 100000, "flowlet_gap_us": 0, "redecide_period_us": 0,
    "n_subflows": 1, "cosim_model": "", "cosim_cell": "train_4k",
    "cosim_iters": 6, "cosim_compress": 1, "checks": 0, "select": None,
    "pathq": None, "congp": None}
WAN_CELL = {"topology": WAN, "load": 0.5, "bg_load": 0.15, "cap_scale": 1.0,
            "duration_us": 100000, "seed": SEED}
TB8_CELL = {"cap_scale": 1.0, "duration_us": 100000, "seed": SEED}
# the grids of the cells as they stood before a cell could carry any
# ExpSpec key, a seed grid or its own reference
PINNED = {
    "wan24.fluid": [dict(WAN_CELL), dict(WAN_CELL, policy="ecmp")],
    "wan24.packet": [dict(WAN_CELL, engine="packet")],
    "tb8.fluid.5pol": [dict(TB8_CELL, policy=p) for p in
                       ("ecmp", "ucmp", "redte", "lcmp", "lcmp_w")],
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_existing_grids_are_pinned(name):
    cell = harness.load_cell(name)
    got = [dataclasses.asdict(s) for s in harness.cell_specs(cell, SEED)]
    assert got == [dict(DEFAULTS, **over) for over in PINNED[name]]
    assert cell.reference == "sim" and cell.num_cells == len(got)


def _bench(tmp_path, traffic, config=None, chips=1):
    """A checkout in ``tmp_path`` with one more cell, ``new``, made of
    data files alone: its traffic, its limits and (where given) a
    configuration of its own."""
    for d in ("configs", "limits", "traffic"):
        shutil.copytree(harness.BENCH / d, tmp_path / "bench" / d)
    (tmp_path / "bench" / "traffic" / "new.json").write_text(
        json.dumps(traffic))
    shutil.copy(harness.BENCH / "limits" / "tb8.fluid.5pol.json",
                tmp_path / "bench" / "limits" / "new.json")
    bench = json.loads(json.dumps(BENCH))
    conf = "testbed8"
    if config is not None:
        conf = config["name"]
        (tmp_path / "bench" / "configs" / f"{conf}.json").write_text(
            json.dumps(config))
        bench["configs"].append({"name": conf, "source": "a test",
                                 "file": f"bench/configs/{conf}.json",
                                 "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "new", "config": conf,
                               "traffic": "new", "chips": chips,
                               "why": "added by data alone"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


TRAFFIC = {"engine": "fluid", "policies": ["ecmp", "wcmp"],
           "workload": "websearch", "pairs": "main", "load": 0.3}


def test_a_spec_key_and_a_seed_grid_by_data_alone(tmp_path):
    _bench(tmp_path, dict(TRAFFIC, seeds=2, ctrl_period_us=50000))
    cell = harness.load_cell("new", root=tmp_path)
    assert cell.num_cells == 4
    assert cell.sim_ms_per_unit() == 4 * 2 * 100
    specs = harness.cell_specs(cell, 5)
    assert [(s.policy, s.seed) for s in specs] == [
        ("ecmp", 5), ("wcmp", 5), ("ecmp", 6), ("wcmp", 6)]
    assert cell.grid(5) == [(s.policy, s.seed) for s in specs]
    assert all(s.ctrl_period_us == 50000 and s.topology == "testbed8"
               for s in specs)
    # the configuration's metadata stays out of the spec
    assert "suite" not in cell.params and "name" not in cell.params


@pytest.mark.parametrize("key,value", [("burst_us", 40), ("policy", "lcmp"),
                                       ("seed", 3), ("duration_us", 2000)])
def test_a_traffic_key_outside_the_spec_or_the_grid_is_refused(
        tmp_path, key, value):
    _bench(tmp_path, dict(TRAFFIC, **{key: value}))
    with pytest.raises(KeyError, match=key):
        harness.load_cell("new", root=tmp_path)


def test_the_reference_refuses_a_key_it_does_not_model():
    cell = harness.load_cell("tb8.fluid.5pol")
    params = {k: v for k, v in cell.params.items()
              if k not in harness.GRID_KEYS}
    with pytest.raises(ValueError, match=r"\['load_sched'\]"):
        sim.run_cell(dict(params, load_sched="diurnal:amp=0.8"),
                     "lcmp", SEED)


def test_a_configuration_names_its_reference(tmp_path, monkeypatch):
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    (ref_dir / "stubref.py").write_text(textwrap.dedent("""\
        CALLS = []

        def run_cell(cell, policy, seed, precision):
            CALLS.append((cell["topology"], "policies" in cell or
                          "seeds" in cell, policy, seed, precision))
            return policy, seed
        """))
    monkeypatch.setattr(bench.reference, "__path__",
                        [*bench.reference.__path__, str(ref_dir)])
    config = dict(harness.load_json(harness.BENCH / "configs" /
                                    "testbed8.json"),
                  name="tb8-own-ref", reference="stubref")
    _bench(tmp_path, dict(TRAFFIC, seeds=2), config=config)
    cell = harness.load_cell("new", root=tmp_path)
    assert cell.reference == "stubref" and "reference" not in cell.params
    got = harness.reference_cells(cell, 9, "bfloat16")
    assert got == cell.grid(9)
    stubref = sys.modules.pop("bench.reference.stubref")
    # the grid's keys stay in the harness: a reference sees one cell
    assert stubref.CALLS == [("testbed8", False, p, s, "bfloat16")
                             for p, s in cell.grid(9)]


@pytest.mark.parametrize("chips,want", [(1, {}),
                                        (4, {"use_mesh": True,
                                             "devices": 4})])
def test_the_mesh_follows_chips(monkeypatch, chips, want):
    from repro.netsim import sweep
    seen = []

    def run_sweep(specs, **kw):
        seen.append(kw)
        return SimpleNamespace(build_s=0.0, results=[])
    monkeypatch.setattr(sweep, "run_sweep", run_sweep)
    cell = dataclasses.replace(harness.load_cell("wan24.fluid"), chips=chips)
    harness.run_unit(cell, harness.cell_specs(cell, SEED))
    assert seen == [want]


SHARDED = """
import dataclasses, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import harness
from bench.tests.conftest import tiny
cell = tiny(harness.load_cell("wan24.fluid.x4"))
specs = harness.cell_specs(cell, 2**31 + 77)
four, _ = harness.run_unit(cell, specs)
one, _ = harness.run_unit(dataclasses.replace(cell, chips=1), specs)
print(json.dumps([len(jax.devices()), cell.chips, four.prints, one.prints]))
"""


def test_the_sharded_unit_equals_the_grid_on_one_chip():
    """Four virtual CPU devices: the cell's grid, sharded two cells to a
    device through the harness's unit function, against the same grid
    run unsharded."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SHARDED, str(harness.ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    ndev, chips, four, one = json.loads(proc.stdout.strip().splitlines()[-1])
    assert ndev == chips == 4
    assert len(four) == 8 and four == one
