"""A four-chip cell enters the benchmark by new files and appended entries
alone, as the mesh planner's cell will: a configuration that sets
``balancer_mesh`` "auto", a plane on ``planes/native.py``'s warm-step
seam that hands ``run.py`` its own solve check, and a ``workloads`` entry
with ``chips`` 4 under the contract's rule. Rehearsed here over a
stand-in plane (no world, no chip), with the controls that must fail."""

import json
import os
import re
import shutil
import types

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.reference import greedy
from benchmarks.spec import ROOT, Spec, SpecError
from test_bench_spec import add_standin, copy_of_benchmark, files_under

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "standin-mesh4.bulk"

#: what ``planes/native.py::run`` returned before it had a warm-step seam
NATIVE_KEYS = {"device", "facts", "flight", "client_rcs", "world_s",
               "t_world", "solve_inputs", "solve_got", "pad_prio",
               "trace_dir", "trace_window_s"}


def add_fourchip(root: str, **config) -> str:
    """After ``add_standin``, add a four-chip stand-in cell as new files and
    appended entries only: its configuration, its plane (a wrapper of the
    stand-in plane, as a mesh plane wraps the native one) and its cell on
    the committed ``bulk`` mix. ``config`` overrides the configuration's
    keys. Returns the cell's name."""
    add_standin(root)
    bench = os.path.join(root, "benchmarks")
    before = files_under(bench)
    doc_config = {
        "name": "standin-mesh4", "source": "tests", "plane": "standin_mesh",
        "app_ranks": 9, "servers": 8, "types": [1], "work_us": 20000,
        "fetch_batch": 1, "warm_s": 1.0, "fed_warm_s": 1.0,
        "solve_shape": [64, 16], "fault": "none",
        "config": {"balancer": "tpu", "balancer_mesh": "auto",
                   "balancer_auction": "device"},
    }
    doc_config.update(config)
    with open(os.path.join(bench, "configs", "standin-mesh4.json"),
              "w") as f:
        json.dump(doc_config, f)
    shutil.copy(os.path.join(HERE, "standin_mesh_plane.py"),
                os.path.join(bench, "planes", "standin_mesh.py"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "standin-mesh4", "source": "tests",
                           "file": "benchmarks/configs/standin-mesh4.json",
                           "reduced": [], "why": "a four-chip stand-in"})
    doc["workloads"].append({"name": CELL, "config": "standin-mesh4",
                             "traffic": "bulk", "chips": 4,
                             "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    after = files_under(bench)
    for path, content in before.items():  # nothing that was there changed
        assert after[path] == content, path
    return CELL


def run_cell(root: str, cell: str = CELL) -> dict:
    """``run.child`` over the cell: everything a run does after the look
    for a chip."""
    args = types.SimpleNamespace(workload=cell, seed=2**31 + 47,
                                 seconds=2.0, trace=0, t0=0.0)
    os.makedirs(bench_run.scratch_dir(root, cell))
    assert bench_run.child(args, root=root) == 0
    with open(os.path.join(bench_run.scratch_dir(root, cell),
                           "result.json")) as f:
        return json.load(f)


def test_a_fourchip_cell_enters_by_new_files_and_runs_correct(tmp_path):
    root = copy_of_benchmark(tmp_path)
    cell = add_fourchip(root)
    spec = Spec(root)
    spec.check_files()
    spec.check_chips()
    assert spec.cell(cell)["chips"] == 4
    assert spec.plane(cell).__name__ == "benchmarks.planes.standin_mesh"
    result = run_cell(root)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert result["compared"]["solve_mismatch"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"units_per_s", "worker_fed_pct",
                                      "setup_s"}


@pytest.mark.parametrize("control,config,refusal", [
    # the device program's answer altered in one entry
    ("solve_altered", {"fault": "solve_altered"}, None),
    # the planner kept its table on one chip of the cell's four
    ("one_table_device", {"table_devices": 1},
     "did not spread its table over the cell's 4 chips"),
    # the plane handed over no solve check: no silent 0
    ("no_solve_check", {"solve_check": False}, "no solve check"),
    # a second four-chip cell in a benchmark of two cells
    ("second_fourchip_cell", {}, "at most 1 may be"),
    # a four-chip cell whose planner is not told to use the mesh
    ("mesh_off", {"config": {"balancer": "tpu", "balancer_mesh": "off"}},
     "not 'auto'"),
])
def test_each_control_of_the_fourchip_cell_is_refused(tmp_path, control,
                                                      config, refusal):
    root = copy_of_benchmark(tmp_path)
    add_fourchip(root, **config)
    if control == "second_fourchip_cell":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            doc = json.load(f)
        second = {"name": "standin-mesh4.flood2", "config": "standin-mesh4",
                  "traffic": "flood2", "chips": 4, "why": "test"}
        doc["workloads"].append(second)
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(doc, f)
        Spec(root).check_chips()  # two of nine cells on four chips is fine
        doc["workloads"] = [w for w in doc["workloads"] if w["chips"] == 4]
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(doc, f)
        with pytest.raises(SpecError, match=refusal):
            Spec(root).check_chips()
    elif control == "mesh_off":
        with pytest.raises(SpecError, match=refusal):
            Spec(root).check_chips()
    elif refusal is None:
        result = run_cell(root)
        assert result["correct"] is False
        assert result["compared"]["solve_mismatch"] == {"value": 1,
                                                        "limit": 0}
    else:
        with pytest.raises(SystemExit, match=refusal):
            run_cell(root)
        assert not os.path.exists(os.path.join(
            bench_run.scratch_dir(root, CELL), "result.json"))


def small_check():
    inputs = greedy.seeded_snapshot(3, 64, 16, 1, -(2**31) + 1)
    return inputs, greedy.greedy_assign(*inputs, -(2**31) + 1)


@pytest.mark.parametrize("form,value", [
    ("dense", 0),
    ("dense_one_off", 1),
    ("arrays", 0),
    ("arrays_one_off", 1),
    ("callable_want", 0),
    ("shapes_differ", "not two integer arrays of one shape"),
    ("float_got", "not two integer arrays of one shape"),
    ("both", "both forms of solve check"),
    ("neither", "no solve check"),
])
def test_the_solve_check_is_counted_in_either_form_or_ends_the_run(form,
                                                                   value):
    inputs, want = small_check()
    got = want.copy()
    if form.endswith("one_off"):
        got[int(np.flatnonzero(got >= 0)[0])] = -1
    dense = {"solve_inputs": inputs, "solve_got": got,
             "pad_prio": -(2**31) + 1}
    check = {"solve_check": {"want": want, "got": got}}
    rec = {
        "dense": dense, "dense_one_off": dense, "arrays": check,
        "arrays_one_off": check,
        "callable_want": {"solve_check": {"want": lambda: want, "got": got}},
        "shapes_differ": {"solve_check": {"want": want, "got": got[:-1]}},
        "float_got": {"solve_check": {"want": want,
                                      "got": got.astype(np.float32)}},
        "both": {**dense, **check}, "neither": {},
    }[form]
    if isinstance(value, int):
        assert bench_run.solve_mismatch(rec) == value
    else:
        with pytest.raises(SystemExit, match=value):
            bench_run.solve_mismatch(rec)


@pytest.mark.parametrize("chips,table_devices,passes", [
    (1, None, True),    # a one-chip planner reports no table_devices
    (4, 4, True),
    (4, 1, False),
    (4, None, False),
])
def test_the_planner_is_held_to_the_cells_chips(chips, table_devices,
                                                passes):
    facts = {"platform": "tpu", "host_solves": 0, "device_failures": 0,
             "device_solves": 3}
    if table_devices is not None:
        facts["table_devices"] = table_devices
    if passes:
        bench_run.check_planner(facts, chips)
    else:
        with pytest.raises(SystemExit, match="did not spread its table"):
            bench_run.check_planner(facts, chips)


def mesh_warm(ctx):
    """A sibling's warm step: its own solve check, its own shape."""
    want = np.arange(8, dtype=np.int32)
    return ({"solve_check": {"want": want, "got": want.copy()}},
            "8 rows on 4 shards", 0.25)


@pytest.mark.parametrize("sibling", [False, True])
def test_the_native_plane_runs_either_warm_step_through_one_seam(
        tmp_path, monkeypatch, sibling):
    """``planes/native.py::run`` with everything that needs a chip or a
    world faked: the default warm step hands over what the plane handed
    over before the seam, under the same set-up line; a sibling's warm
    step hands over its own check through the same run."""
    import adlb_tpu.native.capi as capi

    spec = Spec(ROOT)
    cell = "hotspot-native-n64.bulk"
    plane = spec.plane(cell)
    config = spec.config(cell)
    facts = {"platform": "tpu", "host_solves": 0, "device_failures": 0,
             "device_solves": 3}
    calls = []
    monkeypatch.setattr(plane, "require_tpu", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    inputs, want = small_check()
    monkeypatch.setattr(plane, "warm_solve", lambda config, seed: (
        calls.append(("warm_solve", seed)) or (inputs, want, -(2**31) + 1,
                                               0.125)))
    monkeypatch.setattr(capi, "build_example", lambda src: (
        calls.append(("build_example", os.path.basename(src))) or "exe"))

    def world(n_clients, nservers, **_kw):
        calls.append(("run_native_world", n_clients, nservers))
        return ([(0, "", "")] * n_clients,
                {n_clients + nservers: {"solver": facts}})

    monkeypatch.setattr(capi, "run_native_world", world)
    said = []
    ctx = types.SimpleNamespace(
        config=config, mix=spec.traffic(cell), seed=9, seconds=2.0,
        trace=False, chips=1, scratch=str(tmp_path), logdir=str(tmp_path),
        plan_path=str(tmp_path / "plan.bin"), say=said.append)
    rec = plane.run(ctx, warm=mesh_warm) if sibling else plane.run(ctx)
    if sibling:
        assert set(rec) == NATIVE_KEYS - {"solve_inputs", "solve_got",
                                          "pad_prio"} | {"solve_check"}
        shape = r"8 rows on 4 shards 0\.25s"
    else:
        assert set(rec) == NATIVE_KEYS
        assert rec["solve_got"] is want and rec["pad_prio"] == -(2**31) + 1
        shape = r"64x16 0\.12s"
    assert [c[0] for c in calls] == (["warm_solve"] * (not sibling)
                                     + ["build_example", "run_native_world"])
    assert re.fullmatch(rf"set-up: backend \d+\.\d\ds, warm solve {shape} "
                        r"\(of \d+\.\d\ds with its inputs\), client build "
                        r"\d+\.\d\ds", said[0])
    assert rec["facts"] is facts and rec["client_rcs"] == [0] * 64
    assert bench_run.solve_mismatch(rec) == 0
