"""The harness is driven by data: a configuration, a traffic mix, a cell,
a plane and a per-layer metric are each added as new files and entries,
with no edit to a file that was there; names and units are held to the
characters the contract allows."""

import json
import os
import shutil

import pytest

from benchmarks.spec import ROOT, Spec, SpecError

HERE = os.path.dirname(os.path.abspath(__file__))


def copy_of_benchmark(tmp_path) -> str:
    root = str(tmp_path / "tree")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def files_under(bench: str) -> dict:
    """Path -> content of every file under ``bench``."""
    found = {}
    for d, _dirs, files in os.walk(bench):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                found[path] = fh.read()
    return found


def add_standin(root: str, fault: str = "none", app_ranks: int = 9,
                work_us: int = 20000) -> str:
    """Add, as new files and entries only: a configuration, a traffic mix,
    a plane, a per-layer metric and the cell that uses them. Returns the
    cell's name."""
    bench = os.path.join(root, "benchmarks")
    before = files_under(bench)
    config = {
        "name": "standin-n8", "source": "tests", "plane": "standin",
        "app_ranks": app_ranks, "servers": 2, "types": [1],
        "work_us": work_us, "fetch_batch": 1, "warm_s": 1.0,
        "fed_warm_s": 1.0, "solve_shape": [64, 16], "fault": fault,
        "config": {},
    }
    with open(os.path.join(bench, "configs", "standin-n8.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "flood2.json"), "w") as f:
        json.dump({"put_routing": "home", "needs_backlog": False,
                   "work_mult": [[1.0, 0.5], [2.0, 0.5]]}, f)
    shutil.copy(os.path.join(HERE, "standin_plane.py"),
                os.path.join(bench, "planes", "standin.py"))
    with open(os.path.join(bench, "metrics", "units_planned.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run['logs'].units))\n")
    with open(os.path.join(bench, "metrics", "nothing_to_read.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "standin-n8", "source": "tests",
                           "file": "benchmarks/configs/standin-n8.json",
                           "reduced": [], "why": "a stand-in"})
    cell = "standin-n8.flood2"
    doc["workloads"].append({"name": cell, "config": "standin-n8",
                             "traffic": "flood2", "chips": 1, "why": "test"})
    for name in ("units_planned", "nothing_to_read"):
        doc["per_layer"].append(
            {"name": name, "unit": "units", "better": "higher",
             "source": "program_counter", "layer": "traffic",
             "moves": "units_per_s", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    after = files_under(bench)
    for path, content in before.items():  # nothing that was there changed
        assert after[path] == content, path
    return cell


def in_order(wanted: list, names: list) -> bool:
    """Every name of ``wanted`` is in ``names``, in this order; whatever a
    later cell or entry adds before, between or behind them is no matter."""
    rest = iter(names)
    return all(name in rest for name in wanted)


def test_the_committed_benchmark_resolves_every_cell():
    spec = Spec(ROOT)
    spec.check_files()
    assert spec.cells()[0] == "hotspot-native-n128.bulk"
    for cell in spec.cells():
        names = [m["name"] for m in spec.metrics("end_to_end", cell)]
        assert {"units_per_s", "worker_fed_pct", "setup_s"} <= set(names)
        assert spec.metrics("per_layer", cell)
    spec.check_chips()  # the contract's rule, which a four-chip cell meets


def test_new_cells_and_metrics_are_found_by_name_without_an_edit(tmp_path):
    root = copy_of_benchmark(tmp_path)
    cell = add_standin(root)
    spec = Spec(root)
    spec.check_files()
    assert cell in spec.cells()
    assert spec.config(cell)["plane"] == "standin"
    assert spec.traffic(cell)["work_mult"] == [[1.0, 0.5], [2.0, 0.5]]
    assert spec.plane(cell).__name__ == "benchmarks.planes.standin"
    listed = [m["name"] for m in spec.metrics("per_layer", cell)]
    assert "units_planned" in listed and "plan_round_ms" not in listed
    # the old cells do not report the new cell's metric
    old = [m["name"] for m in spec.metrics("per_layer", spec.cells()[0])]
    assert "units_planned" not in old and "plan_round_ms" in old
    assert spec.reader("units_planned")({"logs": type(
        "L", (), {"units": [1, 2, 3]})}) == 3.0


def test_a_missing_file_is_named(tmp_path):
    root = copy_of_benchmark(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"].append({"name": "hotspot-native-n64.absent",
                             "config": "hotspot-native-n64",
                             "traffic": "absent", "chips": 1, "why": "x"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    spec = Spec(root)
    with pytest.raises(SpecError, match="absent"):
        spec.traffic("hotspot-native-n64.absent")
    with pytest.raises(SpecError, match="no workload"):
        spec.cell("nope")
    with pytest.raises(SpecError, match="no reader"):
        spec.reader("never_written")


@pytest.mark.parametrize("where,value", [
    ("metric_name", "units per s"),
    ("metric_name", "units,s"),
    ("metric_name", "a/b"),
    ("metric_name", "x" * 65),
    ("metric_name", "µs_wait"),
    ("unit", "tokens per second"),
    ("unit", "µs"),
    ("unit", "u" * 17),
    ("unit", ""),
    ("cell_name", "n128 bulk"),
    ("traffic", "bulk/2"),
    ("better", "faster"),
])
def test_names_and_units_outside_the_allowed_characters_are_refused(
        tmp_path, where, value):
    root = copy_of_benchmark(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    if where == "metric_name":
        doc["per_layer"][0]["name"] = value
    elif where == "unit":
        doc["end_to_end"][0]["unit"] = value
    elif where == "cell_name":
        doc["workloads"][0]["name"] = value
    elif where == "traffic":
        doc["workloads"][0]["traffic"] = value
    else:
        doc["end_to_end"][0]["better"] = value
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    with pytest.raises(SpecError):
        Spec(root)


@pytest.mark.parametrize("name", ["units/s", "%", "solves/s", "ms", "GB/s",
                                  "units"])
def test_allowed_units_pass(name):
    from benchmarks.spec import UNIT
    assert UNIT.match(name)


def test_the_committed_file_keeps_to_the_contracts_limits():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    doc = json.loads(text)
    assert len(text) <= 64 * 1024
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
        assert all(1 <= len(c[k]) <= 200 for k in ("source", "why"))
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert all(key in held for key in c["reduced"])
        assert held["guarantees"] and held["assumed"]
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in doc["workloads"]}
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["name"].endswith("_roofline")
