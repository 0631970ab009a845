"""The Python plane's benchmark files: a small world through the functions
``planes/python.py`` launches and collects with, judged against the plain
reference; the records ``traffic/window_app.py`` writes; the new readers'
arithmetic; and the committed configuration. CPU, no chip: the planner
stays on its numpy twin, as tier-1's forked ``balancer="tpu"`` worlds do.
"""

import json
import math
import os
import types

import numpy as np
import pytest

from benchmarks import control
from benchmarks.metrics import (master_planner_busy_pct, master_ship_ms,
                                reactor_busy_pct)
from benchmarks.planes import python as plane
from benchmarks.reduce import hostspans, records
from benchmarks.reduce.window import Window
from benchmarks.reference import compare, pool
from benchmarks.spec import ROOT, Spec
from benchmarks.traffic import window_app
from benchmarks.traffic.generate import make_plan

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "hotspot-py-n64.bulk"

SMALL = {
    "app_ranks": 6, "servers": 3, "types": [1], "work_us": 5000,
    "fetch_batch": 4, "warm_s": 1.0, "fed_warm_s": 1.0,
    "config": {"balancer": "tpu", "balancer_max_tasks": 2048,
               "balancer_max_requesters": 256, "balancer_mesh": "off",
               "exhaust_check_interval": 0.2,
               "on_worker_failure": "abort", "on_server_failure": "abort"},
}


@pytest.fixture(scope="module")
def small_world(tmp_path_factory):
    """Six app ranks and three servers under ``spawn_world``, launched and
    collected by the plane's own functions."""
    from adlb_tpu.runtime.transport_tcp import probe_free_ports

    scratch = tmp_path_factory.mktemp("py-plane")
    logdir = scratch / "logs"
    logdir.mkdir()
    mix = Spec(ROOT).traffic(CELL)
    seed, seconds = 2**31 + 17, 2.0
    plan = make_plan(SMALL, mix, seed, seconds)
    plan.tofile(scratch / "plan.bin")
    flight_dir = str(scratch / "flight")
    cfg = plane.world_config(SMALL, mix, flight_dir, probe_free_ports(1)[0])
    res = plane.launch(SMALL, mix, str(scratch / "plan.bin"), str(logdir),
                       seconds, cfg)
    return types.SimpleNamespace(
        plan=plan, seconds=seconds, logs=records.read_logs(str(logdir)),
        got=plane.collect(SMALL, res, flight_dir), cfg=cfg)


def test_a_small_python_world_agrees_with_the_plain_reference(small_world):
    w = small_world
    assert w.cfg.put_routing == "home" and w.cfg.ops_port is not None
    assert w.got["client_rcs"] == [0] * SMALL["app_ranks"]
    numbers = compare.compare(pool.deliveries(w.plan), w.logs,
                              w.got["client_rcs"], 0, len(w.plan))
    assert compare.verdict(numbers) is True
    assert all(numbers[name] == 0 == limit
               for name, limit in compare.LIMITS.items())
    assert len(w.logs.units) == len(w.plan) == 3000
    window = Window(w.logs, w.seconds, SMALL["app_ranks"] - 1,
                    SMALL["servers"], True)
    assert window.t_end == float(w.logs.producer["t_end"])
    assert 0.0 <= window.worker_blocked_pct <= 100.0
    # every worker fetched in batches and logged its last, empty fetch
    assert window.fetch_calls and set(w.logs.fetch_rank) == {1, 2, 3, 4, 5}
    assert int(w.logs.units["work_us"].max()) == SMALL["work_us"]


def test_the_small_world_leaves_what_the_readers_need(small_world):
    got = small_world.got
    facts = got["facts"]
    assert facts["path"] == "numpy" and facts["memory_peak_bytes"] == 0
    # the master's registry at a normal end, as the sidecar leaves its own
    hists = got["flight"]["metrics"]["histograms"]
    for name in ("balancer_round_s", "span_s{name=adlb.master.wait}",
                 "span_s{name=adlb.round}"):
        assert hists[name]["count"] > 0, name
    assert got["flight"]["reason"] == "exit"
    master = str(SMALL["app_ranks"])
    assert set(got["servers"]) == {"6", "7", "8"}
    for stats in got["servers"].values():
        assert 0.0 < stats["reactor_busy_s"] < stats["reactor_loop_s"]
        by_second = stats["reactor_busy_by_second"]
        assert sum(by_second.values()) == pytest.approx(
            stats["reactor_busy_s"])
        # a turn that straddles a second is split: none reads over one
        assert max(by_second.values()) <= 1.0 + 1e-9
    # every put enters the producer's home server: it is the busy one
    busy = {r: s["reactor_busy_s"] for r, s in got["servers"].items()}
    assert max(busy, key=busy.get) == master


@pytest.mark.parametrize("guarantee,number", [
    ("at_least_once", "duplicated_units"),
    ("at_most_once", "missing_units"),
    ("altered", "altered_units"),
])
def test_each_control_is_not_correct_at_the_cells_own_size(guarantee, number):
    out = control.judge(CELL, seed=2**31 + 5, seconds=2.0,
                        guarantee=guarantee)
    warm_s = Spec(ROOT).config(CELL)["fed_warm_s"]
    assert out["units"] == math.ceil(63 / 0.05 * (warm_s + 2.0))
    assert out["correct"] is False
    assert out["compared"][number]["value"] > 0
    sound = control.judge(CELL, seed=4, seconds=2.0, guarantee="exactly_once")
    assert sound["correct"] is True


# ------------------------------------------------ the records, byte for byte


class CannedContext:
    """Stands in for ``AdlbContext``: hands out canned batches, takes puts."""

    def __init__(self, rank: int, batches=()):
        self.rank = rank
        self.batches = list(batches)
        self.put_payloads = []

    def get_work_batch(self, _types, max_units):
        from adlb_tpu.types import ADLB_DONE_BY_EXHAUSTION, ADLB_SUCCESS

        if not self.batches:
            return ADLB_DONE_BY_EXHAUSTION, []
        batch = self.batches.pop(0)
        assert len(batch) <= max_units
        return ADLB_SUCCESS, [types.SimpleNamespace(payload=p) for p in batch]

    def iput(self, payload, _work_type):
        from adlb_tpu.types import ADLB_SUCCESS

        self.put_payloads.append(payload)
        return ADLB_SUCCESS

    def flush_puts(self):
        from adlb_tpu.types import ADLB_SUCCESS

        return ADLB_SUCCESS


def fixture_units() -> np.ndarray:
    units = np.zeros(5, dtype=records.PAYLOAD)
    units["id"] = [2**40 + 3, 7, 2**40 + 1, 9, 11]
    units["t_put"] = [1.5, 2.5, 3.5, 4.5, 5.5]
    units["t_end"] = 0.25  # long past: no unit sleeps
    units["work_us"] = 50000
    units["tag"] = [2**32 - 1, 0, 17, 2**31, 5]
    return units


def write_with(writer: str, logdir: str) -> None:
    units = fixture_units()
    if writer == "records":  # the numpy writer the reference pool uses
        rows = np.zeros(5, dtype=records.UNIT)
        for name in records.PAYLOAD.names:
            rows[name] = units[name]
        fetches = np.zeros(3, dtype=records.FETCH)
        fetches["n_got"], fetches["rc"] = [4, 1, 0], [1, 1, -999999998]
        records.write_worker_log(logdir, 3, rows, fetches)
        records.write_producer_log(logdir, 5, 1.0, 2.0, 0.25)
    else:  # the Python traffic client's own writer
        payloads = [u.tobytes() for u in units]
        ctx = CannedContext(3, [payloads[:4], payloads[4:]])
        assert window_app.consume(ctx, logdir, batch=4) == 0
        plan = np.zeros(5, dtype=records.PLAN)
        plan["id"], plan["work_us"] = units["id"], units["work_us"]
        plan["tag"] = units["tag"]
        plan.tofile(os.path.join(logdir, "plan.bin"))
        producer = CannedContext(0)
        assert window_app.produce(producer, os.path.join(logdir, "plan.bin"),
                                  logdir, warm_s=1.0, seconds=2.0,
                                  flush_every=2) == 0
        assert len(producer.put_payloads) == 5


@pytest.mark.parametrize("writer", ["records", "window_app"])
def test_both_writers_records_read_back_the_same(tmp_path, writer):
    write_with(writer, str(tmp_path))
    logs = records.read_logs(str(tmp_path))
    want = fixture_units()
    assert len(logs.units) == 5 and set(logs.unit_rank) == {3}
    for name in ("id", "t_put", "work_us", "tag"):
        assert logs.units[name].tolist() == want[name].tolist(), name
    assert logs.fetches["n_got"].tolist() == [4, 1, 0]
    assert logs.fetches["rc"].tolist() == [1, 1, -999999998]
    assert int(logs.producer["n_acked"]) == 5
    for path, dtype in (("w3.units", records.UNIT), ("w3.fetch",
                                                     records.FETCH),
                        ("p0.bin", records.PRODUCER)):
        assert os.path.getsize(tmp_path / path) % dtype.itemsize == 0
    if writer == "window_app":
        # the unit rides whole in its record, then the three times
        raw = (tmp_path / "w3.units").read_bytes()
        assert raw[:32] == want[0].tobytes() and len(raw) == 5 * 56
        assert (logs.units["t_call"] <= logs.units["t_ret"]).all()
        assert (logs.units["t_ret"] <= logs.units["t_done"]).all()
        assert logs.units["t_end"].tolist() == [0.25] * 5
        t_first, t_end = np.fromfile(tmp_path / "p0.start", dtype="<f8")
        assert t_end == pytest.approx(t_first + 3.0)
        assert float(logs.producer["t_end"]) == t_end


def test_a_payload_of_another_length_is_logged_as_altered(tmp_path):
    ctx = CannedContext(2, [[b"short", fixture_units()[0].tobytes()]])
    assert window_app.consume(ctx, str(tmp_path), batch=4) == 0
    logs = records.read_logs(str(tmp_path))
    assert logs.units["id"].tolist() == [-1, 2**40 + 3]


# ------------------------------------------------------- the plane itself


def test_the_plane_stays_off_jax_and_refuses_a_program_without_the_request(
        monkeypatch):
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from benchmarks.planes import python; "
         "from benchmarks.traffic import window_app; "
         "import adlb_tpu.api, adlb_tpu.runtime.transport_tcp; "
         # what every server rank imports: only the planner's host goes
         # on to balancer.solve, and with it to JAX
         "import adlb_tpu.runtime.server, adlb_tpu.balancer.ledger; "
         "python.require_facility(); print('jax' in sys.modules)" % ROOT],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "False", out.stderr
    monkeypatch.setattr(plane.importlib.util, "find_spec", lambda name: None)
    with pytest.raises(SystemExit, match="no world was started"):
        plane.run(types.SimpleNamespace())


# -------------------------------------------------------------- the readers


def test_reactor_busy_pct_takes_the_home_servers_seconds_in_the_window(
        tmp_path):
    cell_dir = tmp_path / ".bench_scratch" / CELL
    cell_dir.mkdir(parents=True)
    run = {"bench_dir": str(tmp_path / "benchmarks"), "cell": CELL,
           "config": {"app_ranks": 64, "servers": 16},
           "window": types.SimpleNamespace(t0=99.5, t_end=103.5)}
    assert reactor_busy_pct.read(run) is None  # a program without it
    by_second = {"98": 1.0, "99": 1.0, "100": 0.5, "101": 0.25, "102": 0.75,
                 "103": 1.0}
    (cell_dir / "servers.json").write_text(json.dumps({
        "64": {"reactor_busy_by_second": by_second},
        "65": {"reactor_busy_by_second": {"100": 1.0, "101": 1.0}}}))
    # whole seconds inside [99.5, 103.5]: 100, 101, 102
    assert reactor_busy_pct.read(run) == pytest.approx(50.0)


def test_the_master_span_readers_on_a_recorded_trace():
    with open(os.path.join(HERE, "data", "xplane_spans_small.json")) as f:
        trace = json.load(f)
    run = {"cell": "t"}
    assert hostspans.attach(run, trace) is not None
    # the recorded trace is the sidecar's: nothing for the master's readers
    assert master_planner_busy_pct.read(run) is None
    assert master_ship_ms.read(run) is None
    renamed = json.loads(json.dumps(trace).replace("adlb.sidecar.",
                                                   "adlb.master."))
    run = {"cell": "t"}
    red = hostspans.attach(run, renamed)
    resting = sum(red["self_ns"].get(n, 0)
                  for n in ("adlb.master.wait", "adlb.master.pace"))
    assert master_planner_busy_pct.read(run) == pytest.approx(
        100.0 * (red["window_ns"] - resting) / red["window_ns"])
    assert 0.0 < master_planner_busy_pct.read(run) < 100.0
    assert master_ship_ms.read(run) == pytest.approx(
        red["median_ns"]["adlb.master.ship"] * 1e-6)


# ---------------------------------------------- the committed configuration


def test_the_committed_python_cell_is_the_deployment_it_names():
    spec = Spec(ROOT)
    cell, config, mix = spec.cell(CELL), spec.config(CELL), spec.traffic(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hotspot-py-n64", "bulk", 1)
    assert config["plane"] == "python" and spec.plane(CELL).run
    assert (config["app_ranks"], config["servers"], config["work_us"],
            config["fetch_batch"], config["types"]) == (64, 16, 50000, 4, [1])
    assert config["config"] == {
        "balancer": "tpu", "balancer_max_tasks": 2048,
        "balancer_max_requesters": 256, "solver_host_threshold": 0,
        "balancer_mesh": "off", "solver_backend": "auto",
        "exhaust_check_interval": 0.2, "on_worker_failure": "abort",
        "on_server_failure": "abort"}
    assert config["solve_shape"] == [16 * 2048, 16 * 256]
    native = spec.config("hotspot-native-n64.bulk")
    assert config["guarantees"] == native["guarantees"]
    assert config["solve_shape"] == native["solve_shape"]
    assert list(config["reduced"]) == ["hosts"] and config["hosts"] == 1
    assert (mix["put_routing"], mix["flush_every"], mix["pace"]) == (
        "home", 512, 0)
    listed = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert {"worker_blocked_pct", "match_wait_p95_ms", "fetch_rtt_p50_ms",
            "units_per_fetch", "device_solves_per_s", "reactor_busy_pct",
            "master_planner_busy_pct", "master_ship_ms", "solve_kernel_ms",
            "solve_roofline", "device_idle_pct"} <= listed
    # the sidecar's spans are not this plane's
    assert not {"planner_busy_pct", "ingest_ms_per_s", "plan_ship_ms"} & listed
    old = {m["name"] for m in spec.metrics("per_layer",
                                           "hotspot-native-n64.bulk")}
    assert not {"reactor_busy_pct", "master_planner_busy_pct",
                "master_ship_ms"} & old
    # the world's Config takes every field, the mix's routing among them
    cfg = plane.world_config(config, mix, "/nowhere", 12345)
    assert cfg.put_routing == "home" and cfg.solver_host_threshold == 0


def test_the_span_metrics_list_the_cells_whose_plane_emits_their_spans():
    """Every span metric of PR 26 and ``plan_age_p95_ms`` is a
    ``program_span`` that moves ``worker_fed_pct``; a reader of
    ``adlb.sidecar.*`` spans lists the native plane's cells alone, a
    reader blind to the plane lists those and this plane's, each list
    exact and in the order of ``workloads``."""
    spec = Spec(ROOT)
    plane_of = {cell: spec.config(cell)["plane"] for cell in spec.cells()}
    native = [c for c, p in plane_of.items() if p == "native"]
    both = [c for c, p in plane_of.items() if p in ("native", "python")]
    assert len(native) >= 3 and set(both) - set(native) == {CELL}
    by_name = {m["name"]: m for m in spec.doc["per_layer"]}
    want = {
        "planner_busy_pct": native, "ingest_ms_per_s": native,
        "plan_ship_ms": native,
        "planning_rounds_per_s": both, "round_pump_ms": both,
        "round_solve_ms": both, "idle_named_pct": both,
        "plan_age_p95_ms": both,
    }
    for name, cells in want.items():
        entry = by_name[name]
        assert entry["source"] == "program_span", name
        assert entry["moves"] == "worker_fed_pct", name
        assert entry["workloads"] == cells, name
    for cell in native:
        listed = {m["name"] for m in spec.metrics("per_layer", cell)}
        assert set(want) <= listed
    listed = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert {name for name, cells in want.items() if CELL in cells} <= listed
    assert not {"planner_busy_pct", "ingest_ms_per_s",
                "plan_ship_ms"} & listed
