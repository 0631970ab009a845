"""reduce/hostspans.py and the readers that stand on it: on hand-made
spans, and on a small recorded trace (``data/xplane_spans_small.json``: the
first 0.75 s of a traced window of ``hotspot-native-n64.bulk`` on a TPU v5
lite, seed 2611000001 — the device plane's modules and operations, names
cut at `` = ``, and the planner thread's ``adlb.*`` events)."""

import copy
import json
import os

import pytest

from benchmarks.reduce import hostspans, xplane
from benchmarks.spec import ROOT, Spec

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "xplane_spans_small.json")
SPAN_METRICS = ["planner_busy_pct", "planning_rounds_per_s",
                "ingest_ms_per_s", "round_pump_ms", "round_solve_ms",
                "plan_ship_ms", "idle_named_pct"]
MS = 1_000_000


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


def trace_of(host_events, ops=(), modules=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]},
            {"name": "XLA Modules", "events": [list(e) for e in modules]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "other", "events": [["adlb.stray", 0, 5]]},
            {"name": "python3", "events": [list(e) for e in host_events]}]},
    ]}


def one_round(t0, solve_at=None):
    """A loop turn of 10 ms from ``t0``: wait 2, ingest 1, a round of 6
    (admit 2, plan 4: view 1, [solve 1.5: call 0.2, wait 1], migrations 1),
    ship 0.5, pace 0.5."""
    ev = [["adlb.sidecar.wait", t0, 2 * MS],
          ["adlb.sidecar.ingest", t0 + 2 * MS, 1 * MS],
          ["adlb.round", t0 + 3 * MS, 6 * MS],
          ["adlb.round.admit", t0 + 3 * MS, 2 * MS],
          ["adlb.round.plan", t0 + 5 * MS, 4 * MS],
          ["adlb.round.view", t0 + 5 * MS, 1 * MS],
          ["adlb.round.migrations", t0 + 8 * MS, 1 * MS],
          ["adlb.sidecar.ship", t0 + 9 * MS, MS // 2],
          ["adlb.sidecar.pace", t0 + 9 * MS + MS // 2, MS // 2]]
    if solve_at is not None:
        ev += [["adlb.solve", solve_at, 3 * MS // 2],
               ["adlb.solve.call", solve_at + MS // 10, MS // 5],
               ["adlb.solve.wait", solve_at + MS // 10 + MS // 5, MS]]
    return ev


def hand_made(device_lead_ns=0):
    """Two turns; the first solves. The device runs the solve 0.4 ms after
    the dispatch, for 0.5 ms; its clock stands ``device_lead_ns`` early."""
    events = one_round(0, solve_at=6 * MS) + one_round(10 * MS)
    start = 6 * MS + MS // 2 - device_lead_ns
    ops = [["a", start, 300_000], ["b", start + 300_000, 200_000]]
    modules = [["jit_pallas_greedy_assign(1)", start, 500_000]]
    return trace_of(events, ops, modules)


# ------------------------------------------------------------- arithmetic


def test_innermost_gives_every_instant_one_name():
    events = sorted(one_round(0, solve_at=6 * MS),
                    key=lambda e: (e[1], -e[2]))
    segments = hostspans.innermost(events)
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    self_ns = {}
    for start, end, name in segments:
        self_ns[name] = self_ns.get(name, 0) + end - start
    assert sum(self_ns.values()) == 10 * MS  # the turn, once
    assert self_ns["adlb.sidecar.wait"] == 2 * MS
    assert "adlb.round" not in self_ns  # admit and plan cover it
    # plan 4 ms less view 1, solve 1.5, migrations 1
    assert self_ns["adlb.round.plan"] == MS // 2
    assert self_ns["adlb.solve"] == 3 * MS // 2 - MS // 5 - MS
    assert self_ns["adlb.solve.wait"] == MS


def test_a_child_is_cut_at_its_parents_end():
    segments = hostspans.innermost([["p", 0, 100], ["c", 50, 80]])
    assert segments == [[0, 50, "p"], [50, 100, "c"]]


def test_complement_and_overlap():
    idle = hostspans.complement([[10, 20], [40, 50]], 0, 60)
    assert idle == [[0, 10], [20, 40], [50, 60]]
    assert hostspans.complement([[10, 20]], 12, 18) == []
    segments = [[0, 15, "x"], [15, 30, "y"], [45, 70, "x"]]
    assert hostspans.overlap_by_name(idle, segments) == {
        "x": 10 + 10, "y": 10}


def test_rounds_hold_what_lies_inside_them():
    events = sorted(hand_made()["planes"][1]["lines"][1]["events"],
                    key=lambda e: (e[1], -e[2]))
    first, second = hostspans.rounds(events)
    assert first["adlb.round.plan"] == 4 * MS
    assert first["adlb.solve"] == 3 * MS // 2 and "adlb.solve" not in second
    assert first["adlb.round.migrations"] == second["adlb.round.migrations"]
    assert "adlb.round.admit" not in first  # a sibling, not a child
    whole = hostspans.rounds(events, "adlb.round")
    assert whole[0]["adlb.round.plan"] == 4 * MS


@pytest.mark.parametrize("lead_ns,passes", [
    (0, True), (1_000_000, True), (-900_000, True),
    (6_000_000, False), (1_000_000_000, False)])
def test_clock_check_moves_the_device_plane_only_so_far(
        lead_ns, passes, capsys):
    run = {"cell": "c"}
    red = hostspans.attach(run, hand_made(device_lead_ns=lead_ns))
    out = capsys.readouterr().out
    if not passes:
        assert red is None and hostspans.analyse(run) is None
        assert "clock check failed" in out
        return
    assert red["clock_check"] == 1.0
    # the solve may sit anywhere between dispatch and the wait's end: the
    # least move that puts it there is taken, none where none is needed
    assert abs(red["clock_offset_ns"] - lead_ns) <= 400_000
    assert abs(red["clock_offset_ns"]) <= abs(lead_ns)
    assert (red["clock_check_unmoved"] == 1.0) == (lead_ns == 0)
    assert "clock check: device plane moved by" in out


def test_host_events_shifted_by_a_second_fail_the_check(recorded, capsys):
    shifted = copy.deepcopy(recorded)
    for plane in shifted["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for ev in line["events"]:
                    ev[1] += 1_000_000_000
    run = {"cell": "c"}
    assert hostspans.attach(run, shifted) is None
    assert "clock check failed" in capsys.readouterr().out


def test_hand_made_reduction():
    red = hostspans.reduce(hand_made())
    assert red["window_ns"] == 20 * MS and red["events"] == 21
    assert red["count"]["adlb.round.plan"] == 2
    assert red["idle_ns"] == 20 * MS - 500_000
    # the device was busy inside adlb.solve.wait; all else is idle and named
    assert red["idle_named_ns"] == red["idle_ns"]
    assert red["idle_by"]["adlb.solve.wait"] == MS - 500_000
    assert red["idle_by"]["adlb.sidecar.wait"] == 4 * MS
    assert red["round_ns"] == [6 * MS, 6 * MS]
    longest = red["gaps"][0]
    assert longest["seconds"] == pytest.approx(13e-3)
    assert sum(longest["by"].values()) == pytest.approx(1.0)
    # the stray adlb.* event of another thread is not the planner's
    assert "adlb.stray" not in red["count"]


# ------------------------------------------------------- the recorded trace


def test_recorded_self_time_adds_up_to_the_window(recorded):
    red = hostspans.reduce(recorded)
    assert sum(red["self_ns"].values()) == pytest.approx(
        red["window_ns"], rel=5e-3)  # loop glue between spans: under 0.5%
    assert sum(red["self_ns"].values()) <= red["window_ns"]
    # this session's device plane stood 1.8 ms before its host plane
    assert red["clock_check"] == 1.0 and red["clock_check_unmoved"] == 0.0
    assert 1_000_000 < red["clock_offset_ns"] < 2_500_000
    busy = xplane.busy_s(recorded)
    assert red["idle_ns"] * 1e-9 == pytest.approx(
        red["window_ns"] * 1e-9 - busy, rel=1e-3)
    assert red["idle_named_ns"] <= red["idle_ns"]
    for gap in red["gaps"]:
        assert sum(gap["by"].values()) == pytest.approx(1.0)


def test_recorded_device_time_lies_in_the_solve_spans(recorded):
    """With the device plane moved onto the host's clock, the device is
    busy only while the planner dispatches the solve or waits for it. (The
    least move puts the tightest solve's start at its dispatch, so some
    device time falls under ``call`` that a later clock would give
    ``wait``.)"""
    red = hostspans.reduce(recorded)
    events = hostspans.planner_events(recorded)
    segments = hostspans.innermost(events)
    ops = xplane._line(xplane.device_planes(recorded)[0], xplane.OPS_LINE)
    busy = [[s + red["clock_offset_ns"], e + red["clock_offset_ns"]]
            for s, e in xplane.busy_intervals(ops)]
    by = hostspans.overlap_by_name(busy, segments)
    assert set(by) <= {"adlb.solve.call", "adlb.solve.wait", "adlb.solve"}
    assert by["adlb.solve.wait"] > 0.6 * sum(by.values())


def test_recorded_readers(recorded, spec, capsys):
    run = {"cell": "hotspot-native-n64.bulk", "trace": recorded}
    red = hostspans.attach(run, recorded)
    lines = capsys.readouterr().out.splitlines()
    assert sum("idle gap" in line for line in lines) == len(red["gaps"])
    assert any("planning rounds" in line and "cover" in line
               for line in lines)
    values = {name: spec.reader(name)(run) for name in SPAN_METRICS}
    assert all(v is not None for v in values.values()), values
    window_s = red["window_ns"] * 1e-9
    assert values == {name: pytest.approx(want, rel=1e-6)
                      for name, want in RECORDED_VALUES.items()}
    assert values["planning_rounds_per_s"] == pytest.approx(
        red["count"]["adlb.round.plan"] / window_s)
    resting = (red["self_ns"]["adlb.sidecar.wait"]
               + red["self_ns"]["adlb.sidecar.pace"]) * 1e-9
    assert values["planner_busy_pct"] == pytest.approx(
        100 * (1 - resting / window_s))
    assert values["round_solve_ms"] > 1.186  # the device's share of it


#: what the readers give on the recorded trace (ms, %, rounds/s)
RECORDED_VALUES = {
    "planner_busy_pct": 60.39929084022394,
    "planning_rounds_per_s": 25.118200534017436,
    "ingest_ms_per_s": 94.14781317779934,
    "round_pump_ms": 2.853251,
    "round_solve_ms": 4.554675,
    "plan_ship_ms": 0.247815,
    "idle_named_pct": 99.69352052435951,
}


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_spans_or_no_trace_reads_nothing(recorded, spec, name, capsys):
    bare = copy.deepcopy(recorded)
    for plane in bare["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                line["events"] = [e for e in line["events"]
                                  if not e[0].startswith("adlb.")]
    run = {"cell": "c", "trace": bare}
    assert hostspans.attach(run, bare) is None
    assert "no adlb.* event" in capsys.readouterr().out
    assert spec.reader(name)(run) is None  # a parent commit: left out
    # an untraced run never looks for a file
    assert spec.reader(name)({"cell": "c", "trace": None}) is None


def test_plan_age_p95_reads_the_flight_artefact(spec):
    read = spec.reader("plan_age_p95_ms")
    hist = {"bounds": [0.001, 0.004, 0.016, 0.064],
            "counts": [0, 10, 80, 10, 0], "sum": 1.0, "count": 100}
    run = {"flight": {"metrics": {"histograms": {
        "balancer_plan_age_s": hist}}}}
    # rank 95 of 100 is half-way through the fourth bucket, 16 to 64 ms
    assert read(run) == pytest.approx(40.0)
    assert read({"flight": None}) is None
    assert read({"flight": {"metrics": {"histograms": {}}}}) is None
    empty = dict(hist, counts=[0] * 5, count=0)
    assert read({"flight": {"metrics": {"histograms": {
        "balancer_plan_age_s": empty}}}}) is None


def test_the_new_metrics_are_listed_for_both_cells(spec):
    """Since a second plane has a cell: for the cells whose plane has a
    sidecar. Its spans are read there and nowhere else
    (``test_bench_python_plane.py`` holds the other plane's lists)."""
    sidecar = [cell for cell in spec.cells()
               if spec.config(cell)["plane"] == "native"]
    assert {"hotspot-native-n128.bulk", "hotspot-native-n64.bulk",
            "hotspot-native-n128.syncput"} <= set(sidecar)
    for cell in sidecar:
        listed = [m["name"] for m in spec.metrics("per_layer", cell)]
        assert set(SPAN_METRICS + ["plan_age_p95_ms"]) <= set(listed)
    by_name = {m["name"]: m for m in spec.doc["per_layer"]}
    for name in SPAN_METRICS + ["plan_age_p95_ms"]:
        entry = by_name[name]
        assert entry["source"] == "program_span"
        assert entry["moves"] == "worker_fed_pct"
        assert [c for c in entry["workloads"] if c in sidecar] == sidecar
