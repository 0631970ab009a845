"""reduce/xplane.py on a small recorded trace (three solves of the n128
world on a TPU v5 lite, kept as JSON) and on hand-made intervals."""

import json
import os

import pytest

from benchmarks.reduce import xplane

DATA = os.path.join(os.path.dirname(__file__), "data", "xplane_small.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def plane(ops, modules=(), name="/device:TPU:0"):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in ops]},
        {"name": "XLA Modules", "events": [list(e) for e in modules]}]}


def test_busy_is_the_union_of_intervals_not_their_sum():
    ops = [("a", 0, 100), ("b", 50, 100), ("c", 300, 50), ("d", 310, 10)]
    assert xplane.busy_intervals(ops) == [[0, 150], [300, 350]]
    trace = {"planes": [plane(ops)]}
    assert xplane.busy_s(trace) == pytest.approx(200e-9)


def test_busy_is_averaged_over_the_chips():
    trace = {"planes": [plane([("a", 0, 100)]),
                        plane([("a", 0, 300)], name="/device:TPU:1")]}
    assert xplane.busy_s(trace) == pytest.approx(200e-9)


def test_a_trace_without_a_device_plane_reads_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert xplane.busy_s(trace) is None
    assert xplane.program_runs(trace, "solve") == (0, 0.0)
    assert xplane.idle_gaps(trace) == [] and xplane.top_ops(trace) == []


def test_program_time_counts_only_the_operations_inside_its_runs():
    ops = [("x", 10, 20), ("y", 40, 20), ("other", 200, 50)]
    modules = [("jit_solve(1)", 0, 100), ("jit_other(2)", 190, 100)]
    trace = {"planes": [plane(ops, modules)]}
    assert xplane.program_runs(trace, "solve") == (1, pytest.approx(40e-9))
    assert xplane.program_runs(trace, "nothing") == (0, 0.0)


def test_gaps_are_named_after_the_host_span_that_covers_them():
    ops = [("a", 0, 100), ("b", 1100, 100), ("c", 1300, 100)]
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["ingest", 150, 900], ["blip", 1210, 20]]}]}
    gaps = xplane.idle_gaps({"planes": [plane(ops), host]})
    assert gaps[0] == ["ingest", pytest.approx(1000e-9)]
    # a span over a fifth of the gap does not name it
    assert gaps[1] == ["unattributed", pytest.approx(100e-9)]


def test_recorded_trace_three_solves(recorded):
    runs, seconds = xplane.program_runs(recorded, "greedy_assign")
    assert runs == 3
    # each solve of the 65,536 x 8,192 table took 2.95 ms on the device
    assert seconds / runs == pytest.approx(2.951e-3, rel=2e-3)
    # nothing else ran: the device was busy exactly in the solves
    assert xplane.busy_s(recorded) == pytest.approx(seconds)
    top = xplane.top_ops(recorded, 2)
    assert "and_convert_fusion" in top[0][0]
    assert "tpu_custom_call" in top[1][0]
    assert top[0][1] > top[1][1] > 0
    gaps = xplane.idle_gaps(recorded, 2)
    # between the solves the host ran planner code that has no span
    assert gaps[0][0] == "unattributed" and gaps[0][1] > 0.05
    assert gaps[1][1] > 0.05


def test_recorded_trace_busy_matches_a_brute_force_union(recorded):
    ops = xplane._line(xplane.device_planes(recorded)[0], xplane.OPS_LINE)
    t0 = min(e[1] for e in ops)
    marks = set()
    for _name, start, dur in ops:  # microsecond grid, coarse but independent
        marks.update(range((start - t0) // 1000, (start + dur - t0) // 1000))
    assert xplane.busy_s(recorded) == pytest.approx(len(marks) * 1e-6,
                                                    rel=0.02)
