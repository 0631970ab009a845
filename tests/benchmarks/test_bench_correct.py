"""The comparison that decides ``correct``: the control (the plain
reference with one stated guarantee broken) comes out as not correct, and
so does a whole run of the harness with the timed path broken underneath,
once for each fault a cell can have. No world, no chip."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks import control, run as bench_run
from benchmarks.reference import compare, greedy, pool
from test_bench_spec import add_standin, copy_of_benchmark

CELL = "hotspot-native-n64.bulk"


def test_the_sound_reference_passes_its_own_comparison():
    out = control.judge(CELL, seed=5, seconds=2.0, guarantee="exactly_once")
    assert out["correct"] is True and out["units"] > 10000
    assert all(v["value"] == 0 == v["limit"]
               for v in out["compared"].values())


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 11])
def test_the_control_is_not_correct(seed):
    """At-least-once delivery in place of exactly-once."""
    out = control.judge(CELL, seed=seed, seconds=2.0,
                        guarantee="at_least_once")
    assert out["correct"] is False
    assert out["compared"]["duplicated_units"]["value"] > 0
    assert out["compared"]["missing_units"]["value"] == 0


@pytest.mark.parametrize("guarantee,number", [
    ("at_most_once", "missing_units"),
    ("altered", "altered_units"),
])
def test_the_other_broken_guarantees_are_not_correct(guarantee, number):
    out = control.judge(CELL, seed=3, seconds=2.0, guarantee=guarantee)
    assert out["correct"] is False
    assert out["compared"][number]["value"] > 0


def run_standin(tmp_path, fault: str, trace: int = 0) -> dict:
    """Drive ``run.child`` — everything a run does after the look for a
    chip — over the stand-in plane with ``fault`` planted."""
    root = copy_of_benchmark(tmp_path)
    cell = add_standin(root, fault=fault)
    args = types.SimpleNamespace(workload=cell, seed=7, seconds=2.0,
                                 trace=trace, t0=0.0)
    os.makedirs(bench_run.scratch_dir(root, cell))
    assert bench_run.child(args, root=root) == 0
    with open(os.path.join(bench_run.scratch_dir(root, cell),
                           "result.json")) as f:
        return json.load(f)


def test_a_sound_run_is_correct_and_prints_the_contracts_line(tmp_path):
    result = run_standin(tmp_path, "none")
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"units_per_s", "worker_fed_pct",
                                      "setup_s"}
    assert result["attempted"] == 800  # 8 workers / 30 ms x (1 + 2) s
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}


@pytest.mark.parametrize("fault,number", [
    ("at_least_once", "duplicated_units"),   # a unit delivered twice
    ("at_most_once", "missing_units"),       # an acknowledged put lost
    ("altered", "altered_units"),            # an answer altered at its source
    ("t_end_altered", "altered_units"),      # a payload altered in flight
    ("client_failed", "clients_failed"),     # not ended by exhaustion
    ("unacked_put", "unacked_puts"),         # a put without acknowledgement
    ("solve_altered", "solve_mismatch"),     # the device program's answer
])
def test_a_run_with_the_timed_path_broken_is_not_correct(tmp_path, fault,
                                                         number):
    result = run_standin(tmp_path, fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > result["compared"][
        number]["limit"] == 0


def test_a_traced_run_reports_only_what_its_readers_found(tmp_path):
    """No trace, no flight artefact: the device metrics are left out of
    the line, never reported as 0 — and a run whose trace shows no
    operation on the device gives no result at all."""
    with pytest.raises(SystemExit, match="no operation on the device"):
        run_standin(tmp_path, "none", trace=1)


def test_a_planner_that_was_not_the_chip_ends_the_run():
    good = {"platform": "tpu", "host_solves": 0, "device_failures": 0,
            "device_solves": 3}
    bench_run.check_planner(good, 1)
    for bad in ({"platform": "cpu"}, {"host_solves": 1},
                {"device_failures": 1}, {"device_solves": 0}):
        with pytest.raises(SystemExit):
            bench_run.check_planner({**good, **bad}, 1)


def test_plain_greedy_by_hand():
    # tasks: prio 5, 9, 9, pad; requesters: r0 invalid, r1, r2
    prio = np.array([5, 9, 9, -99], dtype=np.int32)
    ttype = np.array([0, 0, 0, -1], dtype=np.int32)
    mask = np.ones((3, 1), dtype=bool)
    valid = np.array([False, True, True])
    got = greedy.greedy_assign(prio, ttype, mask, valid, pad_prio=-99)
    # the two 9s go first, lower index first, to the lowest open requester
    assert got.tolist() == [-1, 1, 2]


def test_plain_pool_is_priority_then_fifo_and_typed():
    p = pool.PlainPool()
    p.put(("a",), work_type=1, prio=0)
    p.put(("b",), work_type=2, prio=5)
    p.put(("c",), work_type=1, prio=5)
    p.put(("d",), work_type=1, prio=5)
    assert [p.get((1,)) for _ in range(4)] == [("c",), ("d",), ("a",), None]
    assert p.get((2,)) == ("b",) and p.get((1, 2)) is None
    assert compare.verdict({name: 0 for name in compare.LIMITS})
