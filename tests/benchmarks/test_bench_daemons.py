"""reduce/daemons.py and the readers that stand on it, on two stored
artefacts (``data/flight-serverd-r2-p101.json``, the hot daemon of a world
of two app ranks, and ``...-r3-p102.json``, the other one: the keys a
daemon writes, ``tests/test_native_flight.py`` holds those, with round
numbers so that every reader's value is hand arithmetic) and on a stand-in
trace with clock marks."""

import glob
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks.reduce import daemons, hostspans, records
from benchmarks.spec import ROOT, Spec

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "some-cell.bulk"
NATIVE_CELLS = ["hotspot-native-n128.bulk", "hotspot-native-n64.bulk",
                "hotspot-native-n128.syncput"]
LAYER_1 = "client library + wire + server reactor"
LAYER_PLANS = "plan shipping + enactment"
#: name -> (unit, layer, the value the stored artefacts give), in the
#: order of BENCHMARK.json. The window [100.4, 104.6] holds the whole
#: seconds 101, 102 and 103.
METRICS = {
    # hot daemon: asleep 0.70 + 0.60 + 0.80, poll 3 x 0.10, of 3 s
    "daemon_busy_pct": ("%", LAYER_1, 100.0 * (1.0 - 2.4 / 3.0)),
    # hot daemon: fetch 0.06 + 0.09 + 0.03 s over 300 + 500 + 200 frames
    "daemon_fetch_self_us": ("us", LAYER_1, 180.0),
    # hot daemon: snapshot 30 + 50 + 10 ms over 3 s
    "snapshot_ms_per_s": ("ms/s", LAYER_1, 30.0),
    # plan (hot: 10 in (8.192, 11.585] ms) and migrated (other: 10 in
    # (16.384, 23.170] ms) merged, 20 waits: the 10th ends the first bucket
    "park_wait_plan_p50_ms": ("ms", LAYER_1, 11.5852375),
    # the 19th is nine tenths into the second
    "park_wait_plan_p95_ms": ("ms", LAYER_1,
                              16.384 + 0.9 * (23.170475 - 16.384)),
    # both daemons: enact 0.03 + 3 x 0.004 s over 50 + 3 x 4 frames
    "plan_enact_us_per_frame": ("us", LAYER_PLANS, 0.042 / 62 * 1e6),
    # both daemons, whole world: 10 + 15 stale of 400 + 100
    "plan_stale_pct": ("%", LAYER_PLANS, 5.0),
    # hot daemon, whole world: handler:FA_PUT 0.14 s over 1,400 puts
    "daemon_put_self_us": ("us", LAYER_1, 100.0),
    # hot daemon, whole world: decode 0.07 + flush 0.105 s over 3,500 frames
    "daemon_wire_us_per_frame": ("us", LAYER_1, 50.0),
}
MS = 1_000_000


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


def run_of(tmp_path, t0=100.4, t_end=104.6, artefacts=True, **more):
    """What ``run.py`` hands a reader, over a scratch checkout that holds
    the stored artefacts where the plane would have left them."""
    flight = tmp_path / ".bench_scratch" / CELL / "flight"
    flight.mkdir(parents=True, exist_ok=True)
    if artefacts:
        for path in glob.glob(os.path.join(DATA, "flight-serverd-*.json")):
            shutil.copy(path, flight)
    return {"cell": CELL, "bench_dir": str(tmp_path / "benchmarks"),
            "config": {"app_ranks": 2, "servers": 2}, "trace": None,
            "window": types.SimpleNamespace(t0=t0, t_end=t_end), **more}


# ---------------------------------------------------------------- the readers


@pytest.mark.parametrize("name", list(METRICS))
def test_a_readers_value_is_hand_arithmetic(spec, tmp_path, name):
    value = spec.reader(name)(run_of(tmp_path))
    assert value == pytest.approx(METRICS[name][2], rel=1e-9)


@pytest.mark.parametrize("name", list(METRICS))
def test_a_run_that_left_no_artefact_reports_nothing(
        spec, tmp_path, capsys, name):
    """A parent commit's daemons write none: every reader gives None and
    does not raise, and an earlier line says why."""
    assert spec.reader(name)(run_of(tmp_path, artefacts=False)) is None
    assert "no flight-serverd artefact" in capsys.readouterr().out


def test_the_window_is_clipped_to_its_whole_seconds(tmp_path):
    # [100.0, 104.0] holds 100 .. 103: the hot daemon's 100 counts too
    win = daemons.analyse(run_of(tmp_path, 100.0, 104.0))["hot_window"]
    assert win["seconds"] == 4
    assert win["s"]["asleep"] == pytest.approx(0.9 + 0.7 + 0.6 + 0.8)
    assert win["n"]["put"] == 200 + 400 + 600 + 200
    # [101.2, 103.9] holds one whole second, 102 alone
    win = daemons.analyse(run_of(tmp_path, 101.2, 103.9))["hot_window"]
    assert (win["seconds"], win["s"]["snapshot"], win["n"]["fetch"]) == (
        1, 0.05, 500)
    assert sum(win["s"].values()) == pytest.approx(1.0)
    # no second whole: nothing to clip to
    red = daemons.analyse(run_of(tmp_path, 101.2, 102.9))
    assert red["hot_window"] is None and red["all_window"] is None


def test_a_second_a_daemon_did_not_record_clips_nothing(spec, tmp_path):
    """The window reaches into second 106, which neither daemon lived: the
    windowed readers report nothing rather than a sum over fewer seconds;
    the whole-world readers are as they were."""
    run = run_of(tmp_path, 100.4, 107.2)
    for name in ("daemon_busy_pct", "daemon_fetch_self_us",
                 "snapshot_ms_per_s", "plan_enact_us_per_frame"):
        assert spec.reader(name)(run) is None, name
    for name in ("plan_stale_pct", "daemon_put_self_us",
                 "daemon_wire_us_per_frame", "park_wait_plan_p50_ms"):
        assert spec.reader(name)(run) == pytest.approx(METRICS[name][2])


def test_a_sum_over_the_daemons_needs_every_daemon(tmp_path):
    run = run_of(tmp_path)
    flight = daemons.flight_dir(run)
    other = os.path.join(flight, "flight-serverd-r3-p102.json")
    with open(other) as f:
        text = f.read()
    with open(other, "w") as f:  # rank 3 missed second 102
        f.write(text.replace('"102":', '"1020":'))
    red = daemons.analyse(run)
    assert red["all_window"] is None and red["hot_window"]["seconds"] == 3


def test_the_reduction_is_made_and_said_once_a_run(tmp_path, capsys):
    run = run_of(tmp_path)
    red = daemons.analyse(run)
    said = capsys.readouterr().out
    assert daemons.analyse(run) is red and capsys.readouterr().out == ""
    lines = [line for line in said.splitlines()
             if line.startswith(f"[{CELL}] daemons: ")]
    assert len(lines) == len(said.splitlines()) == 7
    # the hot daemon's phases by share of the window, closed
    assert "rank 2 over 3 whole seconds" in lines[0]
    assert "(sum 3.000000s)" in lines[0] and "asleep 70.00%" in lines[0]
    assert "busy 20.00%" in lines[0]
    # its top handlers by self time and a frame
    assert "FA_RESERVE 0.1500s = 166.67us x 900, FA_PUT 0.1400s = " \
        "100.00us x 1400" in lines[2]
    # the second with most puts (102: 600 of them), a put: the daemon's half
    # of a put's round trip under a flood, piece by piece
    assert "second with most puts (102): 600 puts, a put decode 50.000us, " \
        "put 100.000us, flush 66.667us, other 16.667us, poll 166.667us; " \
        "asleep 60.00%" in lines[3]
    del lines[3]
    # the trailer's eight counters get their reader here: hot | the others
    for counter, pair in (("waits_polled", "300 | 50"),
                          ("waits_slept", "40 | 30"),
                          ("bells_rung", "700 | 60"),
                          ("bells_elided", "2300 | 10"),
                          ("frames_ring", "3000 | 70"),
                          ("frames_sock", "500 | 2"),
                          ("conns_unix", "20 | 6"), ("conns_tcp", "2 | 2")):
        assert f"{counter} {pair}" in lines[3]
    # park waits by cause: n, p50, p95
    assert "local n=5" in lines[4] and "steal n=2" in lines[4]
    assert "plan n=10 p50 9.889 p95 11.416" in lines[4]
    assert "plan entries 500, stale 25 (5.00%)" in lines[5]


# ---------------------------------------------------------------- the overlay


def planner_thread(t0):
    """20 ms of a planner: wait 8, ingest 2, a round of 8 with a solve of 4
    inside, pace 2."""
    return [["adlb.sidecar.wait", t0, 8 * MS],
            ["adlb.sidecar.ingest", t0 + 8 * MS, 2 * MS],
            ["adlb.round", t0 + 10 * MS, 8 * MS],
            ["adlb.solve", t0 + 12 * MS, 4 * MS],
            ["adlb.sidecar.pace", t0 + 18 * MS, 2 * MS]]


def fetches_of(calls):
    f = np.zeros(len(calls), dtype=records.FETCH)
    for i, (t_call, t_ret) in enumerate(calls):
        f[i] = (t_call, t_ret, 1, 1)
    return f


def test_marks_give_the_offset_and_its_spread():
    # a trace whose host plane began 50 s of CLOCK_MONOTONIC ago, read with
    # 3, 1 and 2 us of delay
    marks = [[1_000_000 + 3_000, 50_000_000_000 + 1_000_000],
             [2_000_000 + 1_000, 50_000_000_000 + 2_000_000],
             [3_000_000 + 2_000, 50_000_000_000 + 3_000_000]]
    assert daemons.clock(marks) == {
        "marks": 3, "offset_ns": -50_000_000_000 + 2_000, "spread_ns": 2_000}
    assert daemons.clock([]) is None


def test_the_overlay_splits_remote_fetch_time_by_the_planners_span():
    """Two servers, rank 0 produces: rank 1 is remote, rank 2 is homed
    with the producer and does not count. On the trace's clock the planner
    runs from 5 ms; CLOCK_MONOTONIC is 50 s ahead of it."""
    events = planner_thread(5 * MS)
    offset = -50_000_000_000
    mono = lambda ms: 50.0 + ms * 1e-3  # noqa: E731
    fetches = fetches_of([
        (mono(1), mono(9)),     # rank 1: 4 ms of it in the window, in wait
        (mono(12), mono(20)),   # rank 1: wait 1, ingest 2, round 2, solve 3
        (mono(6), mono(24)),    # rank 2, local: left out
        (mono(23.5), mono(40)),  # rank 1: pace 1.5, the rest past the window
        (mono(41), mono(42)),   # rank 1: outside
    ])
    rank = np.array([1, 1, 2, 1, 1], dtype=np.int32)
    over = daemons.overlay(events, offset, fetches, rank, nservers=2)
    assert over["calls"] == 3 and over["window_s"] == pytest.approx(0.020)
    assert over["fetch_s"] == pytest.approx(0.0135)
    assert {k: round(v * 1e3, 6) for k, v in over["by_s"].items()} == {
        "adlb.sidecar.wait": 5.0, "adlb.sidecar.ingest": 2.0,
        "adlb.round": 2.0, "adlb.solve": 3.0, "adlb.sidecar.pace": 1.5}
    # nobody remote in a fetch inside the window: nothing to split
    assert daemons.overlay(events, offset, fetches[2:3], rank[2:3], 2) is None
    assert daemons.overlay([], offset, fetches, rank, 2) is None


def test_time_outside_every_span_is_named_so():
    events = [["adlb.round", 0, 4 * MS], ["adlb.round", 6 * MS, 4 * MS]]
    over = daemons.overlay(events, 0, fetches_of([(0.003, 0.008)]),
                           np.array([1], dtype=np.int32), nservers=2)
    assert {k: round(v * 1e3, 6) for k, v in over["by_s"].items()} == {
        "adlb.round": 3.0, hostspans.NO_SPAN: 2.0}


def test_a_traced_run_says_the_offset_and_the_overlay(
        tmp_path, capsys, monkeypatch):
    """``analyse`` on a traced run: the marks and the planner's thread
    come from the trace, the fetch records from the clients' logs."""
    events = planner_thread(5 * MS)
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": events}]}]}
    marks = [[7 * MS, 50_000_000_000 + 7 * MS - 2_000],
             [9 * MS, 50_000_000_000 + 9 * MS - 1_000]]
    monkeypatch.setattr(hostspans, "trace_path", lambda run: "some.xplane.pb")
    monkeypatch.setattr(daemons, "load_planner", lambda path: (trace, marks))
    logs = types.SimpleNamespace(
        fetches=fetches_of([(50.012, 50.020)]),
        fetch_rank=np.array([1], dtype=np.int32))
    red = daemons.analyse(run_of(tmp_path, trace={"planes": []}, logs=logs))
    assert red["clock"] == {"marks": 2, "offset_ns": -50_000_000_000 + 1_500,
                            "spread_ns": 1_000}
    assert red["overlay"]["fetch_s"] == pytest.approx(0.008)
    said = capsys.readouterr().out.splitlines()
    assert "clock: 2 marks; trace host plane = CLOCK_MONOTONIC " \
        "-49999998500 ns, spread 1.000 us" in said[-2]
    assert "overlay: 1 fetch calls of remote workers" in said[-1]
    assert "adlb.solve 37.52%" in said[-1]


def test_a_trace_without_marks_gets_no_overlay(tmp_path, capsys, monkeypatch):
    """The parent's program has no ``clock_mark``: the line says so and
    the readers are none the worse."""
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": planner_thread(0)}]}]}
    monkeypatch.setattr(hostspans, "trace_path", lambda run: "some.xplane.pb")
    monkeypatch.setattr(daemons, "load_planner", lambda path: (trace, []))
    red = daemons.analyse(run_of(tmp_path, trace={"planes": []}, logs=None))
    assert red["clock"] is None and red["overlay"] is None
    assert "holds no adlb.clock mark" in capsys.readouterr().out
    assert red["hot_window"]["seconds"] == 3


def test_a_profile_gives_up_the_planners_spans_and_the_marks(
        tmp_path, monkeypatch):
    """``load_planner`` on a real ``.xplane.pb`` (a session on the CPU
    backend): the ``adlb.*`` events in ``xplane.load``'s shape, the marks
    apart with the reading they carry."""
    import time

    import jax

    from adlb_tpu.runtime import trace as tracing

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        before = time.monotonic_ns()
        for _ in range(2):
            monkeypatch.setattr(tracing, "_next_clock_mark", 0.0)
            tracing.clock_mark()
            with tracing.span("adlb.sidecar.wait"):
                time.sleep(0.002)
        after = time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    trace, marks = daemons.load_planner(path)
    events = hostspans.planner_events(trace)
    assert [e[0] for e in events] == ["adlb.sidecar.wait"] * 2
    assert all(e[2] >= 2 * MS for e in events)
    assert len(marks) == 2 and all(before <= ns <= after for _t, ns in marks)
    clk = daemons.clock(marks)
    assert clk["spread_ns"] < MS
    # laid on the trace's clock, a mark's reading is where its event is
    assert marks[0][1] + clk["offset_ns"] == pytest.approx(
        marks[0][0], abs=MS)
    assert marks[0][0] <= events[0][1] <= marks[1][0]


# ---------------------------------------------------------- BENCHMARK.json


def test_the_new_entries_end_the_list_and_name_the_native_cells(spec):
    # they were appended together; a later PR may append behind them
    names = [m["name"] for m in spec.doc["per_layer"]]
    first = names.index(next(iter(METRICS)))
    tail = spec.doc["per_layer"][first:first + len(METRICS)]
    assert [m["name"] for m in tail] == list(METRICS)
    for m in tail:
        unit, layer, _value = METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": "worker_fed_pct", "workloads": NATIVE_CELLS}
        assert callable(spec.reader(m["name"]))
    # both layers were there already, under these names
    layers = {m["layer"] for m in spec.doc["per_layer"][:first]}
    assert {LAYER_1, LAYER_PLANS} <= layers
    # the cells' plane is the one whose daemons write the artefact
    for cell in spec.cells():
        listed = {m["name"] for m in spec.metrics("per_layer", cell)}
        native = spec.config(cell)["plane"] == "native"
        assert (set(METRICS) <= listed) == native, cell
        assert native or not set(METRICS) & listed
