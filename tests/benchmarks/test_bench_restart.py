"""What PR 35 added to the benchmark: the durable configuration, the
``restart`` mix, the plane that kills a fleet and restarts it, the plain
durable pool with its control, and three readers. The spec resolves them
and nothing that was there changed; the plane's orchestration is driven
with the worlds stood in for (real processes die, no ADLB world runs);
the control's broken pools come out not correct; the readers' arithmetic
is checked on synthetic records. CPU, no chip.
(``tests/test_wal_restart_world.py`` drives the same plane over real
worlds.)"""

import dataclasses
import json
import mmap
import os
import struct
import time
import types
import uuid

import pytest

from benchmarks import control, control_restart, run as bench_run
from benchmarks.metrics import (wal_flush_ms_per_s, wal_fsync_p50_ms,
                                wal_records_per_commit)
from benchmarks.planes import python as base
from benchmarks.planes import python_wal as plane
from benchmarks.reduce import records
from benchmarks.reference import durable_pool, greedy
from benchmarks.spec import ROOT, Spec
from benchmarks.traffic.generate import make_plan, n_units
from test_bench_spec import in_order

CELL = "hotspot-py-n64-wal.restart"
TWIN = "hotspot-py-n64.bulk"
NEW_METRICS = ["wal_fsync_p50_ms", "wal_flush_ms_per_s",
               "wal_records_per_commit"]


# ------------------------------------------------------------- the spec


def test_the_spec_resolves_the_additions_and_nothing_else_changed():
    spec = Spec(ROOT)
    spec.check_files()
    doc = spec.doc
    # the configurations' prefix and the cells this one stands among: a
    # later benchmark may add behind them, or add and retire cells
    assert [c["name"] for c in doc["configs"]][:4] == [
        "hotspot-native-n128", "hotspot-native-n64", "hotspot-py-n64",
        "hotspot-py-n64-wal"]
    assert in_order(["hotspot-native-n128.bulk", "hotspot-native-n64.bulk",
                     TWIN, CELL], spec.cells())
    assert spec.cell(CELL) == {
        "name": CELL, "config": "hotspot-py-n64-wal", "traffic": "restart",
        "chips": 1, "why": spec.cell(CELL)["why"]}
    # appended behind the 23 entries that were there, and ahead of any
    # that came later
    assert [m["name"] for m in doc["per_layer"][23:26]] == NEW_METRICS
    assert [m["name"] for m in doc["end_to_end"]][:3] == [
        "units_per_s", "worker_fed_pct", "setup_s"]
    for m in doc["per_layer"][23:26]:
        assert (m["layer"], m["moves"], m["workloads"], m["better"]) == (
            "write-ahead log + recovery", "worker_fed_pct", [CELL],
            "higher" if m["name"] == "wal_records_per_commit" else "lower")
    # no list that was there took the new cell
    for m in doc["end_to_end"] + doc["per_layer"][:23]:
        assert CELL not in m.get("workloads", [])
    assert spec.plane(CELL).__name__ == "benchmarks.planes.python_wal"
    e2e = sorted(m["name"] for m in spec.metrics("end_to_end", CELL))
    assert e2e == ["setup_s", "units_per_s", "worker_fed_pct"]
    listed = [m["name"] for m in spec.metrics("per_layer", CELL)]
    assert in_order(["worker_blocked_pct", "match_wait_p95_ms",
                     "fetch_rtt_p50_ms", "units_per_fetch",
                     "device_solves_per_s"] + NEW_METRICS, listed)
    # nothing of the planner's spans or the native daemons' artefacts: the
    # Python plane's durable cell emits neither
    assert not {"planner_busy_pct", "daemon_busy_pct"} & set(listed)


def test_the_configuration_is_its_twin_with_the_log_on():
    spec = Spec(ROOT)
    config, twin = spec.config(CELL), spec.config(TWIN)
    differs = {key for key in set(config) | set(twin)
               if config.get(key) != twin.get(key)}
    assert differs == {"name", "source", "plane", "deployment", "warm_s",
                       "fed_warm_s", "assumed", "guarantees",
                       "not_exercised"}
    # every width of the source, and no literal of the log's settings
    assert config["config"] == twin["config"]
    assert not [key for key in config["config"] if key.startswith("wal")]
    assert config["plane"] == "python_wal"
    assert config["warm_s"] == config["fed_warm_s"] == \
        config["assumed"]["warm_s"]
    assert config["guarantees"][:2] == twin["guarantees"][:2]
    assert "fsynced" in config["guarantees"][2]
    assert "exactly once by the restarted fleet" in config["guarantees"][2]
    assert len(config["not_exercised"]) == 4
    for key in ("wal_dir", "warm_s_why", "kill_point", "wal_fsync_ms",
                "wal_max_bytes"):
        assert config["assumed"][key]
    mix, bulk = spec.traffic(CELL), spec.traffic(TWIN)
    for key in ("put_routing", "pace", "flush_every", "work_mult",
                "needs_backlog"):
        assert mix[key] == bulk[key]
    assert set(mix["restart"]) == {"kill", "at", "then"}
    assert n_units(config, mix, spec.run_seconds) == int(
        1260 * (config["warm_s"] + 20))
    # the documented defaults are what a world of the plane runs
    cfg = plane.world_config(config, mix, "/nowhere", 12345, "/nowhere/wal")
    assert (cfg.wal_dir, cfg.wal_fsync_ms, cfg.wal_max_bytes) == (
        "/nowhere/wal", 5.0, 64 << 20)
    assert cfg.put_routing == "home" and cfg.solver_host_threshold == 0
    assert cfg == dataclasses.replace(
        base.world_config(twin, bulk, "/nowhere", 12345),
        wal_dir="/nowhere/wal")


def test_the_plane_imports_no_jax():
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from benchmarks.planes import python_wal; "
         "from benchmarks.traffic import restart_app; "
         "from benchmarks.reference import durable_pool; "
         "from benchmarks import control_restart; "
         "print('jax' in sys.modules, 'adlb_tpu' in sys.modules)" % ROOT],
        capture_output=True, text=True, timeout=60)
    assert out.stdout.split() == ["False", "False"], out.stderr


# ------------------------------- the plane, with the worlds stood in for

SMALL = {"app_ranks": 9, "servers": 2, "types": [1], "work_us": 20000,
         "fetch_batch": 1, "warm_s": 1.0, "fed_warm_s": 1.0,
         "solve_shape": [64, 16], "config": {}}
#: a world key of the program's own form (``transport_shm.new_world_key``)
KEY = f"adlb{uuid.uuid4().hex[:12]}"


def sleeping_fleet(logdir: str, ranks: int, producer_after: float):
    """World A's stand-in, run by the plane's helper in its own session:
    ``ranks`` forked children that hold a ring of the world open and sleep,
    and a producer's record after a moment. Nothing of it ends by itself."""

    def world(_config, _app, _cfg, _limit_s):
        path = os.path.join(plane.SHM_DIR, f"{KEY}.0to1")
        with open(path, "wb+") as f:
            f.truncate(4096)
            ring = mmap.mmap(f.fileno(), 4096)
        bell = os.path.join(plane.SHM_DIR, f"{KEY}.bell.0")
        os.mkfifo(bell)  # held open as a rank holds its doorbell
        os.open(bell, os.O_RDONLY | os.O_NONBLOCK)
        for _rank in range(ranks):
            if os.fork() == 0:
                while True:
                    time.sleep(1.0)
        if producer_after is not None:
            with open(os.path.join(logdir, "p0.start"), "wb") as f:
                f.write(struct.pack("<dd", 10.0, 14.0))
            time.sleep(producer_after)
            records.write_producer_log(logdir, 1200, 10.0, 11.0, 14.0)
            while True:
                time.sleep(1.0)
        ring.close()

    return world


def served_world(plan, logdir: str, recovered):
    """World B's stand-in: the plain durable pool's deliveries logged as
    the clients would, and what restarted servers report."""

    def world(config, _app, cfg, _limit_s):
        assert cfg.wal_dir.endswith("wal") and cfg.wal_fsync_ms == 5.0
        rcs = control.stand_in_logs(
            plan, durable_pool.deliveries(plan), logdir,
            config["app_ranks"] - 1, 2.0, config["warm_s"])
        stats = {}
        for i, rank in enumerate((9, 10)):
            stats[rank] = {
                "reactor_loop_s": 5.0, "reactor_busy_s": 1.0,
                "reactor_busy_by_second": {}, "wal_recovered": recovered[i],
                "wal_replayed": 3 * recovered[i], "wal_recover_s": 0.25,
                "wal_syncs": 10, "wal_records": 40, "wal_bytes": 4000,
                "wal_flush_by_second": {1: 0.5, 2: 0.25}}
        facts = {"platform": "tpu", "path": "standin", "device_kind": "x",
                 "device_count": 1, "memory_peak_bytes": 0,
                 "device_solves": 1, "host_solves": 0, "device_failures": 0}
        return types.SimpleNamespace(
            app_results=dict(enumerate(rcs)), server_stats=stats,
            solver_facts=lambda: facts)

    return world


def standin_ctx(tmp_path, monkeypatch, recovered=(1190, 10),
                producer_after=0.3):
    """A run's ``ctx`` over a checkout-shaped scratch, with both worlds of
    the plane stood in for and the look for a chip skipped."""
    scratch = tmp_path / ".bench_scratch" / CELL
    logdir = scratch / "logs"
    logdir.mkdir(parents=True)
    mix = Spec(ROOT).traffic(CELL)
    plan = make_plan(SMALL, mix, 2**31 + 9, 2.0)
    assert len(plan) == 1200
    plan.tofile(scratch / "plan.bin")
    # world A is launched by the plane's helper process, world B by this one
    here = os.getpid()
    world_a = sleeping_fleet(str(logdir), 5, producer_after)
    world_b = served_world(plan, str(logdir), recovered)
    monkeypatch.setattr(
        plane, "launch", lambda *args: (
            world_b if os.getpid() == here else world_a)(*args))
    inputs = greedy.seeded_snapshot(7, 64, 16, 1, -(2**31) + 1)
    monkeypatch.setattr(
        base, "solve_after_world", lambda config, seed, chips: (
            inputs, greedy.greedy_assign(*inputs, -(2**31) + 1),
            -(2**31) + 1, 0.0))
    monkeypatch.setattr(base, "require_facility", lambda: None)  # no chip
    # another test of this process may have loaded JAX: the plane's check
    # that the harness kept off it while a world ran is for real worlds
    monkeypatch.setattr(plane, "sys", types.SimpleNamespace(modules={}))
    said = []
    ctx = types.SimpleNamespace(
        config=SMALL, mix=mix, seed=7, seconds=2.0, trace=False, chips=1,
        scratch=str(scratch), logdir=str(logdir),
        plan_path=str(scratch / "plan.bin"), plan=plan, say=said.append)
    return ctx, said


def shm_left() -> list:
    return [n for n in os.listdir(plane.SHM_DIR) if n.startswith(KEY)]


def test_world_a_is_killed_reaped_and_swept_and_the_run_reports(
        tmp_path, monkeypatch):
    ctx, said = standin_ctx(tmp_path, monkeypatch)
    rec = plane.run(ctx)
    with open(os.path.join(ctx.scratch, "restart.json")) as f:
        restart = json.load(f)
    # the helper and its five sleeping ranks: killed within 50 ms of the
    # producer's record, gone, their ring and FIFO swept
    assert restart["killed"] == 6 and restart["shm_key"] == KEY
    assert restart["shm_swept"] == 2 and shm_left() == []
    assert 0.0 <= restart["t_kill"] - restart["t_p0_seen"] < 0.05
    assert restart["t_kill"] <= restart["t_gone"] <= restart["t_world_b"]
    assert all(plane._gone(pid)
               for pid in plane.group_members(restart["group"]))
    # the restart's own numbers, on the run's earlier lines and kept
    assert restart["n_acked"] == restart["wal_recovered"] == 1200
    assert restart["wal_recovered_hot"] == 1190
    assert restart["durable_puts_per_s"] == pytest.approx(1200.0)
    assert restart["wal_recover_s"] == 0.25
    line = [text for text in said if text.startswith("restart: ")][0]
    for name in ("durable_puts_per_s", "restart_s", "wal_recover_s",
                 "wal_recovered"):
        assert f"{name}=" in line
    kept = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL,
                        "restart-seed7-trace0.json")
    with open(kept) as f:
        assert json.load(f) == restart
    # what python.py's run returns, world B's; t_world is world A's call
    assert set(rec) == {"device", "facts", "flight", "client_rcs", "world_s",
                        "t_world", "solve_inputs", "solve_got", "pad_prio",
                        "trace_dir", "trace_window_s"}
    assert rec["t_world"] == restart["t_world_a"] < restart["t_world_b"]
    assert rec["client_rcs"] == [0] * 9 and rec["trace_dir"] is None
    with open(os.path.join(ctx.scratch, "servers.json")) as f:
        servers = json.load(f)
    assert servers["9"]["wal_recovered"] == 1190
    assert servers["9"]["wal_flush_by_second"] == {"1": 0.5, "2": 0.25}
    assert servers["10"]["reactor_busy_s"] == 1.0
    # and the harness judges it as any run
    bench_run.check_planner(rec["facts"])
    args = types.SimpleNamespace(workload=CELL, trace=0)
    result = bench_run.finish(Spec(ROOT), args, ctx, rec, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"units_per_s", "worker_fed_pct",
                                      "setup_s"}


@pytest.mark.parametrize("recovered,producer_after,says", [
    ((0, 0), 0.3, "recovered nothing"),
    ((1190, 9), 0.3, "recovered 1199 units, the producer holds "
                     "acknowledgements for 1200"),
    ((1190, 10), None, "world A ended by itself"),
])
def test_a_run_that_measured_another_system_is_refused(
        tmp_path, monkeypatch, recovered, producer_after, says):
    ctx, _said = standin_ctx(tmp_path, monkeypatch, recovered,
                             producer_after)
    with pytest.raises(SystemExit, match=says) as refused:
        plane.run(ctx)
    assert "this run measured another system" in str(refused.value)
    assert shm_left() == []  # swept whatever the verdict


def test_a_world_a_that_never_finishes_fails_inside_its_time_limit(
        tmp_path, monkeypatch):
    ctx, _said = standin_ctx(tmp_path, monkeypatch)
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="had not finished"):
        plane.ingest_and_kill(ctx, None, types.SimpleNamespace(
            wal_dir=ctx.scratch, fabric="shm"), 1.0, done=lambda _dir: False)
    assert time.monotonic() - t0 < 1.0 + plane.GONE_WAIT_S / 2
    assert shm_left() == []


def test_without_restart_in_the_mix_one_world_runs_and_nobody_is_killed(
        tmp_path, monkeypatch):
    ctx, said = standin_ctx(tmp_path, monkeypatch)
    ctx.mix = {k: v for k, v in ctx.mix.items() if k != "restart"}
    records.write_producer_log(ctx.logdir, 1200, 10.0, 11.0, 14.0)
    served = served_world(ctx.plan, ctx.logdir, (0, 0))
    monkeypatch.setattr(plane, "launch", served)
    rec = plane.run(ctx)
    assert rec["client_rcs"] == [0] * 9 and rec["t_world"] > 0
    assert not os.path.exists(os.path.join(ctx.scratch, "restart.json"))
    assert not [text for text in said if text.startswith("restart: ")]


def test_a_program_whose_servers_do_not_say_what_they_recovered_is_refused(
        tmp_path, monkeypatch):
    from adlb_tpu.runtime.server import Server

    ctx, _said = standin_ctx(tmp_path, monkeypatch)
    monkeypatch.delattr(Server, "wal_stats")  # the parent commit's
    with pytest.raises(SystemExit, match="no world was started"):
        plane.run(ctx)
    assert not os.path.exists(os.path.join(ctx.logdir, "p0.bin"))


# -------------------------------------------------------------- the control


@pytest.mark.parametrize("guarantee,number,value", [
    ("ack_before_log", "missing_units", 52),
    ("replay_twice", "duplicated_units", 52),
    ("torn_accepted", "altered_units", 1),
])
def test_each_broken_durable_pool_is_not_correct(guarantee, number, value):
    out = control_restart.judge(CELL, seed=2**31 + 35, seconds=2.0,
                                guarantee=guarantee)
    warm_s = Spec(ROOT).config(CELL)["fed_warm_s"]
    assert out["units"] == int(1260 * (warm_s + 2.0)) == 52920
    assert out["correct"] is False
    assert out["compared"][number] == {"value": value, "limit": 0}
    others = {"missing_units", "duplicated_units", "altered_units"} - {number}
    assert all(out["compared"][name]["value"] == 0 for name in others)


def test_the_sound_durable_pool_is_correct_and_the_control_says_so(capsys):
    out = control_restart.judge(CELL, seed=35, seconds=2.0,
                                guarantee="durable")
    assert out["correct"] is True
    assert all(v == {"value": 0, "limit": 0}
               for v in out["compared"].values())
    assert control_restart.main(["--workload", CELL, "--seeds", "5",
                                 "--seconds", "2"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line]
    assert [(o["guarantee"], o["correct"]) for o in lines] == [
        ("durable", True), ("ack_before_log", False),
        ("replay_twice", False), ("torn_accepted", False)]


def test_the_plain_durable_pool_keeps_what_it_logged_and_nothing_else():
    pool = durable_pool.DurablePool()
    units = [(10 + i, 50000, i) for i in range(5)]
    assert all(pool.put(u) for u in units)
    assert pool.get() == units[0]          # first in, first out
    pool.crash(writing=(99, 50000, 9))     # memory gone, one record torn
    assert pool.get() is None
    assert pool.recover() == 5             # what was logged, each once
    assert [pool.get() for _ in range(6)] == units + [None]
    with pytest.raises(ValueError):
        durable_pool.DurablePool("eventually")


# -------------------------------------------------------------- the readers


def reader_run(tmp_path, servers=None) -> dict:
    cell_dir = tmp_path / ".bench_scratch" / CELL
    cell_dir.mkdir(parents=True, exist_ok=True)
    if servers is not None:
        (cell_dir / "servers.json").write_text(json.dumps(servers))
    return {"bench_dir": str(tmp_path / "benchmarks"), "cell": CELL,
            "config": {"app_ranks": 64, "servers": 16}, "trace": None,
            "window": types.SimpleNamespace(t0=99.5, t_end=103.5)}


def test_wal_flush_ms_per_s_takes_the_hot_servers_seconds_in_the_window(
        tmp_path):
    assert wal_flush_ms_per_s.read(reader_run(tmp_path)) is None
    # a program without the counter (the parent commit)
    run = reader_run(tmp_path, {"64": {"reactor_busy_by_second": {}}})
    assert wal_flush_ms_per_s.read(run) is None
    by_second = {"99": 0.9, "100": 0.06, "101": 0.03, "102": 0.09,
                 "103": 0.9}
    run = reader_run(tmp_path, {
        "64": {"wal_flush_by_second": by_second},
        "65": {"wal_flush_by_second": {"100": 1.0, "101": 1.0}}})
    # whole seconds inside [99.5, 103.5]: 100, 101, 102
    assert wal_flush_ms_per_s.read(run) == pytest.approx(60.0)
    run["window"] = types.SimpleNamespace(t0=100.2, t_end=100.9)
    assert wal_flush_ms_per_s.read(run) is None


def test_wal_records_per_commit_divides_the_hot_servers_counters(tmp_path):
    assert wal_records_per_commit.read(reader_run(tmp_path)) is None
    run = reader_run(tmp_path, {"64": {"wal_syncs": 0, "wal_records": 0}})
    assert wal_records_per_commit.read(run) is None
    run = reader_run(tmp_path, {"64": {"wal_syncs": 400, "wal_records": 3000},
                                "65": {"wal_syncs": 1, "wal_records": 99}})
    assert wal_records_per_commit.read(run) == pytest.approx(7.5)


def test_wal_fsync_p50_ms_is_the_median_commit_span_on_any_host_line(
        tmp_path):
    assert wal_fsync_p50_ms.read(reader_run(tmp_path)) is None  # untraced
    run = dict(reader_run(tmp_path), trace={"planes": []})
    assert wal_fsync_p50_ms.read(run) is None  # traced, and no trace file
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["adlb.wal.fsync", 0, 9_000_000]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "planner", "events": [["adlb.round", 0, 4_000_000],
                                           ["adlb.solve", 10, 2_000_000]]},
            {"name": "reactor", "events": [
                ["adlb.wal.fsync", 100, 200_000],
                ["adlb.wal.fsync", 5_000_100, 400_000],
                ["adlb.wal.fsync", 10_000_100, 150_000]]}]}]}
    assert wal_fsync_p50_ms.median_ms(trace) == pytest.approx(0.2)
    # a program without the span (the parent commit) gives nothing
    trace["planes"][1]["lines"].pop()
    assert wal_fsync_p50_ms.median_ms(trace) is None
