"""What PR 37 added to the benchmark: the replicated configuration, the
``killhot`` mix, the plane that kills the master alone in mid-flood, the
plain replicated pool with its control, and three readers. The spec
resolves them and nothing that was there changed; the plane's
orchestration is driven with the world stood in for (a real process dies
at the marker, no ADLB world runs); each refusal fires; the control's
broken pools come out not correct; the readers' arithmetic is checked on
synthetic records. CPU, no chip.
(``tests/test_failover_world.py`` drives the same plane over real worlds.)"""

import dataclasses
import json
import multiprocessing
import os
import struct
import time
import types

import numpy as np
import pytest

from benchmarks import control, control_failover, run as bench_run
from benchmarks.metrics import (repl_entries_per_frame, repl_flush_ms_per_s,
                                repl_flush_p50_ms)
from benchmarks.planes import python as base
from benchmarks.planes import python_failover as plane
from benchmarks.reduce import failover as reduce_failover
from benchmarks.reduce import records
from benchmarks.reference import greedy, pool, replicated_pool
from benchmarks.spec import ROOT, Spec
from benchmarks.traffic import killhot_app
from benchmarks.traffic.generate import make_plan, n_units
from test_bench_spec import in_order

CELL = "hotspot-py-n64-failover.killhot"
TWIN = "hotspot-py-n64.bulk"
NEW_METRICS = ["repl_flush_p50_ms", "repl_flush_ms_per_s",
               "repl_entries_per_frame"]


# ------------------------------------------------------------- the spec


def test_the_spec_resolves_the_additions_and_nothing_else_changed():
    spec = Spec(ROOT)
    spec.check_files()
    doc = spec.doc
    # what the benchmark held before and this configuration behind it; a
    # later benchmark may add cells anywhere and retire some, so only the
    # configurations' prefix and the cells this one stands among are held
    assert [c["name"] for c in doc["configs"]][:5] == [
        "hotspot-native-n128", "hotspot-native-n64", "hotspot-py-n64",
        "hotspot-py-n64-wal", "hotspot-py-n64-failover"]
    assert in_order([
        "hotspot-native-n128.bulk", "hotspot-native-n64.bulk", TWIN,
        "hotspot-py-n64-wal.restart", CELL], spec.cells())
    assert spec.cell(CELL) == {
        "name": CELL, "config": "hotspot-py-n64-failover",
        "traffic": "killhot", "chips": 1, "why": spec.cell(CELL)["why"]}
    assert [m["name"] for m in doc["per_layer"][26:29]] == NEW_METRICS
    assert [m["name"] for m in doc["end_to_end"]][:3] == [
        "units_per_s", "worker_fed_pct", "setup_s"]
    for m in doc["per_layer"][26:29]:
        assert (m["layer"], m["moves"], m["workloads"], m["better"]) == (
            "replication + failover", "worker_fed_pct", [CELL],
            "higher" if m["name"] == "repl_entries_per_frame" else "lower")
    # no list that was there took the new cell
    for m in doc["end_to_end"] + doc["per_layer"][:26]:
        assert CELL not in m.get("workloads", [])
    assert spec.plane(CELL).__name__ == "benchmarks.planes.python_failover"
    e2e = sorted(m["name"] for m in spec.metrics("end_to_end", CELL))
    assert e2e == ["setup_s", "units_per_s", "worker_fed_pct"]
    listed = [m["name"] for m in spec.metrics("per_layer", CELL)]
    assert listed[:8] == ["worker_blocked_pct", "match_wait_p95_ms",
                          "fetch_rtt_p50_ms", "units_per_fetch",
                          "device_solves_per_s"] + NEW_METRICS


def test_the_configuration_is_its_twin_with_failover_on():
    spec = Spec(ROOT)
    config, twin = spec.config(CELL), spec.config(TWIN)
    differs = {key for key in set(config) | set(twin)
               if config.get(key) != twin.get(key)}
    assert differs == {"name", "source", "plane", "deployment", "warm_s",
                       "fed_warm_s", "config", "assumed", "guarantees",
                       "not_exercised"}
    # every width of the source; the policy is the one key that differs,
    # and the client's wait is stated by being left alone
    assert config["config"] == dict(twin["config"],
                                    on_server_failure="failover")
    assert not [key for key in config["config"]
                if key.startswith(("failover", "wal"))]
    assert config["plane"] == "python_failover"
    assert config["warm_s"] == config["fed_warm_s"] == \
        config["assumed"]["warm_s"] == config["assumed"]["fed_warm_s"]
    assert config["guarantees"][:2] == twin["guarantees"][:2]
    assert "on the wire to the server's ring buddy" in config["guarantees"][2]
    assert "re-sent under its put id and stored once" in \
        config["guarantees"][2]
    assert len(config["not_exercised"]) == 4
    for key in ("warm_s_why", "kill_point", "failover_client_wait",
                "ops_announce_dir"):
        assert config["assumed"][key]
    assert len(config["source"]) <= 200
    mix, bulk = spec.traffic(CELL), spec.traffic(TWIN)
    for key in ("put_routing", "pace", "flush_every", "work_mult",
                "needs_backlog"):
        assert mix[key] == bulk[key]
    assert set(mix["failover"]) == {"kill", "at", "then"}
    assert n_units(config, mix, spec.run_seconds) == int(
        1260 * (config["warm_s"] + 20))
    # the documented defaults are what a world of the plane runs
    cfg = plane.world_config(config, mix, "/nowhere", 12345, "/nowhere/ops")
    assert (cfg.on_server_failure, cfg.failover_client_wait,
            cfg.ops_announce_dir, cfg.wal_dir) == (
        "failover", 15.0, "/nowhere/ops", None)
    assert cfg.put_routing == "home" and cfg.solver_host_threshold == 0
    assert cfg == dataclasses.replace(
        base.world_config(twin, bulk, "/nowhere", 12345),
        on_server_failure="failover", ops_announce_dir="/nowhere/ops")


def test_the_plane_imports_no_jax():
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from benchmarks.planes import python_failover; "
         "from benchmarks.traffic import killhot_app; "
         "from benchmarks.reference import replicated_pool; "
         "from benchmarks.reduce import failover; "
         "from benchmarks import control_failover; "
         "print('jax' in sys.modules, 'adlb_tpu' in sys.modules)" % ROOT],
        capture_output=True, text=True, timeout=60)
    assert out.stdout.split() == ["False", "False"], out.stderr


# -------------------------------- the plane, with the world stood in for

SMALL = {"app_ranks": 9, "servers": 3, "types": [1], "work_us": 20000,
         "fetch_batch": 1, "warm_s": 1.0, "fed_warm_s": 1.0,
         "solve_shape": [64, 16],
         "config": {"on_server_failure": "failover"}}
DEAD, BUDDY, THIRD = 9, 10, 11
FACTS = {"platform": "tpu", "path": "standin", "device_kind": "x",
         "device_count": 1, "memory_peak_bytes": 0, "device_solves": 1,
         "host_solves": 0, "device_failures": 0}


def _sleep_forever():
    while True:
        time.sleep(1.0)


def standin_world(plan, logdir: str, fault: str | None):
    """The world's stand-in, in the plane's own process as ``spawn_world``
    is: a child named as ``spawn_world`` names the master's sleeps until it
    is killed; the producer's flushes and markers appear as
    ``killhot_app``'s would; once ``killed`` is there the plain replicated
    pool's deliveries are logged as the clients would log them, after the
    kill, and the servers that are left report. ``fault`` plants what each
    refusal is for."""
    from adlb_tpu.types import InfoKey

    def world(config, _app, cfg, _limit_s):
        assert cfg.on_server_failure == "failover"
        assert os.path.isdir(cfg.ops_announce_dir)
        master = multiprocessing.get_context("fork").Process(
            target=_sleep_forever, name=f"adlb-rank-{DEAD}", daemon=True)
        master.start()
        other = multiprocessing.get_context("fork").Process(
            target=_sleep_forever, name=f"adlb-rank-{BUDDY}", daemon=True)
        other.start()
        try:
            t_first = time.monotonic() - 0.1  # the first half, acknowledged
            flushes = [(t_first + 0.01 * k, t_first + 0.01 * k + 0.004, 100)
                       for k in range(6)]
            if fault == "flood_done":
                records.write_producer_log(logdir, 1200, t_first,
                                           t_first + 0.1, t_first + 3.0)
            with open(os.path.join(logdir, "p0.half"), "wb") as f:
                f.write(killhot_app.HALF.pack(time.monotonic(), 600))
            give_up = time.monotonic() + 10.0
            killed = os.path.join(logdir, "killed")
            # the plane creates the file and then writes its two stamps:
            # wait for both, not for the name alone
            while not (os.path.exists(killed)
                       and os.path.getsize(killed) >= 16):
                assert time.monotonic() < give_up, "nobody was killed"
                time.sleep(0.005)
            with open(killed, "rb") as f:
                t_kill, t_gone = struct.unpack("<dd", f.read(16))
            assert not master.is_alive() and master.exitcode == -9
            assert other.is_alive()  # killed alone
            t_now = time.monotonic()
            flushes += [(t_kill - 0.001, t_now + 0.25, 100)]
            flushes += [(t_now + 0.25 + 0.01 * k, t_now + 0.255 + 0.01 * k,
                         100) for k in range(5)]
            np.asarray(flushes, dtype=killhot_app.FLUSH).tofile(
                os.path.join(logdir, "p0.flushes"))
            rcs = control.stand_in_logs(
                plan, replicated_pool.deliveries(plan), logdir,
                config["app_ranks"] - 1, 2.0, config["warm_s"])
            logs = records.read_logs(logdir)
            # onto the run's own clock: every delivery after the kill
            shift = t_now + 0.3
            for rank in range(1, config["app_ranks"]):
                units = logs.units[logs.unit_rank == rank].copy()
                fetches = logs.fetches[logs.fetch_rank == rank].copy()
                for arr, cols in ((units, ("t_put", "t_call", "t_ret",
                                           "t_done", "t_end")),
                                  (fetches, ("t_call", "t_ret"))):
                    for col in cols:
                        arr[col] += shift
                if fault == "early_delivery" and rank == 1:
                    units["t_ret"][0] = t_kill - 0.5
                records.write_worker_log(logdir, rank, units, fetches)
            records.write_producer_log(logdir, 1200, t_first, t_now + 0.3,
                                       shift + config["warm_s"] + 2.0)
        finally:
            for proc in (master, other):
                proc.kill()
                proc.join()
        stats = {}
        for rank in (BUDDY, THIRD):
            hot = rank == BUDDY
            stats[rank] = {
                int(InfoKey.NUM_FAILOVERS): float(
                    hot and fault != "no_promotion"),
                int(InfoKey.FAILOVER_LOST): float(
                    hot and fault == "lost") * 3,
                int(InfoKey.FAILOVER_MTTR_MS): 450.0 * hot,
                "reactor_loop_s": 5.0, "reactor_busy_s": 1.0,
                "reactor_busy_by_second": {}, "repl_frames": 40 * (1 + hot),
                "repl_entries": 100 * (1 + 5 * hot), "repl_bytes": 4000,
                "repl_applied": 700, "repl_flush_s": 0.5,
                "repl_flush_by_second": {1: 0.5, 2: 0.25},
                "failover_adopted": 640 * (
                    hot and fault != "nothing_adopted"),
                "failover_resent_puts": 60 * hot,
                "failover_deduped_puts": 40 * hot}
        stats[BUDDY]["master_failover_mttr_ms"] = 400.0
        stats[BUDDY if fault != "other_facts" else THIRD]["solver"] = \
            dict(FACTS)
        if fault == "dead_reported":
            stats[DEAD] = dict(stats[THIRD])
        casualties = {None: [DEAD], "no_casualty": [],
                      "other_casualty": [THIRD]}.get(fault, [DEAD])

        def solver_facts():
            for s in stats.values():
                if "solver" in s:
                    return s["solver"]

        return types.SimpleNamespace(
            app_results=dict(enumerate(rcs)), server_stats=stats,
            server_casualties=casualties, solver_facts=solver_facts)

    return world


def standin_ctx(tmp_path, monkeypatch, fault=None):
    """A run's ``ctx`` over a checkout-shaped scratch, with the world of
    the plane stood in for and the look for a chip skipped."""
    scratch = tmp_path / ".bench_scratch" / CELL
    logdir = scratch / "logs"
    logdir.mkdir(parents=True)
    mix = Spec(ROOT).traffic(CELL)
    plan = make_plan(SMALL, mix, 2**31 + 37, 2.0)
    assert len(plan) == 1200
    plan.tofile(scratch / "plan.bin")
    monkeypatch.setattr(plane, "launch",
                        standin_world(plan, str(logdir), fault))
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


def test_the_master_is_killed_alone_at_the_marker_reaped_and_the_run_reports(
        tmp_path, monkeypatch):
    ctx, said = standin_ctx(tmp_path, monkeypatch)
    rec = plane.run(ctx)
    with open(os.path.join(ctx.scratch, "failover.json")) as f:
        death = json.load(f)
    # one process, the master's, killed within 50 ms of the marker, reaped
    assert death["dead"] == DEAD and death["promoted"] == BUDDY
    assert death["exitcode"] == -9 and death["server_casualties"] == [DEAD]
    assert 0.0 <= death["kill_after_half_s"] < 0.05
    assert death["t_half"] <= death["t_half_seen"] <= death["t_kill"] \
        <= death["t_gone"]
    with pytest.raises(ProcessLookupError):
        os.kill(death["pid"], 0)
    # the death's own numbers, on the run's earlier lines and kept
    assert death["n_acked_at_half"] == death["n_acked_at_kill"] == 600
    assert death["n_acked"] == 1200 and death["adopted"] == 640
    assert death["replicated_puts_per_s"] == pytest.approx(600 / 0.054)
    assert death["puts_after_per_s"] == pytest.approx(500 / 0.045)
    assert 0.25 < death["producer_stall_s"] < 0.35
    assert (death["promote_ms"], death["master_promote_ms"]) == (450.0, 400.0)
    assert (death["resent_puts"], death["deduped_puts"]) == (60, 40)
    assert death["first_remote_s"] > 0.0
    assert death["servers"][str(BUDDY)] == {
        "failover_mttr_ms": 450.0, "master_failover_mttr_ms": 400.0,
        "NUM_FAILOVERS": 1.0, "FAILOVER_LOST": 0.0, "adopted": 640}
    line = [text for text in said if text.startswith("failover: ")][0]
    for name in plane.LINE_KEYS:
        assert f"{name}=" in line
    kept = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL,
                        "failover-seed7-trace0.json")
    with open(kept) as f:
        assert json.load(f) == death
    # what python.py's run returns, from the servers that are left
    assert set(rec) == {"device", "facts", "flight", "client_rcs", "world_s",
                        "t_world", "solve_inputs", "solve_got", "pad_prio",
                        "trace_dir", "trace_window_s"}
    assert rec["facts"] == FACTS and rec["client_rcs"] == [0] * 9
    assert rec["trace_dir"] is None and rec["t_world"] < death["t_kill"]
    with open(os.path.join(ctx.scratch, "servers.json")) as f:
        servers = json.load(f)
    assert sorted(servers) == [str(BUDDY), str(THIRD)]
    assert servers[str(BUDDY)]["repl_flush_by_second"] == {"1": 0.5,
                                                           "2": 0.25}
    assert servers[str(BUDDY)]["failover_adopted"] == 640
    assert servers[str(THIRD)]["reactor_busy_s"] == 1.0
    # and the harness judges it as any run
    bench_run.check_planner(rec["facts"], 1)
    args = types.SimpleNamespace(workload=CELL, trace=0)
    result = bench_run.finish(Spec(ROOT), args, ctx, rec, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"units_per_s", "worker_fed_pct",
                                      "setup_s"}


@pytest.mark.parametrize("fault,says", [
    ("no_casualty", r"counts the server casualties \[\], the cell kills "
                    r"rank 9 alone"),
    ("other_casualty", r"counts the server casualties \[11\]"),
    ("dead_reported", "the killed rank 9 reported at the world's end"),
    ("no_promotion", "the servers count 0 promotions"),
    ("nothing_adopted", "the promoted server 10 adopted no unit"),
    ("lost", "count 3 units lost to the failover"),
    ("flood_done", "the flood outran it"),
    ("early_delivery", "1 units were delivered before the kill"),
    ("other_facts", "the planner's facts are not the promoted server 10's"),
])
def test_a_run_that_measured_another_system_is_refused(
        tmp_path, monkeypatch, fault, says):
    ctx, _said = standin_ctx(tmp_path, monkeypatch, fault)
    with pytest.raises(SystemExit, match=says) as refused:
        plane.run(ctx)
    assert "this run measured another system" in str(refused.value)
    assert not os.path.exists(os.path.join(ctx.scratch, "failover.json"))


def test_a_producer_that_never_reaches_half_fails_inside_its_time_limit(
        tmp_path):
    killer = plane.Killer(str(tmp_path), DEAD, half_wait_s=0.3)
    t0 = time.monotonic()
    killer.start()
    killer.join(5.0)
    assert not killer.is_alive() and time.monotonic() - t0 < 1.0
    assert "had not acknowledged half the plan" in str(killer.error)
    assert not os.path.exists(tmp_path / "killed")
    # and a marker with no such child of this process kills nobody
    with open(tmp_path / "p0.half", "wb") as f:
        f.write(killhot_app.HALF.pack(time.monotonic(), 1))
    killer = plane.Killer(str(tmp_path), DEAD)
    killer.start()
    killer.join(5.0)
    assert "no process of rank 9" in str(killer.error)
    assert not os.path.exists(tmp_path / "killed")


def test_the_tracer_asks_the_master_of_the_hour(tmp_path):
    tracer = plane.Tracer(str(tmp_path), str(tmp_path / "trace"), 2.0,
                          str(tmp_path), DEAD)
    (tmp_path / "ops_endpoint.json").write_text(json.dumps(
        {"host": "127.0.0.1", "port": 4242, "master": DEAD, "epoch": 0}))
    with pytest.raises(RuntimeError, match="still the dead master's"):
        tracer.ops_port
    (tmp_path / "ops_endpoint.json").write_text(json.dumps(
        {"host": "127.0.0.1", "port": 4343, "master": BUDDY, "epoch": 2}))
    assert tracer.ops_port == 4343


def test_without_failover_in_the_mix_one_world_runs_and_nobody_is_killed(
        tmp_path, monkeypatch):
    ctx, said = standin_ctx(tmp_path, monkeypatch)
    ctx.mix = {k: v for k, v in ctx.mix.items() if k != "failover"}

    def world(config, _app, _cfg, _limit_s):
        rcs = control.stand_in_logs(
            ctx.plan, pool.deliveries(ctx.plan), ctx.logdir,
            config["app_ranks"] - 1, 2.0, config["warm_s"])
        stats = {DEAD: {"solver": dict(FACTS), "repl_frames": 7}}
        return types.SimpleNamespace(
            app_results=dict(enumerate(rcs)), server_stats=stats,
            server_casualties=[], solver_facts=lambda: stats[DEAD]["solver"])

    monkeypatch.setattr(plane, "launch", world)
    rec = plane.run(ctx)
    assert rec["client_rcs"] == [0] * 9 and rec["facts"] == FACTS
    assert not os.path.exists(os.path.join(ctx.scratch, "failover.json"))
    assert not [text for text in said if text.startswith("failover: ")]
    with open(os.path.join(ctx.scratch, "servers.json")) as f:
        assert json.load(f)[str(DEAD)]["repl_frames"] == 7


def test_a_program_whose_servers_do_not_say_what_they_adopted_is_refused(
        tmp_path, monkeypatch):
    from adlb_tpu.runtime.server import Server

    ctx, _said = standin_ctx(tmp_path, monkeypatch)
    monkeypatch.delattr(Server, "failover_stats")  # the parent commit's
    with pytest.raises(SystemExit, match="no world was started"):
        plane.run(ctx)
    assert not os.path.exists(os.path.join(ctx.logdir, "p0.half"))


# ------------------------------------------------------------ the traffic


def test_the_producers_proxy_times_every_flush_and_marks_the_half(tmp_path):
    from adlb_tpu.types import ADLB_SUCCESS

    calls = []
    inner = types.SimpleNamespace(
        rank=0, put=lambda *a: calls.append("put") or ADLB_SUCCESS,
        iput=lambda payload, wtype: calls.append("iput") or ADLB_SUCCESS,
        flush_puts=lambda: calls.append("flush") or ADLB_SUCCESS)
    proxy = killhot_app.CountingCtx(inner, str(tmp_path), 9)
    for n in (2, 2):
        for _ in range(n):
            assert proxy.iput(b"x", 1) == ADLB_SUCCESS
        assert proxy.flush_puts() == ADLB_SUCCESS
    assert killhot_app.read_half(str(tmp_path)) is None  # 4 of 9
    assert proxy.iput(b"x", 1) == ADLB_SUCCESS
    assert proxy.flush_puts() == ADLB_SUCCESS
    t_half, n_half = killhot_app.read_half(str(tmp_path))
    assert n_half == 5 and t_half <= time.monotonic()
    proxy.iput(b"x", 1)
    proxy.flush_puts()
    assert killhot_app.read_half(str(tmp_path)) == (t_half, 5)  # once
    proxy.write()
    flushes = killhot_app.read_flushes(str(tmp_path))
    assert flushes["n"].tolist() == [2, 2, 1, 1] and proxy.acked == 6
    assert (flushes["t_ret"] >= flushes["t_call"]).all()
    assert calls.count("iput") == 6 and calls.count("flush") == 4


def test_a_worker_that_never_sees_the_kill_gives_up(tmp_path):
    app = killhot_app.make_app(str(tmp_path / "plan.bin"), str(tmp_path),
                               1.0, 1.0, 4, 512, kill_wait_s=0.05)
    assert app(types.SimpleNamespace(rank=3)) == 7


# -------------------------------------------------------------- the control


@pytest.mark.parametrize("guarantee,number,value", [
    ("ack_before_mirror", "missing_units", 26),
    ("no_dedup", "duplicated_units", 256),
])
def test_each_broken_replicated_pool_is_not_correct(guarantee, number, value):
    out = control_failover.judge(CELL, seed=2**31 + 37, seconds=2.0,
                                 guarantee=guarantee)
    warm_s = Spec(ROOT).config(CELL)["fed_warm_s"]
    assert out["units"] == int(1260 * (warm_s + 2.0))
    assert out["correct"] is False
    assert out["compared"][number] == {"value": value, "limit": 0}
    others = {"missing_units", "duplicated_units", "altered_units"} - {number}
    assert all(out["compared"][name]["value"] == 0 for name in others)


def test_the_sound_replicated_pool_is_correct_and_the_control_says_so(
        capsys):
    out = control_failover.judge(CELL, seed=37, seconds=2.0,
                                 guarantee="replicated")
    assert out["correct"] is True
    assert all(v == {"value": 0, "limit": 0}
               for v in out["compared"].values())
    assert control_failover.main(["--workload", CELL, "--seeds", "5",
                                  "--seconds", "2"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line]
    assert [(o["guarantee"], o["correct"]) for o in lines] == [
        ("replicated", True), ("ack_before_mirror", False),
        ("no_dedup", False)]


def test_the_plain_replicated_pool_keeps_what_it_mirrored_and_each_put_once():
    ref = replicated_pool.ReplicatedPool()
    units = [(10 + i, 50000, i) for i in range(6)]
    assert all(ref.put(u, i) for i, u in enumerate(units[:3]))
    assert ref.get() == units[0]            # first in, first out
    assert ref.put(units[3], 3, acknowledge=False) is False  # mirrored,
    ref.kill_primary()                      # and the ack dies with it
    assert ref.get() is None
    assert ref.promote() == 4               # what was mirrored, each once
    assert ref.put(units[3], 3)             # re-sent: absorbed
    assert ref.put(units[4], 4) and ref.put(units[5], 5)
    assert [ref.get() for _ in range(7)] == units + [None]
    with pytest.raises(ValueError):
        replicated_pool.ReplicatedPool("eventually")


# -------------------------------------------------------------- the readers


def reader_run(tmp_path, servers=None, death={"promoted": 65}) -> dict:
    cell_dir = tmp_path / ".bench_scratch" / CELL
    cell_dir.mkdir(parents=True, exist_ok=True)
    if servers is not None:
        (cell_dir / "servers.json").write_text(json.dumps(servers))
    if death is not None:
        (cell_dir / "failover.json").write_text(json.dumps(death))
    return {"bench_dir": str(tmp_path / "benchmarks"), "cell": CELL,
            "config": {"app_ranks": 64, "servers": 16}, "trace": None,
            "window": types.SimpleNamespace(t0=99.5, t_end=103.5)}


def test_hot_is_the_promoted_server_and_not_the_producers_home(tmp_path):
    assert reduce_failover.hot(reader_run(tmp_path)) is None
    run = reader_run(tmp_path, {"64": {"x": 1}, "65": {"x": 2}}, death=None)
    os.remove(tmp_path / ".bench_scratch" / CELL / "failover.json")
    assert reduce_failover.hot(run) is None  # a run of another plane
    run = reader_run(tmp_path, {"64": {"x": 1}, "65": {"x": 2}})
    assert reduce_failover.hot(run) == {"x": 2}


def test_repl_flush_ms_per_s_takes_the_hot_servers_seconds_in_the_window(
        tmp_path):
    assert repl_flush_ms_per_s.read(reader_run(tmp_path)) is None
    # a program without the counter (the parent commit)
    run = reader_run(tmp_path, {"65": {"reactor_busy_by_second": {}}})
    assert repl_flush_ms_per_s.read(run) is None
    by_second = {"99": 0.9, "100": 0.06, "101": 0.03, "102": 0.09,
                 "103": 0.9}
    run = reader_run(tmp_path, {
        "65": {"repl_flush_by_second": by_second},
        "66": {"repl_flush_by_second": {"100": 1.0, "101": 1.0}}})
    # whole seconds inside [99.5, 103.5]: 100, 101, 102
    assert repl_flush_ms_per_s.read(run) == pytest.approx(60.0)
    run["window"] = types.SimpleNamespace(t0=100.2, t_end=100.9)
    assert repl_flush_ms_per_s.read(run) is None


def test_repl_entries_per_frame_divides_the_hot_servers_counters(tmp_path):
    assert repl_entries_per_frame.read(reader_run(tmp_path)) is None
    run = reader_run(tmp_path, {"65": {"repl_frames": 0, "repl_entries": 0}})
    assert repl_entries_per_frame.read(run) is None
    run = reader_run(tmp_path, {
        "65": {"repl_frames": 400, "repl_entries": 3000},
        "66": {"repl_frames": 1, "repl_entries": 99}})
    assert repl_entries_per_frame.read(run) == pytest.approx(7.5)


def test_repl_flush_p50_ms_is_the_median_flush_span_on_any_host_line(
        tmp_path):
    assert repl_flush_p50_ms.read(reader_run(tmp_path)) is None  # untraced
    run = dict(reader_run(tmp_path), trace={"planes": []})
    assert repl_flush_p50_ms.read(run) is None  # traced, and no trace file
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["adlb.repl.flush", 0, 9_000_000]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "planner", "events": [["adlb.round", 0, 4_000_000],
                                           ["adlb.solve", 10, 2_000_000]]},
            {"name": "reactor", "events": [
                ["adlb.repl.flush", 100, 20_000],
                ["adlb.repl.flush", 5_000_100, 40_000],
                ["adlb.wal.fsync", 6_000_100, 900_000],
                ["adlb.repl.flush", 10_000_100, 15_000]]}]}]}
    assert repl_flush_p50_ms.median_ms(trace) == pytest.approx(0.02)
    # a program without the span (the parent commit) gives nothing
    trace["planes"][1]["lines"].pop()
    assert repl_flush_p50_ms.median_ms(trace) is None
