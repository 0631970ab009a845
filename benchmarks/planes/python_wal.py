"""The durable Python plane: ``planes/python.py``'s world with the
write-ahead log on (``Config(wal_dir)``, ``docs/USERGUIDE.md`` §10), and,
where the traffic mix says ``restart``, the death of the whole fleet
between the flood and the service.

* **World A** (ingest) runs in a process group of its own: a helper
  process in a new session calls ``spawn_world``; the producer floods the
  plan with every acknowledgement held for its group commit, and nobody
  fetches. This process polls ``<logdir>/p0.bin``, the producer's record
  after its last acknowledgement, and within 50 ms of its being complete
  sends ``SIGKILL`` to the whole group: helper, 64 app ranks, 16 servers.
  It reaps them, sweeps the shared-memory rings and FIFOs they could not
  unlink (``transport_shm.cleanup_world``, by the world key the ranks
  held open while they lived) and writes ``<scratch>/restart.json``.
* **World B** (serve) is the same world shape on the same ``wal_dir``,
  run exactly as ``planes/python.py`` runs its world: every server replays
  its log and adopts what it recovered, the workers join and drain the
  pool through the window. Facts, flight artefact, exit codes, the
  device's numbers and ``servers.json`` are world B's.

A run whose restarted servers recovered nothing, or not exactly the puts
the producer holds acknowledgements for, or whose world A ended before it
was killed, measured another system: the plane says so and exits, as
``run.py::check_planner`` does for a host solve. Without ``restart`` in
the mix the plane runs one world with the log on and nobody is killed.

This process stays off JAX until world B has ended, as ``python.py``'s.
"""

from __future__ import annotations

import ctypes
import dataclasses
import json
import multiprocessing
import os
import re
import shutil
import signal
import struct
import sys
import threading
import time

from benchmarks.planes import python as base
from benchmarks.reduce import records
from benchmarks.traffic import restart_app, window_app

#: how often the producer's record is looked for; the kill follows its
#: completion by at most this and the scan of the group's processes
KILL_POLL_S = 0.02
#: how long the killed group may take to be gone
GONE_WAIT_S = 30.0
SHM_DIR = "/dev/shm"
#: a ring or FIFO of a ``spawn_world`` world (``transport_shm.py``:
#: ``new_world_key`` and the names ``ShmEndpoint`` gives under it)
_RING = re.compile(re.escape(SHM_DIR) + r"/(adlb[0-9a-f]{12})\.")
_PR_SET_CHILD_SUBREAPER = 36
#: a successful fetch that blocked longer than this waited for the planner
STARVED_S = 0.5


def world_config(config: dict, mix: dict, flight_dir: str, ops_port: int,
                 wal_dir: str):
    """``python.py``'s ``Config`` with the log's directory; the group
    commit window and the compaction threshold stay ``Config``'s
    documented defaults."""
    return dataclasses.replace(
        base.world_config(config, mix, flight_dir, ops_port),
        wal_dir=wal_dir)


def launch(config: dict, app, cfg, limit_s: float):
    """One world of the configuration; one that has not ended after
    ``limit_s`` raises."""
    from adlb_tpu.runtime.transport_tcp import spawn_world

    return spawn_world(
        config["app_ranks"], config["servers"], list(config["types"]), app,
        cfg=cfg, timeout=limit_s)


def require_facility() -> None:
    """The restarted servers have to say what they recovered."""
    base.require_facility()
    from adlb_tpu.runtime.server import Server

    if not hasattr(Server, "wal_stats"):
        raise SystemExit(
            "benchmark: this adlb_tpu's servers do not report what they "
            "recovered from the write-ahead log (Server.wal_stats in "
            "finalize_stats()), so a restart cannot be held to the "
            "producer's acknowledgements; no world was started")


# --------------------------------------------------- world A and its death


def _ingest_world(config: dict, app, cfg, limit_s: float) -> None:
    """The helper's body: a session of its own, then world A. Should this
    process's parent die first, the group kills itself."""
    os.setsid()
    parent = os.getppid()

    def watch_parent() -> None:
        while os.getppid() == parent:
            time.sleep(0.5)
        os.killpg(0, signal.SIGKILL)

    threading.Thread(target=watch_parent, daemon=True).start()
    launch(config, app, cfg, limit_s)


def group_members(pgid: int) -> list:
    """The pids whose process group is ``pgid``, from ``/proc``."""
    pids = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue
        if int(fields[2]) == pgid:  # state, ppid, pgrp
            pids.append(int(name))
    return pids


def shm_key(pids: list) -> str | None:
    """The world key under which these processes hold shared-memory
    FIFOs open (every rank on the ring fabric keeps its doorbell's read
    end, ``<key>.bell.<rank>``), which is what ``cleanup_world`` takes;
    None while none of them has one. One world has one key."""
    for pid in pids:
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                found = _RING.match(os.readlink(f"/proc/{pid}/fd/{fd}"))
            except OSError:
                continue
            if found:
                return found.group(1)
    return None


def _gone(pid: int) -> bool:
    """No such process, or only its zombie: it holds nothing any more."""
    try:
        os.waitpid(pid, os.WNOHANG)  # ours, where this process adopts orphans
    except ChildProcessError:
        pass
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except (OSError, IndexError):
        return True


def kill_group(helper, key: str | None) -> tuple:
    """SIGKILL to the helper's whole group, then wait until every process
    of it is gone and sweep what they left in ``/dev/shm``. Returns when
    the signal was sent, how many processes it met and the shm entries
    swept."""
    from adlb_tpu.runtime.transport_shm import cleanup_world

    try:
        os.killpg(helper.pid, signal.SIGKILL)
    except ProcessLookupError:  # the helper never reached its setsid
        helper.kill()
    t_kill = time.monotonic()
    left = pids = group_members(helper.pid)
    helper.join(timeout=GONE_WAIT_S)
    give_up = t_kill + GONE_WAIT_S
    while left:
        left = [pid for pid in left if not _gone(pid)]
        if left and time.monotonic() >= give_up:
            raise SystemExit(f"benchmark: {len(left)} processes of world A "
                             f"outlived SIGKILL by {GONE_WAIT_S:g}s: {left}")
        if left:
            time.sleep(0.01)
    swept = 0
    if key:
        swept = sum(name.startswith(key + ".")
                    for name in os.listdir(SHM_DIR))
        cleanup_world(key)
    return t_kill, len(pids), swept


def producer_done(logdir: str) -> bool:
    path = os.path.join(logdir, "p0.bin")
    return (os.path.exists(path)
            and os.path.getsize(path) == records.PRODUCER.itemsize)


def ingest_and_kill(ctx, app, cfg, limit_s: float,
                    done=producer_done) -> dict:
    """World A from its start to the last of its processes, killed as soon
    as ``done(logdir)`` holds; what ``restart.json`` says of it."""
    from adlb_tpu.runtime.transport_shm import resolve_fabric

    config = ctx.config
    # orphans of the killed helper become this process's to reap
    prctl = ctypes.CDLL(None, use_errno=True).prctl
    prctl(_PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0)
    helper = multiprocessing.get_context("fork").Process(
        target=_ingest_world, args=(config, app, cfg, limit_s),
        name="bench-world-a")
    t_world = time.monotonic()
    helper.start()
    deadline = t_world + limit_s
    failure = key = None
    look_for_key = t_world + 0.5 if resolve_fabric(cfg) == "shm" else None
    try:
        while not done(ctx.logdir):
            now = time.monotonic()
            if not helper.is_alive():
                failure = (f"world A ended by itself (exit "
                           f"{helper.exitcode}) before the kill")
                break
            if now >= deadline:
                failure = (f"world A's producer had not finished "
                           f"{deadline - t_world:.0f}s after its start")
                break
            if look_for_key is not None and key is None \
                    and now >= look_for_key:
                # while the flood runs, so that nothing stands between
                # the producer's record and the kill
                key = shm_key(group_members(helper.pid))
                look_for_key = now + 0.5
            time.sleep(KILL_POLL_S)
        t_seen = time.monotonic()
        if look_for_key is not None and key is None:
            key = shm_key(group_members(helper.pid))  # a flood that short
        if failure is None and not helper.is_alive():
            failure = "world A ended by itself before the kill"
    finally:
        t_kill, killed, swept = kill_group(helper, key)
        prctl(_PR_SET_CHILD_SUBREAPER, 0, 0, 0, 0)
    t_gone = time.monotonic()
    if failure is None and look_for_key is not None and key is None:
        failure = ("world A ran on shared-memory rings and their key was "
                   "not found in /proc, so they are left in /dev/shm")
    if failure is not None:
        raise SystemExit(f"benchmark: {failure}; this run measured "
                         f"another system")
    wal_dir = cfg.wal_dir
    logs = sorted(os.listdir(wal_dir)) if os.path.isdir(wal_dir) else []
    return {
        "t_world_a": t_world, "t_p0_seen": t_seen, "t_kill": t_kill,
        "t_gone": t_gone, "group": helper.pid, "killed": killed,
        "shm_key": key,
        "shm_swept": swept,
        "log_bytes_at_kill": {
            name: os.path.getsize(os.path.join(wal_dir, name))
            for name in logs},
    }


# ----------------------------------------------------------- the two worlds


def serve_world(ctx, app, cfg, ops_port: int, limit_s: float):
    """One world run as ``python.py::run`` runs its own: the tracer
    against its ops port, the world, the check that this process kept off
    JAX. Returns the ``WorldResult``, the tracer, when the world was called
    and how long it took."""
    tracer = None
    if ctx.trace:
        tracer = base.Tracer(ctx.logdir, os.path.join(ctx.scratch, "trace"),
                             ctx.seconds, ops_port)
        tracer.start()
    t0 = time.monotonic()
    try:
        res = launch(ctx.config, app, cfg, limit_s)
    finally:
        if tracer is not None:
            tracer.stop.set()
            tracer.join(timeout=120.0)
    world_s = time.monotonic() - t0
    if "jax" in sys.modules:
        raise SystemExit("benchmark: the harness imported JAX while the "
                         "world ran; the master rank has to own the chip")
    if tracer is not None and (tracer.error is not None
                               or tracer.session is None):
        raise RuntimeError(f"tracing failed: {tracer.error!r}")
    return res, tracer, t0, world_s


WAL_KEYS = ("wal_recovered", "wal_replayed", "wal_recover_s", "wal_syncs",
            "wal_records", "wal_bytes", "wal_flush_by_second")


def check_recovery(servers: dict, n_acked: int) -> int:
    """What the restarted servers adopted against what the producer holds
    acknowledgements for; anything else measured another system."""
    recovered = sum(int(s.get("wal_recovered", 0)) for s in servers.values())
    if recovered == 0:
        raise SystemExit("benchmark: the restarted servers recovered "
                         "nothing from the write-ahead log; this run "
                         "measured another system")
    if recovered != n_acked:
        raise SystemExit(
            f"benchmark: the restarted servers recovered {recovered} units, "
            f"the producer holds acknowledgements for {n_acked}; this run "
            f"measured another system")
    return recovered


def restart_numbers(ctx, restart: dict, servers: dict) -> dict:
    """The restart's own numbers, from the producer's record, the kill and
    world B's logs and servers."""
    from benchmarks.reduce.window import Window

    config = ctx.config
    logs = records.read_logs(ctx.logdir)
    p = logs.producer
    n_acked = int(p["n_acked"])
    recovered = check_recovery(servers, n_acked)
    window = Window(logs, ctx.seconds, config["app_ranks"] - 1,
                    config["servers"], bool(ctx.mix.get("needs_backlog")))
    hot = servers[str(config["app_ranks"])]  # the producer's home server
    # when the last starving worker was fed: the servers the producer is
    # not homed with serve the few units they recovered themselves at
    # once, then their workers block until the new master's planner ships
    f = logs.fetches[(logs.fetch_rank % config["servers"]) != 0]
    starved = f["t_ret"][(f["rc"] == 1) & (f["t_ret"] <= window.t_end)
                         & (f["t_ret"] - f["t_call"] > STARVED_S)]
    fed = float(starved.max()) if len(starved) else None
    return {
        "n_acked": n_acked,
        "durable_puts_per_s": n_acked / float(p["t_last"] - p["t_first"]),
        "flood_s": float(p["t_last"] - p["t_first"]),
        "kill_after_last_ack_s": restart["t_kill"] - float(p["t_last"]),
        "restart_s": (None if window.first_remote is None
                      else window.first_remote - restart["t_kill"]),
        "first_remote_after_first_put_s": (
            None if window.first_remote is None
            else window.first_remote - float(p["t_first"])),
        "fleet_fed_s": None if fed is None else fed - restart["t_kill"],
        "fleet_fed_after_first_put_s": (
            None if fed is None else fed - float(p["t_first"])),
        "wal_recovered": recovered,
        "wal_recovered_hot": int(hot.get("wal_recovered", 0)),
        "wal_recover_s": float(hot.get("wal_recover_s", 0.0)),
        "wal_replayed_hot": int(hot.get("wal_replayed", 0)),
    }


def run(ctx) -> dict:
    """One cell's run. ``ctx`` as ``planes/python.py::run`` takes it."""
    require_facility()
    from adlb_tpu.runtime.transport_tcp import probe_free_ports

    config, mix = ctx.config, ctx.mix
    wal_dir = os.path.join(ctx.scratch, "wal")
    flight_dir = os.path.join(ctx.scratch, "flight")
    for path in (wal_dir, flight_dir):
        shutil.rmtree(path, ignore_errors=True)
    args = (ctx.plan_path, ctx.logdir, float(config["warm_s"]),
            float(ctx.seconds), int(config["fetch_batch"]),
            int(mix.get("flush_every", 0)))
    limit_s = config["warm_s"] + ctx.seconds + 150.0  # a world, as python.py
    restart = None
    if mix.get("restart"):
        ingest, serve = restart_app.make_apps(*args)
        port_a = probe_free_ports(1)[0]
        restart = ingest_and_kill(ctx, ingest, world_config(
            config, mix, os.path.join(ctx.scratch, "flight-a"), port_a,
            wal_dir), limit_s)
        with open(os.path.join(ctx.logdir, "p0.start"), "rb") as f:
            t_first, _t_end = struct.unpack("<dd", f.read(16))
        ctx.say(f"world A: first put {t_first - restart['t_world_a']:.2f}s "
                f"after its call, producer done and {restart['killed']} "
                f"processes killed {restart['t_kill'] - t_first:.2f}s after "
                f"the first put, all gone "
                f"{restart['t_gone'] - restart['t_kill']:.2f}s later, "
                f"{restart['shm_swept']} shm entries swept, logs "
                f"{sum(restart['log_bytes_at_kill'].values())} bytes")
    else:
        serve = window_app.make_app(*args)
    ops_port = probe_free_ports(1)[0]
    cfg = world_config(config, mix, flight_dir, ops_port, wal_dir)
    res, tracer, t_world_b, world_s = serve_world(ctx, serve, cfg, ops_port,
                                                  limit_s)

    got = base.collect(config, res, flight_dir)
    servers = got["servers"]
    for rank, stats in res.server_stats.items():
        servers[str(rank)].update(
            {key: stats[key] for key in WAL_KEYS if key in stats})
    with open(os.path.join(ctx.scratch, "servers.json"), "w") as f:
        json.dump(servers, f)
    if restart is not None:
        restart["t_world_b"] = t_world_b
        restart.update(restart_numbers(ctx, restart, servers))
        for path in (os.path.join(ctx.scratch, "restart.json"),
                     kept_path(ctx)):
            with open(path, "w") as f:
                json.dump(restart, f)
        ctx.say("restart: " + " ".join(
            f"{key}={restart[key]}" for key in (
                "durable_puts_per_s", "restart_s", "wal_recover_s",
                "wal_recovered", "wal_recovered_hot", "wal_replayed_hot",
                "flood_s", "kill_after_last_ack_s", "fleet_fed_s",
                "first_remote_after_first_put_s",
                "fleet_fed_after_first_put_s")))
    facts = got["facts"]
    device = {"platform": facts.get("platform"),
              "kind": facts.get("device_kind"),
              "count": facts.get("device_count"),
              "memory_peak_bytes": facts.get("memory_peak_bytes")}
    ctx.say(f"the master rank's first device solve, with the backend's "
            f"start: {facts.get('first_device_solve_s')}s")
    for rank, rc in enumerate(got["client_rcs"]):
        if rc != 0:
            ctx.say(f"client rank {rank} returned {rc}")
    if device["platform"] != "tpu" or (device["count"] or 0) < ctx.chips:
        raise SystemExit(
            f"benchmark: the master rank reports {device}, the cell needs "
            f"{ctx.chips} TPU chip(s); there is no CPU path")
    t_freed = time.monotonic()
    inputs, solve_got, pad_prio, solve_s = base.solve_after_world(
        config, ctx.seed, ctx.chips)
    ctx.say(f"after the world: backend and seeded solve "
            f"{len(inputs[0])}x{len(inputs[3])} in "
            f"{time.monotonic() - t_freed:.2f}s (the call {solve_s:.2f}s)")
    return {
        "device": device, "facts": facts, "flight": got["flight"],
        "client_rcs": got["client_rcs"], "world_s": world_s,
        "t_world": restart["t_world_a"] if restart else t_world_b,
        "solve_inputs": inputs, "solve_got": solve_got, "pad_prio": pad_prio,
        "trace_dir": os.path.join(ctx.scratch, "trace") if tracer else None,
        "trace_window_s": tracer.session["seconds"] if tracer else None,
    }


def kept_path(ctx) -> str:
    """Beside the run's result under ``chiprun_out/bench/<cell>/``."""
    root = os.path.dirname(os.path.dirname(ctx.scratch))
    out_dir = os.path.join(root, "chiprun_out", "bench",
                           os.path.basename(ctx.scratch))
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(
        out_dir, f"restart-seed{ctx.seed}-trace{int(ctx.trace)}.json")
