"""The replicated Python plane: ``planes/python.py``'s world with
``Config(on_server_failure="failover")`` (``docs/USERGUIDE.md`` §9), and,
where the traffic mix says ``failover``, the death of one server alone in
mid-flood: the producer's home server, which on this plane is the master,
the chip's owner-to-be.

One world. The producer floods through ``traffic/killhot_app.py``, which
writes ``<logdir>/p0.half`` when the flush that acknowledges the first
half of the plan has returned. A thread here polls for the marker, and
within 50 ms of it sends ``SIGKILL`` to the one OS process of the master
rank (``master_process``: the child of this process that ``spawn_world``
named ``adlb-rank-<app_ranks>``), waits until it has been reaped and then
writes ``<logdir>/killed``, which is what the workers wait for. The world
that is left goes on: the dead server's ring buddy promotes from its
in-memory mirror, takes the master's duties under a bumped epoch and
starts the planner (and with it JAX and the chip) cold; the producer
re-sends what was unacknowledged and floods the second half into it; the
workers drain the pool through the window.

Facts, flight artefact, the device's numbers and the trace are the
**promoted** master's: its ops endpoint is found in
``<scratch>/ops/ops_endpoint.json`` (``Config(ops_announce_dir)``).
``<scratch>/servers.json`` holds each surviving server's reactor load with
its replication and failover counters, ``<scratch>/failover.json`` (kept
as ``chiprun_out/bench/<cell>/failover-seed<n>-trace<t>.json``) the
death's own numbers, which one earlier line ``failover: …`` prints.

A run in which the mechanism did not engage measured another system: the
plane says so and exits, as ``run.py::check_planner`` does for a host
solve (``check_failover``). Without ``failover`` in the mix the plane runs
one world with the stream on and nobody is killed.

This process stays off JAX until the world has ended, as ``python.py``'s.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import multiprocessing
import os
import shutil
import signal
import struct
import sys
import threading
import time

from benchmarks.planes import python as base
from benchmarks.planes.python_wal import STARVED_S  # fleet_fed_s, as there
from benchmarks.reduce import records
from benchmarks.traffic import killhot_app, window_app

#: how often the marker is looked for; the kill follows it by at most this
KILL_POLL_S = 0.02
#: how long the killed process may take to be reaped
GONE_WAIT_S = 30.0
#: how long the producer may take to reach half the plan, from the world's
#: call (the fork of 80 processes and half a flood take 5-10 s)
HALF_WAIT_S = 120.0
#: of ``Server.failover_stats()``, what ``servers.json`` keeps by rank
FAILOVER_KEYS = ("repl_frames", "repl_entries", "repl_bytes", "repl_applied",
                 "repl_flush_s", "repl_flush_by_second", "failover_adopted",
                 "failover_resent_puts", "failover_deduped_puts",
                 "master_failover_mttr_ms")
LINE_KEYS = ("replicated_puts_per_s", "puts_after_per_s", "producer_stall_s",
             "promote_ms", "master_promote_ms", "adopted", "n_acked_at_kill",
             "resent_puts", "deduped_puts", "first_remote_s", "fleet_fed_s",
             "fleet_fed_after_first_put_s", "kill_after_half_s")


def world_config(config: dict, mix: dict, flight_dir: str, ops_port: int,
                 ops_dir: str):
    """``python.py``'s ``Config`` (the configuration states
    ``on_server_failure``) with the rendezvous directory in which the
    master of the hour publishes its ops endpoint; ``failover_client_wait``
    stays ``Config``'s documented default."""
    return dataclasses.replace(
        base.world_config(config, mix, flight_dir, ops_port),
        ops_announce_dir=ops_dir)


def launch(config: dict, app, cfg, limit_s: float):
    """One world of the configuration; one that has not ended after
    ``limit_s`` raises."""
    from adlb_tpu.runtime.transport_tcp import spawn_world

    return spawn_world(
        config["app_ranks"], config["servers"], list(config["types"]), app,
        cfg=cfg, timeout=limit_s)


def require_facility() -> None:
    """The promoted server has to say what it adopted."""
    base.require_facility()
    from adlb_tpu.runtime.server import Server

    if not hasattr(Server, "failover_stats"):
        raise SystemExit(
            "benchmark: this adlb_tpu's servers do not report what a "
            "promotion adopted (Server.failover_stats in finalize_stats()), "
            "so a failover cannot be held to the producer's "
            "acknowledgements; no world was started")


# ------------------------------------------------------------- the death


def master_process(rank: int):
    """The OS process of a rank of the world this process is running:
    ``spawn_world`` starts every rank as a ``multiprocessing`` child named
    ``adlb-rank-<rank>``. None while there is none."""
    for proc in multiprocessing.active_children():
        if proc.name == f"adlb-rank-{rank}":
            return proc
    return None


class Killer(threading.Thread):
    """Waits for ``p0.half``, kills the rank's one process, sees it reaped
    and writes ``killed``. What it did is in its attributes; what went
    wrong in ``error``."""

    def __init__(self, logdir: str, rank: int, find=master_process,
                 half_wait_s: float = HALF_WAIT_S):
        super().__init__(daemon=True, name="bench-killer")
        self.logdir, self.rank, self.find = logdir, rank, find
        self.half_wait_s = half_wait_s
        self.stop = threading.Event()
        self.error = None
        self.pid = self.exitcode = None
        self.t_half_seen = self.t_kill = self.t_gone = None
        self.flood_done_at_kill = None

    def run(self) -> None:
        try:
            give_up = time.monotonic() + self.half_wait_s
            while killhot_app.read_half(self.logdir) is None:
                if self.stop.wait(KILL_POLL_S):
                    return
                if time.monotonic() >= give_up:
                    raise RuntimeError(
                        f"the producer had not acknowledged half the plan "
                        f"{self.half_wait_s:g}s after the world was called")
            self.t_half_seen = time.monotonic()
            proc = self.find(self.rank)
            if proc is None or proc.pid is None:
                raise RuntimeError(f"no process of rank {self.rank} among "
                                   f"this process's children")
            self.pid = proc.pid
            self.flood_done_at_kill = os.path.exists(
                os.path.join(self.logdir, "p0.bin"))
            os.kill(self.pid, signal.SIGKILL)
            self.t_kill = time.monotonic()
            # through the world's own handle on its child, so that the
            # world still sees the exit it counts its casualties by
            proc.join(GONE_WAIT_S)
            if proc.exitcode is None:
                raise RuntimeError(f"process {self.pid} of rank {self.rank} "
                                   f"outlived SIGKILL by {GONE_WAIT_S:g}s")
            self.exitcode = proc.exitcode
            self.t_gone = time.monotonic()
        except BaseException as e:  # noqa: BLE001 — raised by the harness
            self.error = e
        finally:
            if self.t_gone is not None:
                with open(os.path.join(self.logdir, "killed"), "wb") as f:
                    f.write(struct.pack("<dd", self.t_kill, self.t_gone))


class Tracer(base.Tracer):
    """``python.py``'s request, sent to the master of the hour: the port
    is read from the rendezvous file when the request is made, in
    mid-window, and has to be the promoted server's."""

    def __init__(self, logdir: str, trace_dir: str, seconds: float,
                 ops_dir: str, dead: int):
        self.ops_dir, self.dead = ops_dir, dead
        super().__init__(logdir, trace_dir, seconds, None)

    @property
    def ops_port(self) -> int:
        with open(os.path.join(self.ops_dir, "ops_endpoint.json")) as f:
            doc = json.load(f)
        if doc["master"] == self.dead:
            raise RuntimeError(f"in mid-window the ops endpoint is still "
                               f"the dead master's: {doc}")
        return int(doc["port"])

    @ops_port.setter
    def ops_port(self, _port) -> None:
        pass


# ------------------------------------------------------- what the run left


def read_flight(flight_dir: str, rank: int):
    """The flight artefact a master writes at a normal end, or None."""
    artefacts = glob.glob(os.path.join(
        flight_dir, f"flight-rank{rank}-exit-p*.json"))
    if len(artefacts) != 1:
        return None
    with open(artefacts[0]) as f:
        return json.load(f)


def collect(config: dict, res) -> dict:
    """``python.py::collect`` less the flight artefact, which is the
    master of the world's end's: the clients' exit codes by rank, the
    planner's facts, and each server's reactor load with its replication
    and failover counters beside it."""
    from adlb_tpu.types import InfoKey

    servers = {}
    for rank, stats in res.server_stats.items():
        servers[str(rank)] = kept = {
            key: stats[key] for key in
            ("reactor_loop_s", "reactor_busy_s", "reactor_busy_by_second")
            + FAILOVER_KEYS if key in stats}
        kept["num_failovers"] = stats.get(int(InfoKey.NUM_FAILOVERS), 0.0)
        kept["failover_lost"] = stats.get(int(InfoKey.FAILOVER_LOST), 0.0)
        kept["failover_mttr_ms"] = stats.get(
            int(InfoKey.FAILOVER_MTTR_MS), 0.0)
    return {
        "client_rcs": [res.app_results.get(rank, -1)
                       for rank in range(config["app_ranks"])],
        "facts": res.solver_facts() or {}, "servers": servers,
    }


def check_failover(config: dict, killer, res, servers: dict, facts: dict,
                   logs) -> int:
    """The run against what the cell says happened in it; anything else
    measured another system. Returns the promoted rank."""
    dead = config["app_ranks"]

    def refuse(what: str):
        raise SystemExit(f"benchmark: {what}; this run measured another "
                         f"system")

    if killer.error is not None:
        refuse(f"the kill failed: {killer.error}")
    if killer.t_gone is None:
        refuse("the world ended before the producer had acknowledged half "
               "the plan, so nobody was killed")
    if list(res.server_casualties) != [dead]:
        refuse(f"the world counts the server casualties "
               f"{list(res.server_casualties)}, the cell kills rank {dead} "
               f"alone")
    if str(dead) in servers:
        refuse(f"the killed rank {dead} reported at the world's end")
    promotions = {int(rank): s["num_failovers"]
                  for rank, s in servers.items() if s["num_failovers"] > 0}
    if sum(promotions.values()) != 1:
        refuse(f"the servers count {sum(promotions.values()):g} promotions, "
               f"the cell has exactly one")
    (promoted,) = promotions
    if not servers[str(promoted)].get("failover_adopted"):
        refuse(f"the promoted server {promoted} adopted no unit from its "
               f"mirror")
    lost = sum(s.get("failover_lost", 0) for s in servers.values())
    if lost != 0:
        refuse(f"the servers count {lost:g} units lost to the failover "
               f"(FAILOVER_LOST)")
    if killer.flood_done_at_kill:
        refuse("the producer's record p0.bin was complete before the kill: "
               "the flood outran it and the death fell on a quiet producer")
    early = int((logs.units["t_ret"] < killer.t_kill).sum())
    if early:
        refuse(f"{early} units were delivered before the kill")
    if "solver" not in (res.server_stats.get(promoted) or {}) \
            or res.server_stats[promoted]["solver"] != facts:
        refuse(f"the planner's facts are not the promoted server "
               f"{promoted}'s")
    return promoted


def failover_numbers(ctx, killer, promoted: int, servers: dict, res,
                     logs) -> dict:
    """The death's own numbers, from the producer's flushes, the kill and
    the surviving servers."""
    from benchmarks.reduce.window import Window

    config = ctx.config
    p = logs.producer
    t_first, t_kill = float(p["t_first"]), killer.t_kill
    flushes = killhot_app.read_flushes(ctx.logdir)
    before = flushes[flushes["t_ret"] <= t_kill]
    felt = flushes[flushes["t_ret"] > t_kill]
    after = flushes[flushes["t_call"] > t_kill]
    n_before = int(before["n"].sum())
    window = Window(logs, ctx.seconds, config["app_ranks"] - 1,
                    config["servers"], bool(ctx.mix.get("needs_backlog")))
    f = logs.fetches[(logs.fetch_rank % config["servers"]) != 0]
    starved = f["t_ret"][(f["rc"] == 1) & (f["t_ret"] <= window.t_end)
                         & (f["t_ret"] - f["t_call"] > STARVED_S)]
    fed = float(starved.max()) if len(starved) else None
    hot = servers[str(promoted)]
    half = killhot_app.read_half(ctx.logdir)
    return {
        "dead": config["app_ranks"], "promoted": promoted,
        "pid": killer.pid, "exitcode": killer.exitcode,
        "t_half": half[0], "t_half_seen": killer.t_half_seen,
        "t_kill": t_kill, "t_gone": killer.t_gone,
        "kill_after_half_s": t_kill - half[0],
        "n_acked_at_half": int(half[1]), "n_acked_at_kill": n_before,
        "n_acked": int(p["n_acked"]),
        "server_casualties": list(res.server_casualties),
        "replicated_puts_per_s": (
            n_before / (float(before["t_ret"].max()) - t_first)
            if n_before else None),
        "puts_after_per_s": (
            int(after["n"].sum())
            / (float(after["t_ret"].max()) - float(after["t_call"].min()))
            if len(after) else None),
        # the longest flush that the death could have held up
        "producer_stall_s": float((felt["t_ret"] - felt["t_call"]).max())
        if len(felt) else None,
        "flood_s": float(p["t_last"]) - t_first,
        "promote_ms": hot.get("failover_mttr_ms"),
        "master_promote_ms": hot.get("master_failover_mttr_ms"),
        "adopted": hot.get("failover_adopted"),
        "resent_puts": hot.get("failover_resent_puts"),
        "deduped_puts": hot.get("failover_deduped_puts"),
        "first_remote_s": (None if window.first_remote is None
                           else window.first_remote - t_kill),
        "fleet_fed_s": None if fed is None else fed - t_kill,
        "fleet_fed_after_first_put_s": (
            None if fed is None else fed - t_first),
        "servers": {
            rank: {"failover_mttr_ms": s.get("failover_mttr_ms"),
                   "master_failover_mttr_ms":
                       s.get("master_failover_mttr_ms"),
                   "NUM_FAILOVERS": s.get("num_failovers"),
                   "FAILOVER_LOST": s.get("failover_lost"),
                   "adopted": s.get("failover_adopted")}
            for rank, s in servers.items()},
    }


def kept_path(ctx) -> str:
    """Beside the run's result under ``chiprun_out/bench/<cell>/``."""
    root = os.path.dirname(os.path.dirname(ctx.scratch))
    out_dir = os.path.join(root, "chiprun_out", "bench",
                           os.path.basename(ctx.scratch))
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(
        out_dir, f"failover-seed{ctx.seed}-trace{int(ctx.trace)}.json")


# ---------------------------------------------------------------- the run


def run(ctx) -> dict:
    """One cell's run. ``ctx`` as ``planes/python.py::run`` takes it."""
    require_facility()
    from adlb_tpu.runtime.transport_tcp import probe_free_ports

    config, mix = ctx.config, ctx.mix
    flight_dir = os.path.join(ctx.scratch, "flight")
    ops_dir = os.path.join(ctx.scratch, "ops")
    for path in (flight_dir, ops_dir):
        shutil.rmtree(path, ignore_errors=True)
    os.makedirs(ops_dir)
    args = (ctx.plan_path, ctx.logdir, float(config["warm_s"]),
            float(ctx.seconds), int(config["fetch_batch"]),
            int(mix.get("flush_every", 0)))
    limit_s = config["warm_s"] + ctx.seconds + 150.0  # a world, as python.py
    dead = config["app_ranks"]
    cfg = world_config(config, mix, flight_dir, probe_free_ports(1)[0],
                       ops_dir)
    killer = tracer = trace_dir = None
    if mix.get("failover"):
        app = killhot_app.make_app(*args)
        killer = Killer(ctx.logdir, dead)
        killer.start()
    else:
        app = window_app.make_app(*args)
    if ctx.trace:
        trace_dir = os.path.join(ctx.scratch, "trace")
        tracer = Tracer(ctx.logdir, trace_dir, ctx.seconds, ops_dir,
                        dead if killer else None)
        tracer.start()
    t0 = time.monotonic()
    try:
        res = launch(config, app, cfg, limit_s)
    finally:
        for thread in (killer, tracer):
            if thread is not None:
                thread.stop.set()
                thread.join(timeout=120.0)
    world_s = time.monotonic() - t0
    if "jax" in sys.modules:
        raise SystemExit("benchmark: the harness imported JAX while the "
                         "world ran; the master rank has to own the chip")
    if tracer is not None and (tracer.error is not None
                               or tracer.session is None):
        raise RuntimeError(f"tracing failed: {tracer.error!r}")

    got = collect(config, res)
    servers, facts = got["servers"], got["facts"]
    master = dead  # of the world's end
    if killer is not None:
        logs = records.read_logs(ctx.logdir)
        master = check_failover(config, killer, res, servers, facts, logs)
    flight = read_flight(flight_dir, master)
    with open(os.path.join(ctx.scratch, "servers.json"), "w") as f:
        json.dump(servers, f)
    if killer is not None:
        if logs.producer is None:
            raise SystemExit("benchmark: the producer left no record — the "
                             "world did not run to its end")
        numbers = failover_numbers(ctx, killer, master, servers, res, logs)
        for path in (os.path.join(ctx.scratch, "failover.json"),
                     kept_path(ctx)):
            with open(path, "w") as f:
                json.dump(numbers, f)
        ctx.say("failover: " + " ".join(
            f"{key}={numbers[key]}" for key in LINE_KEYS))
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
        "device": device, "facts": facts, "flight": flight,
        "client_rcs": got["client_rcs"], "world_s": world_s, "t_world": t0,
        "solve_inputs": inputs, "solve_got": solve_got, "pad_prio": pad_prio,
        "trace_dir": trace_dir,
        "trace_window_s": tracer.session["seconds"] if tracer else None,
    }
