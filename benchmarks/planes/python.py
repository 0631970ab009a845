"""The Python plane: the library's own API end to end. Python app ranks
(``traffic/window_app.py``) and Python servers as OS processes under
``spawn_world`` (fork start, shared-memory rings between the ranks), the
planner a thread of the **master server's** process — which therefore
owns the chip, not this one. This process stays off JAX until the world
has ended:

* the device's facts (platform, kind, count, ``memory_peak_bytes``) and
  the planner's come out of the master with ``WorldResult.solver_facts()``;
* in a traced run a thread here asks the master, over its ops endpoint
  (``POST /device_trace``), for a few seconds of ``jax.profiler`` in the
  middle of the window; where the session began and ended comes back
  from the owner;
* the master's flight artefact (``flight_dir``) carries its registry;
* the servers' reactor load goes to ``<scratch>/servers.json`` for
  ``metrics/reactor_busy_pct.py``;
* when every rank has exited the chip is free, and this process runs the
  seeded solve at the world's own shape once, for ``solve_mismatch``.

A program without the trace request (a commit before it) cannot run this
plane: ``run`` says so and exits before any world starts.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import struct
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

from benchmarks.planes.native import require_tpu, warm_solve
from benchmarks.traffic import window_app

#: how long the runtime may take to let go of the chip after the master
#: rank has exited, before the run fails
CHIP_WAIT_S = 60.0


def require_facility() -> None:
    """The chip's owner has to trace itself and report its device."""
    if importlib.util.find_spec("adlb_tpu.obs.device_trace") is None:
        raise SystemExit(
            "benchmark: this adlb_tpu has no adlb_tpu/obs/device_trace.py "
            "(POST /device_trace on the master's ops endpoint): the forked "
            "master rank that owns the chip cannot be traced, so the "
            "python plane cannot run; no world was started")


class Tracer(threading.Thread):
    """Asks the master for a device trace of a few seconds inside the
    window. It learns where the window lies from the producer's
    ``p0.start``, as ``planes/native.py::Tracer`` does."""

    def __init__(self, logdir: str, trace_dir: str, seconds: float,
                 ops_port: int):
        super().__init__(daemon=True, name="bench-tracer")
        self.logdir, self.trace_dir, self.seconds = logdir, trace_dir, seconds
        self.ops_port = ops_port
        self.session = None  # the owner's answer
        self.error = None
        self.stop = threading.Event()

    def run(self) -> None:
        try:
            start_file = os.path.join(self.logdir, "p0.start")
            while not (os.path.exists(start_file)
                       and os.path.getsize(start_file) == 16):
                if self.stop.wait(0.05):
                    return
            with open(start_file, "rb") as f:
                _t_first, t_end = struct.unpack("<dd", f.read(16))
            span = min(3.0, self.seconds / 3.0)
            begin = t_end - self.seconds + (self.seconds - span) / 2.0
            if self.stop.wait(max(begin - time.monotonic(), 0.0)):
                return
            request = urllib.request.Request(
                f"http://127.0.0.1:{self.ops_port}/device_trace?seconds="
                f"{span!r}&dir={urllib.parse.quote(self.trace_dir)}",
                data=b"", method="POST")
            try:
                with urllib.request.urlopen(request,
                                            timeout=span + 90.0) as resp:
                    self.session = json.load(resp)
            except urllib.error.HTTPError as e:
                raise RuntimeError(f"the master answered {e.code}: "
                                   f"{e.read().decode().strip()}") from e
        except BaseException as e:  # noqa: BLE001 — raised by the harness
            self.error = e


def world_config(config: dict, mix: dict, flight_dir: str, ops_port: int):
    from adlb_tpu.runtime.world import Config

    return Config(flight_dir=flight_dir, ops_port=ops_port,
                  put_routing=mix["put_routing"], **config["config"])


def launch(config: dict, mix: dict, plan_path: str, logdir: str,
           seconds: float, cfg):
    """One world of the configuration under ``cfg``; the ``WorldResult``.
    A world that does not end within ``warm_s + seconds + 150`` raises."""
    from adlb_tpu.runtime.transport_tcp import spawn_world

    app = window_app.make_app(
        plan_path, logdir, float(config["warm_s"]), float(seconds),
        int(config["fetch_batch"]), int(mix.get("flush_every", 0)))
    return spawn_world(
        config["app_ranks"], config["servers"], list(config["types"]), app,
        cfg=cfg, timeout=config["warm_s"] + seconds + 150.0)


def collect(config: dict, res, flight_dir: str) -> dict:
    """What the world left: the clients' exit codes by rank, the
    planner's facts, the master's flight artefact, the servers' reactor
    load."""
    master = config["app_ranks"]
    flight = None
    artefacts = glob.glob(os.path.join(
        flight_dir, f"flight-rank{master}-exit-p*.json"))
    if len(artefacts) == 1:
        with open(artefacts[0]) as f:
            flight = json.load(f)
    servers = {
        str(rank): {key: stats[key] for key in
                    ("reactor_loop_s", "reactor_busy_s",
                     "reactor_busy_by_second") if key in stats}
        for rank, stats in res.server_stats.items()}
    return {
        "client_rcs": [res.app_results.get(rank, -1)
                       for rank in range(config["app_ranks"])],
        "facts": res.solver_facts() or {}, "flight": flight,
        "servers": servers,
    }


def solve_after_world(config: dict, seed: int, chips: int):
    """The seeded solve at the world's own shape, on the chip the master
    rank has let go of: every rank's process has exited, so the device
    is free as soon as the runtime says so. If it does not within
    ``CHIP_WAIT_S`` the run fails; the comparison is never skipped."""
    give_up = time.monotonic() + CHIP_WAIT_S
    while True:
        try:
            require_tpu(chips)
            break
        except RuntimeError as e:  # the backend did not come up
            if time.monotonic() >= give_up:
                raise SystemExit(
                    f"benchmark: the chip was not free {CHIP_WAIT_S:g}s "
                    f"after the world ended: {e}") from e
            time.sleep(2.0)
    return warm_solve(config, seed)


def run(ctx) -> dict:
    """One world of the cell. ``ctx`` has ``config``, ``mix``, ``seed``,
    ``seconds``, ``trace``, ``chips``, ``scratch``, ``logdir``,
    ``plan_path`` and ``say``."""
    require_facility()
    from adlb_tpu.runtime.transport_tcp import probe_free_ports

    config, mix = ctx.config, ctx.mix
    flight_dir = os.path.join(ctx.scratch, "flight")
    shutil.rmtree(flight_dir, ignore_errors=True)
    ops_port = probe_free_ports(1)[0]
    cfg = world_config(config, mix, flight_dir, ops_port)
    tracer = trace_dir = None
    if ctx.trace:
        trace_dir = os.path.join(ctx.scratch, "trace")
        tracer = Tracer(ctx.logdir, trace_dir, ctx.seconds, ops_port)
        tracer.start()
    t0 = time.monotonic()
    try:
        res = launch(config, mix, ctx.plan_path, ctx.logdir, ctx.seconds,
                     cfg)
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

    got = collect(config, res, flight_dir)
    with open(os.path.join(ctx.scratch, "servers.json"), "w") as f:
        json.dump(got["servers"], f)
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
    inputs, solve_got, pad_prio, solve_s = solve_after_world(
        config, ctx.seed, ctx.chips)
    ctx.say(f"after the world: backend and seeded solve "
            f"{len(inputs[0])}x{len(inputs[3])} in "
            f"{time.monotonic() - t_freed:.2f}s (the call {solve_s:.2f}s)")
    return {
        "device": device, "facts": facts, "flight": got["flight"],
        "client_rcs": got["client_rcs"], "world_s": world_s, "t_world": t0,
        "solve_inputs": inputs, "solve_got": solve_got, "pad_prio": pad_prio,
        "trace_dir": trace_dir,
        "trace_window_s": tracer.session["seconds"] if tracer else None,
    }
