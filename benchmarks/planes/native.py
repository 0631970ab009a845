"""The all-native plane: C clients, C++ server daemons, and the planner
as a sidecar thread of THIS process — which therefore owns the chip, can
warm the solve program before the world starts and can trace the device
inside the window."""

from __future__ import annotations

import glob
import json
import os
import shutil
import struct
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CLIENT_SRC = os.path.join(os.path.dirname(HERE), "clients", "window_client.c")


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; anything but enough TPUs ends the run."""
    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if found["platform"] != "tpu" or found["count"] < chips:
        raise SystemExit(
            f"benchmark: JAX shows {found}, the cell needs {chips} TPU "
            f"chip(s); there is no CPU path")
    return found


def world_config(config: dict, flight_dir: str):
    from adlb_tpu.runtime.world import Config

    return Config(server_impl="native", flight_dir=flight_dir,
                  **config["config"])


def warm_solve(config: dict, seed: int):
    """Call the planner's device program once at the world's own shape, on
    a seeded table: backend start-up and program load (a compile, on a
    checkout's first run) happen here, in set-up. Returns the inputs and
    what the device answered, for the comparison after the window."""
    import jax.numpy as jnp

    from adlb_tpu.balancer.solve import _NEG, _greedy_assign
    from adlb_tpu.utils.jaxenv import ensure_compile_cache
    from benchmarks.reference.greedy import seeded_snapshot

    ensure_compile_cache()
    cfg = config["config"]
    nt = config["servers"] * cfg["balancer_max_tasks"]
    nr = config["servers"] * cfg["balancer_max_requesters"]
    inputs = seeded_snapshot(seed, nt, nr, len(config["types"]), int(_NEG))
    if cfg.get("solver_backend", "auto") == "xla":
        fn = _greedy_assign
    else:
        from adlb_tpu.balancer.pallas_solve import make_pallas_assign

        fn = make_pallas_assign(interpret=False)
    t0 = time.monotonic()
    got = np.asarray(fn(*[jnp.asarray(a) for a in inputs]))
    return inputs, got, int(_NEG), time.monotonic() - t0


def warm_dense(ctx):
    """The default warm step of ``run``: ``warm_solve`` at the world's own
    shape, handed over as the one-chip dense check. A warm step takes the
    run's ``ctx`` and returns ``(check, shape, seconds)``: the solve check
    ``run.py::finish`` compares (``solve_inputs``/``solve_got``/
    ``pad_prio``, or ``solve_check``), the shape the set-up line prints,
    and the device program's seconds."""
    inputs, got, pad_prio, solve_s = warm_solve(ctx.config, ctx.seed)
    check = {"solve_inputs": inputs, "solve_got": got, "pad_prio": pad_prio}
    return check, f"{len(inputs[0])}x{len(inputs[3])}", solve_s


class Tracer(threading.Thread):
    """Traces the device for a few seconds inside the window. It learns
    where the window lies from the producer's ``p0.start``."""

    def __init__(self, logdir: str, trace_dir: str, seconds: float):
        super().__init__(daemon=True, name="bench-tracer")
        self.logdir, self.trace_dir, self.seconds = logdir, trace_dir, seconds
        self.window_s = None
        self.error = None
        self.stop = threading.Event()

    def run(self) -> None:
        try:
            import jax

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
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            t0 = time.monotonic()
            self.stop.wait(span)
            self.window_s = time.monotonic() - t0
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — raised by the harness
            self.error = e


def run(ctx, warm=warm_dense) -> dict:
    """One world of the cell. ``ctx`` has ``config``, ``mix``, ``seed``,
    ``seconds``, ``trace``, ``chips``, ``scratch``, ``logdir``,
    ``plan_path`` and ``say``. ``warm`` is the warm step (``warm_dense``'s
    contract): a sibling plane whose planner solves another way passes
    its own, and shares the world, the tracer and the readings."""
    import jax

    from adlb_tpu.native.capi import build_example, run_native_world

    config, mix = ctx.config, ctx.mix
    t_enter = time.monotonic()
    device = require_tpu(ctx.chips)
    t_backend = time.monotonic()
    check, shape, solve_s = warm(ctx)
    t_solved = time.monotonic()
    exe = build_example(CLIENT_SRC)
    ctx.say(f"set-up: backend {t_backend - t_enter:.2f}s, warm solve "
            f"{shape} {solve_s:.2f}s (of "
            f"{t_solved - t_backend:.2f}s with its inputs), client build "
            f"{time.monotonic() - t_solved:.2f}s")

    flight_dir = os.path.join(ctx.scratch, "flight")
    shutil.rmtree(flight_dir, ignore_errors=True)
    cfg = world_config(config, flight_dir)
    tracer = trace_dir = None
    if ctx.trace:
        trace_dir = os.path.join(ctx.scratch, "trace")
        tracer = Tracer(ctx.logdir, trace_dir, ctx.seconds)
        tracer.start()
    env = {
        "ADLB_PUT_ROUTING": mix["put_routing"],
        "ADLB_WIN_UNITS": ctx.plan_path,
        "ADLB_WIN_LOGDIR": ctx.logdir,
        "ADLB_WIN_WARM_S": repr(float(config["warm_s"])),
        "ADLB_WIN_SECONDS": repr(float(ctx.seconds)),
        "ADLB_WIN_FETCH": str(int(config["fetch_batch"])),
        "ADLB_WIN_FLUSH_EVERY": str(int(mix.get("flush_every", 0))),
    }
    t0 = time.monotonic()
    try:
        results, server_stats = run_native_world(
            n_clients=config["app_ranks"], nservers=config["servers"],
            types=list(config["types"]), exe=exe, cfg=cfg, env_extra=env,
            timeout=config["warm_s"] + ctx.seconds + 150.0)
    finally:
        if tracer is not None:
            tracer.stop.set()
            tracer.join(timeout=60.0)
    world_s = time.monotonic() - t0
    if tracer is not None and tracer.error is not None:
        raise RuntimeError(f"tracing failed: {tracer.error!r}")

    facts = server_stats[config["app_ranks"] + config["servers"]]["solver"]
    flight = None
    artefacts = glob.glob(os.path.join(flight_dir, "flight-sidecar-p*.json"))
    if len(artefacts) == 1:
        with open(artefacts[0]) as f:
            flight = json.load(f)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:ctx.chips])
    device["memory_peak_bytes"] = int(peak)
    for rank, (rc, out, err) in enumerate(results):
        if rc != 0:
            ctx.say(f"client rank {rank} exited {rc}: "
                    f"{(err or out).strip()[-300:]}")
    return {
        "device": device, "facts": facts, "flight": flight,
        "client_rcs": [rc for rc, _out, _err in results],
        "world_s": world_s, "t_world": t0, **check,
        "trace_dir": trace_dir,
        "trace_window_s": tracer.window_s if tracer is not None else None,
    }
