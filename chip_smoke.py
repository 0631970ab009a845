#!/usr/bin/env python
"""The quickest proof that the served planning path still runs on a TPU.

    python chip_smoke.py [--seed N]

Drives the system once through the entry points a user calls —
``spawn_world`` with Python servers, ``hotspot_native.run`` on the
all-native plane — at the largest deployment the repo itself runs (the
benchmark's ``hotspot-native-n128``, PERF.md §4: 128 app ranks, 32
servers, 5,291 units, a 65,536 x 8,192 solve), with every planning
round forced onto the device,
and checks the answers: every unit delivered exactly once, device
programs bit-identical to the numpy twin, and the planner's own account
of which path answered (platform ``tpu``, compiled Pallas, no host
solve, no device failure).

One process owns a chip. This process never imports JAX; each stage is
one child process that owns the chip for its lifetime and exits, and
stages run in turn. A stage that fails ends the run with a non-zero exit
and no result line: nothing here catches an error and carries on, and
there is no CPU fallback — where JAX shows no TPU the first stage says
which platform it found and the run fails.

Stages: ``device`` (both single-device programs against ``_host_greedy``,
at the int32 and the int8 kernel layouts), ``py-plane`` (forked master
rank owns the chip), ``native-plane`` (sidecar thread in the stage's own
process owns it), ``mesh`` (four or more devices: the native world on
the mesh planner), ``cache`` (``device`` again in a new process must hit
the persistent compilation cache).

The last line of standard output is one JSON object with exactly these
keys: ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reported it to the stages. The line before it, also
kept as ``chiprun_out/chip_smoke/summary.json``, is the run's summary:
what every stage returned, ending with ``"claim": null``. Seconds in it
are set-up facts (start-up, compile, one world), not speeds.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# the hotspot-native-n128 deployment: the largest world the repo runs
N128B = dict(n_tasks=5291, work_us=24000, num_app_ranks=128, nservers=32,
             fetch="batch:8")
N128B_K, N128B_R = 2048, 256

STAGE_TIMEOUT_S = 420.0   # one stage, one world
DEADLINE_S = 1140.0        # the whole run, compilation and builds included


# ------------------------------------------------------------- stage helpers


def _say(stage: str, text: str) -> None:
    print(f"[{stage}] {text}", flush=True)


def _require_tpu(stage: str, platform, kind, count) -> dict:
    """Print the device as JAX reported it; fail unless it is a TPU."""
    _say(stage, f"platform={platform} device_kind={kind} count={count}")
    if platform != "tpu":
        raise SystemExit(
            f"[{stage}] FAIL: JAX shows platform {platform!r}, not 'tpu' — "
            f"there is no CPU fallback")
    return {"platform": platform, "kind": kind, "count": count}


def _check_facts(stage: str, facts: dict, path: str) -> dict:
    """The planner's own account must say: TPU, the asked-for path, at
    least one device solve, no host solve, no failure — and what its
    devices hold, read in the process that owns them."""
    _say(stage, f"solver facts: {json.dumps(facts, sort_keys=True)}")
    device = _require_tpu(stage, facts["platform"], facts["device_kind"],
                          facts["device_count"])
    _say(stage, f"the chip's owner reports memory_peak_bytes="
                f"{facts.get('memory_peak_bytes')}")
    if not facts.get("memory_peak_bytes"):
        raise SystemExit(
            f"[{stage}] FAIL: memory_peak_bytes="
            f"{facts.get('memory_peak_bytes')!r}: the planner's process "
            f"did not read its devices' memory")
    if facts["path"] != path:
        raise SystemExit(
            f"[{stage}] FAIL: solver path {facts['path']!r}, wanted {path!r}")
    if (facts["device_solves"] < 1 or facts["host_solves"] != 0
            or facts["device_failures"] != 0):
        raise SystemExit(
            f"[{stage}] FAIL: device_solves={facts['device_solves']} "
            f"host_solves={facts['host_solves']} "
            f"device_failures={facts['device_failures']}")
    return device


def _solve_inputs(rng, NT: int, NR: int, T: int, late_type: bool):
    """Seeded solve inputs in the shape __graft_entry__.entry() uses: ~20%
    padding slots, half-full type masks, 80% valid requesters. With
    ``late_type`` the last type is rare, occurs only among the lowest
    priorities, and is all a quarter of the requesters accept — more of
    them than there are such tasks, so requesters stay open to the end
    and no task block of the sweep is skipped."""
    import numpy as np

    from adlb_tpu.balancer.solve import _NEG

    task_prio = rng.integers(-100, 100, size=(NT,)).astype(np.int32)
    req_mask = rng.random((NR, T)) < 0.5
    if late_type:
        task_type = rng.integers(0, T - 1, size=(NT,)).astype(np.int32)
        tail = rng.random(NT) < 0.01
        task_type[tail] = T - 1
        task_prio[tail] -= 1000
        only_late = rng.random(NR) < 0.25
        req_mask[only_late] = False
        req_mask[only_late, T - 1] = True
    else:
        task_type = rng.integers(0, T, size=(NT,)).astype(np.int32)
    pad = rng.random(NT) < 0.2
    task_prio[pad] = int(_NEG)
    task_type[pad] = -1
    req_valid = rng.random(NR) < 0.8
    return task_prio, task_type, req_mask, req_valid


def _run_native_world(stage: str, mesh: bool) -> tuple:
    """The n128b world on the all-native plane, every round on the device;
    returns (HotspotResult, sidecar solver facts, world seconds)."""
    import glob

    from adlb_tpu.runtime.world import Config
    from adlb_tpu.workloads import hotspot_native

    flight = os.path.join(OUT_DIR, f"flight-{stage}")
    shutil.rmtree(flight, ignore_errors=True)
    cfg = Config(
        balancer="tpu", balancer_max_tasks=N128B_K,
        balancer_max_requesters=N128B_R, solver_host_threshold=0,
        balancer_mesh="auto" if mesh else "off", flight_dir=flight,
    )
    t0 = time.monotonic()
    r = hotspot_native.run(cfg=cfg, timeout=STAGE_TIMEOUT_S - 60, **N128B)
    world_s = time.monotonic() - t0
    _say(stage, f"world: tasks={r.tasks} world_s={world_s:.1f}")
    if r.tasks != N128B["n_tasks"]:
        raise SystemExit(
            f"[{stage}] FAIL: {r.tasks} units consumed, "
            f"{N128B['n_tasks']} put")
    # the sidecar — a thread of THIS process, which therefore owns the
    # chip — leaves its solver facts in its flight artifact
    (artifact,) = glob.glob(os.path.join(flight, "flight-sidecar-p*.json"))
    with open(artifact) as f:
        doc = json.load(f)
    _say(stage, f"sidecar rounds={doc['rounds']}")
    return r, doc["solver"], world_s


# -------------------------------------------------------------------- stages


def stage_device(seed: int, stage: str = "device") -> dict:
    """Both single-device programs on the chip against the numpy twin,
    bit for bit, at both kernel layouts — among them the exact shapes the
    two worlds solve at, so their first planning round loads its program
    from the cache this stage filled."""
    import collections
    import functools

    import numpy as np

    import jax
    import jax.monitoring
    import jax.numpy as jnp

    devs = jax.devices()
    device = _require_tpu(stage, devs[0].platform, devs[0].device_kind,
                          len(devs))

    from adlb_tpu.balancer.pallas_solve import (
        _BIG_ELEMS, pallas_greedy_assign)
    from adlb_tpu.balancer.solve import _greedy_assign, _host_greedy
    from adlb_tpu.utils.jaxenv import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **_kw: events.update([name]))

    programs = {
        # interpret=False: Mosaic or nothing
        "pallas": functools.partial(pallas_greedy_assign, interpret=False),
        "xla": _greedy_assign,
    }
    rng = np.random.default_rng(seed)
    cases = [
        # (tasks, requesters, types, late_type)
        (1024, 256, 4, False),      # int32 layout; the entry() shape
        (4096, 1024, 1, False),     # int32 layout; the py-plane world's
        (65536, 8192, 1, False),    # int8 layout; the n128b world's
        (65536, 8192, 4, True),     # int8 layout; every task block swept
    ]
    first_s: dict = {}
    for NT, NR, T, late in cases:
        layout = "int8" if NT * NR >= _BIG_ELEMS else "int32"
        inputs = _solve_inputs(rng, NT, NR, T, late)
        want = _host_greedy(*inputs)
        args = [jnp.asarray(a) for a in inputs]
        for name, fn in programs.items():
            t0 = time.perf_counter()
            got = np.asarray(fn(*args))
            dt = time.perf_counter() - t0
            first_s[f"{name}_{NT}x{NR}x{T}"] = round(dt, 3)
            same = bool(np.array_equal(got, want))
            _say(stage,
                 f"{name} {NT}x{NR} T={T} layout={layout} late_type={late}: "
                 f"matched={int((want >= 0).sum())} identical={same} "
                 f"call_s={dt:.3f}")
            if got.shape != (NR,) or not same:
                raise SystemExit(
                    f"[{stage}] FAIL: {name} {NT}x{NR} differs from "
                    f"_host_greedy in {int((got != want).sum())} slots")
    mem = devs[0].memory_stats() or {}
    hits = events["/jax/compilation_cache/cache_hits"]
    misses = events["/jax/compilation_cache/cache_misses"]
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    _say(stage,
         f"cache dir={cache_dir} entries={entries} hits={hits} "
         f"misses={misses}; peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
         f"of bytes_limit={mem.get('bytes_limit')}")
    return {
        "device": device,
        "first_call_s": first_s,  # set-up: compile (or cache load) + 1 run
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "cache_dir": cache_dir, "cache_entries": entries,
        "cache_hits": hits, "cache_misses": misses,
        "programs": len(first_s),
    }


def stage_cache(seed: int) -> dict:
    """``device`` again, in a new process: the cache sits where it should
    and this run loaded from it what the first one compiled."""
    out = stage_device(seed, stage="cache")
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")
    if os.path.realpath(out["cache_dir"]) != os.path.realpath(want):
        raise SystemExit(
            f"[cache] FAIL: cache at {out['cache_dir']}, expected {want}")
    if out["cache_entries"] == 0 or out["cache_hits"] < out["programs"]:
        raise SystemExit(
            f"[cache] FAIL: {out['cache_entries']} entries, "
            f"{out['cache_hits']} hits for {out['programs']} programs")
    return out


def stage_py_plane(seed: int) -> dict:
    """spawn_world, fork start, Python servers, hotspot traffic. This
    process stays off JAX: the forked master rank owns the chip."""
    from adlb_tpu.runtime.transport_tcp import spawn_world
    from adlb_tpu.runtime.world import Config
    from adlb_tpu.utils.jaxenv import accelerator_held
    from adlb_tpu.workloads import hotspot

    # 4,000 units of 50 ms: the three consumers homed with the producer
    # would need a minute to drain them alone, so the world outlasts the
    # master's start-up and cannot end without plans from the device
    stage, n_tasks, apps, servers = "py-plane", 4000, 64, 16
    cfg = Config(
        balancer="tpu", solver_host_threshold=0, put_routing="home",
        exhaust_check_interval=0.2,
    )
    t0 = time.monotonic()
    res = spawn_world(
        apps, servers, [hotspot.TOKEN],
        hotspot.make_app(n_tasks, work_time=0.05), cfg=cfg,
        timeout=STAGE_TIMEOUT_S - 60,
    )
    world_s = time.monotonic() - t0
    if "jax" in sys.modules or accelerator_held() is not None:
        raise SystemExit(f"[{stage}] FAIL: the stage's own process touched "
                         f"JAX; the master rank's child must own the chip")
    ids = sorted(i for r, v in res.app_results.items() if r != 0
                 for i in v[4])
    r = hotspot.summarize(res)
    _say(stage, f"world: {apps} app ranks, {servers} servers, "
                f"tasks={r.tasks} world_s={world_s:.1f}")
    if ids != list(range(n_tasks)):
        raise SystemExit(
            f"[{stage}] FAIL: {len(ids)} deliveries of {len(set(ids))} "
            f"distinct units, {n_tasks} put — not exactly once")
    facts = res.solver_facts()
    device = _check_facts(stage, facts, "pallas")
    # the forked master rank held the chip, not this process: its memory
    # came out with the facts, as the benchmark's python plane takes it
    return {"device": device, "solver": facts, "tasks": r.tasks,
            "world_s": round(world_s, 1)}


def stage_native_plane(seed: int) -> dict:
    """C clients, C++ daemons, the sidecar thread in this process."""
    stage = "native-plane"
    r, facts, world_s = _run_native_world(stage, mesh=False)
    device = _check_facts(stage, facts, "pallas")
    # what the default placement rule (<= 64 parked -> numpy) would have
    # done with these rounds; printed for ROADMAP A2, not asserted on
    _say(stage,
         f"rounds with > 64 parked requesters: "
         f"{facts['rounds_over_default_threshold']} of "
         f"{facts['device_solves']} device solves")
    return {"device": device, "solver": facts, "tasks": r.tasks,
            "world_s": round(world_s, 1)}


def stage_mesh(seed: int) -> dict:
    """The native world on the mesh planner, then both auction tiers on
    one seeded snapshot."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    stage = "mesh"
    devs = jax.devices()
    _require_tpu(stage, devs[0].platform, devs[0].device_kind, len(devs))
    r, facts, world_s = _run_native_world(stage, mesh=True)
    device = _check_facts(stage, facts, "mesh-device")
    if facts["table_devices"] != len(devs):
        raise SystemExit(
            f"[{stage}] FAIL: resident table on {facts['table_devices']} "
            f"devices, {len(devs)} visible")

    from adlb_tpu.balancer.distributed import DistributedAssignmentSolver

    S, T = N128B["nservers"], 4
    types = tuple(range(1, T + 1))
    rng = np.random.default_rng(seed)
    snaps = {}
    for s in range(S):
        rank = N128B["num_app_ranks"] + s
        snaps[rank] = {
            "tasks": [
                (s * N128B_K + i, int(rng.integers(1, T + 1)),
                 int(rng.integers(-100, 100)), 64)
                for i in range(int(rng.integers(0, N128B_K)))
            ],
            "reqs": [
                (s * 64 + i, i + 1, [int(rng.integers(1, T + 1))])
                for i in range(int(rng.integers(0, 65)))
            ],
        }
    mesh = Mesh(np.array(devs), axis_names=("s",))
    pairs = {}
    for auction in ("device", "host"):
        solver = DistributedAssignmentSolver(
            types, N128B_K, N128B_R, mesh,
            servers_per_device=-(-S // len(devs)), auction=auction)
        pairs[auction] = sorted(solver.solve(snaps, None))
        _say(stage, f"seeded snapshot, auction={auction}: "
                    f"{len(pairs[auction])} pairs, {solver.facts()}")
    if not pairs["device"] or pairs["device"] != pairs["host"]:
        raise SystemExit(
            f"[{stage}] FAIL: device auction planned "
            f"{len(pairs['device'])} pairs, host auction "
            f"{len(pairs['host'])}, and they differ")
    return {"device": device, "solver": facts, "tasks": r.tasks,
            "world_s": round(world_s, 1),
            "auction_pairs": len(pairs["device"])}


STAGES = {
    "device": stage_device,
    "py-plane": stage_py_plane,
    "native-plane": stage_native_plane,
    "mesh": stage_mesh,
    "cache": stage_cache,
}


# -------------------------------------------------------------------- parent


def _run_stage(name: str, seed: int, deadline: float) -> dict:
    """One stage = one child process in its own session; whatever it
    started is killed when it ends, however it ends."""
    result = os.path.join(OUT_DIR, f"{name}.json")
    if os.path.exists(result):
        os.unlink(result)
    sys.stdout.flush()
    t0 = time.monotonic()
    timeout = max(min(STAGE_TIMEOUT_S, deadline - t0), 1.0)
    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--stage", name,
         "--seed", str(seed), "--result", result],
        cwd=HERE, start_new_session=True,
    )
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = f"none within {timeout:.0f}s"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if rc != 0:
        raise SystemExit(f"chip_smoke: stage {name} FAILED (exit {rc})")
    with open(result) as f:
        out = json.load(f)
    out["stage_s"] = round(time.monotonic() - t0, 1)
    print(f"[{name}] ok in {out['stage_s']} s", flush=True)
    return out


def result_line(device: dict) -> str:
    """The last line of a green run: ``ok`` and ``device`` and no other
    key — whoever reads it compares the keys exactly. Everything else the
    run learned is in the summary line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every stage's generated inputs")
    ap.add_argument("--stage", choices=sorted(STAGES), help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    args = ap.parse_args()

    sys.path.insert(0, HERE)
    if args.stage:  # a stage's child process
        out = STAGES[args.stage](args.seed)
        with open(args.result, "w") as f:
            json.dump(out, f)
        return 0

    if importlib.util.find_spec("adlb_tpu") is None:
        print("chip_smoke: adlb_tpu is not beside this script — nothing "
              "to run", file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    deadline = time.monotonic() + DEADLINE_S

    stages: dict = {"device": _run_stage("device", args.seed, deadline)}
    device = stages["device"]["device"]
    # there is a TPU, so the worlds will run: they build their native
    # artefacts from the committed sources, in here, inside the time
    # limit — from an empty build directory
    shutil.rmtree(os.path.join(HERE, "adlb_tpu", "native", "_build"),
                  ignore_errors=True)
    for name in ("py-plane", "native-plane"):
        stages[name] = _run_stage(name, args.seed, deadline)
    if device["count"] >= 4:
        stages["mesh"] = _run_stage("mesh", args.seed, deadline)
    else:
        print(f"mesh: not run ({device['count']} device)", flush=True)
        stages["mesh"] = f"not run ({device['count']} device)"
    stages["cache"] = _run_stage("cache", args.seed, deadline)
    if "jax" in sys.modules:
        raise SystemExit("chip_smoke: the parent process imported JAX")
    for name, out in stages.items():
        if isinstance(out, dict) and out["device"] != device:
            raise SystemExit(
                f"chip_smoke: stage {name} ran on {out['device']}, "
                f"stage device on {device}")
    summary = json.dumps({"ok": True, "device": device, "stages": stages,
                          "claim": None})
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        f.write(summary + "\n")
    print(f"summary: {summary}", flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
