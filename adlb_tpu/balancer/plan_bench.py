"""Planning-latency sweep for the sharded (multichip) balancer.

Measures the full planning round — snapshot-delta ingest -> sharded
solve -> plan extracted on host — on the devices JAX shows, at a ladder
of world sizes up to 10,000 servers / 1M parked requesters. Steady state
is engine-faithful: every round ships task deltas for a handful of
servers, the previous round's plan is consumed by the data plane
(matched tasks leave their queues, matched requesters unpark), and
stamps ride the snapshots so the solver's unchanged-server fast path is
exercised the way the engine drives it.

    python -m adlb_tpu.balancer.plan_bench [--quick] [--ndev N]

``--ndev`` defaults to every visible device and fails when fewer are
visible than asked for; it never re-provisions. For a CPU mesh, say so
in the environment:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m adlb_tpu.balancer.plan_bench --quick

A parent that must stay off JAX runs this module as a child process.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

#: (servers, max_tasks K, max_requesters R) ladder; the last row is the
#: acceptance scale: 10,000 servers x 100 parked requesters each = 1M
#: (--quick keeps the first, 1k and 10k rows: the smoke still covers
#: the acceptance scale AND the 1k row the plan_round_1k_ms key is
#: derived from)
SCALES = [(64, 16, 16), (256, 16, 32), (1000, 16, 100), (10000, 16, 100)]
TYPES = tuple(range(1, 9))
DELTA_SERVERS = 8  # servers receiving a task burst per steady round


def _mk_reqs(rng, s, R):
    return [
        (s * 200 + i, i + 1, [int(rng.integers(1, len(TYPES) + 1))])
        for i in range(R)
    ]


def run_sweep(scales=None, reps: int = 40, ndev: int = 8,
              rounds: int = 16, auction: str = "device") -> dict:
    """Requires >= ndev visible JAX devices. Returns the result dict."""
    import jax
    from jax.sharding import Mesh

    from adlb_tpu.balancer.distributed import DistributedAssignmentSolver

    from adlb_tpu.utils.jaxenv import ensure_compile_cache

    ensure_compile_cache()
    devs = np.array(jax.devices()[:ndev])
    if len(devs) < ndev:
        raise RuntimeError(
            f"need {ndev} devices, JAX shows {len(devs)} "
            f"({jax.devices()[0].platform})")
    mesh = Mesh(devs, axis_names=("s",))
    rows = []
    for S, K, R in scales or SCALES:
        rng = np.random.default_rng(S)
        solver = DistributedAssignmentSolver(
            TYPES, K, R, mesh, rounds=rounds,
            servers_per_device=-(-S // ndev),
            auction=auction,
        )
        clock = [1.0]

        def stamp():
            clock[0] += 1.0
            return clock[0]

        snaps = {}
        for s in range(S):
            st = stamp()
            snaps[100 + s] = {
                "tasks": [], "reqs": _mk_reqs(rng, s, R),
                "stamp": st, "task_stamp": st,
            }
        t0 = time.perf_counter()
        solver.ingest(snaps)
        cold_ingest_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        solver.plan()
        compile_ms = (time.perf_counter() - t0) * 1e3

        seq = [10**6]

        def add_tasks(sv, n):
            snap = snaps[sv]
            burst = [
                (seq[0] + i, int(rng.integers(1, len(TYPES) + 1)),
                 int(rng.integers(-50, 50)), 64)
                for i in range(n)
            ]
            seq[0] += n
            snap["tasks"] = sorted(
                snap["tasks"] + burst, key=lambda t: -t[2])[:K]
            snap["task_stamp"] = stamp()

        lat, npairs = [], []
        rq = [10**7]
        for it in range(reps):
            for d in range(DELTA_SERVERS):
                add_tasks(100 + (it * DELTA_SERVERS + d) % S, K)
            t0 = time.perf_counter()
            solver.ingest(snaps)
            pairs = solver.plan()
            lat.append((time.perf_counter() - t0) * 1e3)
            npairs.append(len(pairs))
            # the data plane consumes the plan; a served worker computes,
            # then re-parks (fresh rqseqno) — the pool stays at scale
            touched: dict = {}
            for holder, seqno, req_home, for_rank, rqseqno in pairs:
                touched.setdefault(holder, set()).add(seqno)
                rs = snaps[req_home]
                rq[0] += 1
                rs["reqs"] = [
                    r for r in rs["reqs"]
                    if not (r[0] == for_rank and r[1] == rqseqno)
                ] + [(for_rank, rq[0],
                      [int(rng.integers(1, len(TYPES) + 1))])]
                rs["stamp"] = stamp()
            for h, seqs in touched.items():
                hs = snaps[h]
                hs["tasks"] = [
                    t for t in hs["tasks"] if t[0] not in seqs]
                hs["task_stamp"] = stamp()
        lat.sort()
        # warm full-mesh sweep cost (the first sweep above paid compile)
        t0 = time.perf_counter()
        solver._sweep()
        warm_sweep_ms = (time.perf_counter() - t0) * 1e3

        def pct(p):
            return round(lat[min(int(p * len(lat)), len(lat) - 1)], 2)

        rows.append({
            "servers": S, "K": K, "R": R, "parked_reqs": S * R,
            "plan_round_p50_ms": pct(0.50),
            "plan_round_p90_ms": pct(0.90),
            "plan_round_max_ms": round(lat[-1], 2),
            "pairs_per_round_p50": int(np.median(npairs)),
            "device_sweep_ms": round(warm_sweep_ms, 2),
            "sweeps": solver.sweep_count,
            "cold_ingest_ms": round(cold_ingest_ms, 1),
            "compile_ms": round(compile_ms, 1),
        })
        print(
            f"plan-sweep {S:5d} servers x {R:4d} reqs "
            f"({S*R} parked): p50 {rows[-1]['plan_round_p50_ms']:7.2f} ms  "
            f"p90 {rows[-1]['plan_round_p90_ms']:7.2f} ms  "
            f"pairs/round {rows[-1]['pairs_per_round_p50']}  "
            f"device sweep {rows[-1]['device_sweep_ms']:.1f} ms "
            f"(x{rows[-1]['sweeps']})"
        )
    out = {
        "metric": "plan_round_latency",
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": ndev,
        "rounds": rounds,
        "auction": auction,
        "delta_servers_per_round": DELTA_SERVERS,
        "rows": rows,
        "note": (
            "full planning round (snapshot-delta ingest -> sharded solve "
            "-> plan extracted on host) on the mesh named above; "
            "steady state is engine-faithful (plans consumed, stamps "
            "ride snapshots). device_sweep_ms is the full mesh re-sweep "
            "paid at cold start / large deltas / every RESYNC_INTERVAL "
            "plans; small deltas patch the merged candidate lists "
            "incrementally (exact, see balancer/distributed.py)."
        ),
    }
    # compact scalar keys beside the rows
    for r in rows:
        if r["servers"] == 1000:
            out["plan_round_1k_ms"] = r["plan_round_p50_ms"]
        elif r["servers"] == 10000:
            out["plan_round_10k_ms"] = r["plan_round_p50_ms"]
    return out


#: engine-round overhead ladder: (servers, tasks-per-supply-server,
#: reqs-per-server) — parked totals 1k / 10k / 100k
ENGINE_SCALES = [(1000, 16, 1), (1000, 16, 10), (1000, 16, 100)]
SUPPLY_SERVERS = 64  # servers holding queued inventory (cross demand)


class _NullSolver:
    """Measures ENGINE-side admission only: accepts either input shape
    and plans nothing (the solve itself is plan_round_1k_ms's job)."""

    SUPPORTS_VIEW = True

    def solve(self, snapshots, world) -> list:
        return []


def run_engine_sweep(scales=None, reps: int = 40) -> dict:
    """engine.round() overhead at 1k/10k/100k parked requesters, array
    ledger vs the pure-Python twin (the pre-vectorization cost), on a
    steady state that stamps DELTA_SERVERS fresh snapshots per round —
    the O(changed rows) path the resident ledger exists for. Needs no
    devices (null solver): this isolates admission — ledger filter,
    suppression, cross-feasibility gate, pump pre-check, solver-input
    packing — from the solve."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine
    from adlb_tpu.balancer.ledger import SnapshotStore

    rows = []
    for S, K, R in scales or ENGINE_SCALES:
        row = {"servers": S, "parked_reqs": S * R}
        for ledger in ("array", "py"):
            rng = np.random.default_rng(S * R)
            eng = PlanEngine(
                types=TYPES, max_tasks=K, max_requesters=max(R, 4),
                host_ledger=ledger,
            )
            eng.solver = _NullSolver()
            seq = [10**6]
            # the array arm is driven the way the runtime drives it: a
            # versioned SnapshotStore, so the ledger sync touches only
            # the DELTA_SERVERS re-stamped ranks per round instead of
            # comparing all S snapshots (the r07 1k-parked floor). The
            # py twin keeps the plain dict — it re-derives everything
            # per round by definition, store or not.
            snaps: dict = SnapshotStore() if ledger == "array" else {}
            t0 = _time.monotonic()
            for s in range(S):
                tasks = []
                if s < SUPPLY_SERVERS:
                    tasks = [
                        (seq[0] + i, int(rng.integers(1, len(TYPES) + 1)),
                         int(rng.integers(-50, 50)), 64)
                        for i in range(K)
                    ]
                    seq[0] += K
                # reqs park on NON-supply servers: cross-server demand,
                # so every round admits the solve (the representative
                # steady state for a serving fleet; consumers stay 0 so
                # the pump never fires — its walk is measured by the
                # hotspot benches)
                reqs = _mk_reqs(rng, s, R) if s >= SUPPLY_SERVERS else []
                snaps[100 + s] = {
                    "tasks": tasks, "reqs": reqs, "consumers": 0,
                    "stamp": t0, "task_stamp": t0,
                }
            lat = []
            rq = [10**7]
            for it in range(max(reps, 4)):
                t1 = _time.perf_counter()
                eng.round(snaps, None)
                dt = (_time.perf_counter() - t1) * 1e6
                if it >= 3:  # first rounds pay allocation/registration
                    lat.append(dt)
                # steady state: a handful of servers re-stamp with fresh
                # parks (everything else rides the unchanged fast path)
                t2 = _time.monotonic()
                for d in range(DELTA_SERVERS):
                    s = SUPPLY_SERVERS + (
                        (it * DELTA_SERVERS + d) % (S - SUPPLY_SERVERS))
                    snap = snaps[100 + s]
                    rq[0] += 1
                    snap["reqs"] = list(snap["reqs"][1:]) + [
                        (s * 200, rq[0],
                         [int(rng.integers(1, len(TYPES) + 1))])
                    ]
                    snap["stamp"] = t2
                    if ledger == "array":
                        snaps.bump(100 + s)  # in-place re-stamp
            lat.sort()
            p50 = lat[len(lat) // 2]
            key = "engine_round_us" if ledger == "array" \
                else "engine_round_py_us"
            row[key] = round(p50, 1)
            if ledger == "array":
                led = eng._ledger
                # the fast path must actually be taken: patches happened,
                # and NOT MORE than the workload explains — cold start
                # builds 2 columns per server, each steady round rebuilds
                # the DELTA_SERVERS re-stamped servers' req columns (a
                # change-key bug that silently rebuilt the world every
                # round would blow straight through this bound), plus a
                # full-resync allowance; full rebuilds only at cadence
                assert led.patch_count > 0, "ledger fast path never taken"
                budget = (
                    2 * S + (max(reps, 4) + 1) * 2 * DELTA_SERVERS
                    + led.resync_count * 2 * S
                )
                assert led.patch_count <= budget, (
                    f"fast path lost: {led.patch_count} patches > "
                    f"{budget} explained by the workload")
                assert led.resync_count <= reps // led.LEDGER_RESYNC_INTERVAL + 1, (
                    led.resync_count)
                # the O(Δ) steady-state claim, reason-labelled: after
                # the one cold full pass, full walks happen ONLY at the
                # cadence resync — a membership-classified walk here
                # would mean the store fast path was never engaged
                assert led.resync_reasons.get("cold", 0) <= 1, (
                    led.resync_reasons)
                assert led.resync_reasons.get("membership", 0) == 0, (
                    f"steady state paid membership walks: "
                    f"{led.resync_reasons}")
                row["ledger_patches"] = led.patch_count
                row["ledger_resyncs"] = led.resync_count
                row["ledger_resync_reasons"] = {
                    k: v for k, v in led.resync_reasons.items() if v}
                row["ledger_rows"] = led.rows_resident()
        row["speedup"] = round(row["engine_round_py_us"]
                               / max(row["engine_round_us"], 1e-9), 1)
        rows.append(row)
        print(
            f"engine-round {row['parked_reqs']:6d} parked: array p50 "
            f"{row['engine_round_us']:9.1f} us  py twin "
            f"{row['engine_round_py_us']:9.1f} us  "
            f"({row['speedup']}x, {row['ledger_patches']} patches, "
            f"{row['ledger_resyncs']} resyncs)"
        )
    out = {
        "metric": "engine_round_overhead",
        "delta_servers_per_round": DELTA_SERVERS,
        "rows": rows,
        "note": (
            "engine.round() admission overhead (ledger filter + "
            "suppression + cross gate + pump pre-check + solver-input "
            "packing; null solver, so the solve itself is excluded) on "
            "a steady state re-stamping DELTA_SERVERS snapshots per "
            "round. engine_round_us = array-resident host ledger "
            "(balancer/ledger.py), engine_round_py_us = the retained "
            "pure-Python twin (the pre-PR-10 cost). The array arm runs "
            "on a versioned SnapshotStore, as the runtime does since "
            "the O(S) scan kill."
        ),
    }
    # compact scalar keys beside the rows
    for r in rows:
        if r["parked_reqs"] == 1000:
            out["admission_1k_ms"] = round(r["engine_round_us"] / 1e3, 3)
        elif r["parked_reqs"] == 100000:
            out["engine_round_us_100k"] = r["engine_round_us"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="fewer reps, smallest+largest scales only")
    ap.add_argument("--ndev", type=int, default=None,
                    help="mesh size (default: every visible device)")
    ap.add_argument("--auction", choices=("device", "host"),
                    default="device",
                    help="sharded-solver auction tier to measure "
                         "(host = the retained reference twin)")
    ap.add_argument("--engine-rounds", action="store_true",
                    help="measure engine.round admission overhead "
                         "(host-ledger ladder) instead of the mesh "
                         "planning sweep; needs no devices")
    ap.add_argument("--json-only", action="store_true",
                    help="suppress progress lines (JSON on stdout)")
    args = ap.parse_args(argv)

    if args.engine_rounds:
        def run():
            scales = (
                [ENGINE_SCALES[0], ENGINE_SCALES[-1]] if args.quick
                else ENGINE_SCALES
            )
            return run_engine_sweep(
                scales=scales, reps=20 if args.quick else 40)
    else:
        scales = [SCALES[0], SCALES[2], SCALES[-1]] if args.quick else SCALES
        reps = 20 if args.quick else 40

        def run():
            import jax

            return run_sweep(scales=scales, reps=reps,
                             ndev=args.ndev or len(jax.devices()),
                             auction=args.auction)

    if args.json_only:
        import contextlib
        import io
        import sys

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            out = run()
        _stamp_provenance(out)
        sys.stdout.write(json.dumps(out) + "\n")
    else:
        out = run()
        _stamp_provenance(out)
        print(json.dumps(out))
    return 0


def _stamp_provenance(out) -> None:
    """Core count + load on every record: scheduler-bound numbers from
    a 1-core box must be readable as such."""
    if isinstance(out, dict):
        import os as _os

        out.setdefault("cpu_count", _os.cpu_count() or 1)
        if hasattr(_os, "getloadavg"):
            out.setdefault("loadavg_1m", round(_os.getloadavg()[0], 2))


if __name__ == "__main__":
    raise SystemExit(main())
