#!/usr/bin/env python
"""Hotspot scaling curve: TPU balancer vs upstream-faithful stealing as
the server count (and with it, the gossip ring length) grows.

Upstream's global load picture is a store-and-forward ring token at a
fixed interval (reference ``src/adlb.c:165,806-822,1705-1757``): its
staleness is O(ring hops), so the balancing gap should WIDEN with server
count. This script measures that on the all-native plane (C clients, C++
daemons, JAX sidecar — one OS process per rank), printing one row per
scale and a JSON line at the end.

Usage: python scripts/scaling_curve.py [--quick]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="halve task counts (smoke test)")
    ap.add_argument("--plan-sweep", action="store_true",
                    help="run the sharded-balancer planning-latency "
                         "sweep (on every device JAX shows) instead of "
                         "the measured-worlds curve")
    args = ap.parse_args()

    if args.plan_sweep:
        from adlb_tpu.balancer import plan_bench

        raise SystemExit(
            plan_bench.main(["--quick"] if args.quick else []))

    from adlb_tpu.runtime.world import Config
    from adlb_tpu.workloads import hotspot_native

    # apps:servers fixed at 4:1; tasks sized for ~1 s of ideal makespan.
    # Grain: 8 ms through 64 ranks (continuity with earlier rounds); 24 ms
    # at 128 ranks — at 8 ms a 161-process world on this one-core host is
    # kernel-scheduling-bound (~70% idle in BOTH modes, the scheduler
    # decides the draw); the coarser grain keeps 128 ranks in the
    # balancing-bound regime the scenario is about.
    scales = [(16, 4, 8000), (32, 8, 8000), (64, 16, 8000),
              (128, 32, 24000)]
    rows = []
    for apps, servers, work_us in scales:
        n = (apps - 1) * 1000000 // work_us // (2 if args.quick else 1)
        # >= 32 ranks: a 41-161-process world on one core has
        # multi-second scheduler slow phases that swing single draws
        # +-30% in BOTH modes (a round-4 confirmatory run drew a 0.68
        # ratio on a single 32-rank rep whose immediate 3-rep re-draws
        # measured 1.12-1.15); interleaved 3-rep medians keep the rows
        # about balancing
        reps = 1 if (apps < 32 or args.quick) else 3
        runs = {"steal": [], "tpu": []}
        for _ in range(reps):
            for mode in ("steal", "tpu"):
                if mode == "steal":
                    c = Config(balancer="steal", qmstat_mode="ring",
                               qmstat_interval=0.1)
                else:
                    # K=2048 (matching bench.py's native rows): the hot
                    # queue runs ~2k deep and the fair-share pump needs
                    # the real total — a 512-cap snapshot understates the
                    # pool and distorts shares (measured: 16r tpu draws
                    # sag 5-15% under K=512). solver_host_threshold as
                    # in bench.py's native rows: ROADMAP A2 decides it.
                    c = Config(balancer="tpu", balancer_max_tasks=2048,
                               balancer_max_requesters=256,
                               solver_host_threshold=10**6)
                for attempt in (0, 1):
                    try:
                        r = hotspot_native.run(
                            n_tasks=n, work_us=work_us, num_app_ranks=apps,
                            nservers=servers, cfg=c, timeout=180.0,
                        )
                        break
                    except TimeoutError:
                        if attempt:
                            raise
                        print(f"  ({mode}@{servers} timed out; retrying)",
                              file=sys.stderr)
                assert r.tasks == n, f"{mode}@{servers}: lost work ({r.tasks})"
                runs[mode].append(r)

        def med(v, key):
            return sorted(v, key=key)[len(v) // 2]

        per = {m: med(runs[m], key=lambda r: r.tasks_per_sec)
               for m in ("steal", "tpu")}
        ratio = per["tpu"].tasks_per_sec / per["steal"].tasks_per_sec
        row = {
            "apps": apps,
            "servers": servers,
            "steal_tasks_per_sec": round(per["steal"].tasks_per_sec, 1),
            "tpu_tasks_per_sec": round(per["tpu"].tasks_per_sec, 1),
            "ratio": round(ratio, 3),
            "steal_idle_pct": round(per["steal"].idle_pct, 1),
            "tpu_idle_pct": round(per["tpu"].idle_pct, 1),
            "steal_wait_pct": round(per["steal"].wait_pct, 1),
            "tpu_wait_pct": round(per["tpu"].wait_pct, 1),
            "work_us": work_us,
        }
        rows.append(row)
        print(
            f"{apps:4d} ranks / {servers:2d} servers:  "
            f"steal {row['steal_tasks_per_sec']:>8.1f}/s "
            f"(idle {row['steal_idle_pct']:4.1f}%)   "
            f"tpu {row['tpu_tasks_per_sec']:>8.1f}/s "
            f"(idle {row['tpu_idle_pct']:4.1f}%)   ratio {row['ratio']:.3f}"
        )
    print(json.dumps({"metric": "hotspot_scaling_curve", "rows": rows}))


if __name__ == "__main__":
    main()
