#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's own
size: the plain reference pool put in the program's place with one stated
guarantee broken, logged as the clients would log it, and judged by the
same comparison. It has to come out as not correct.

    python3 benchmarks/control.py --workload <cell> [--seeds 11 12 13] [--seconds <s>]

The configurations state no precision to lower; what a later PR would be
tempted to give up is exactly-once delivery — the holder's lease, the
delivery acknowledgement (``SS_DELIVERED``) and the validation at
enactment all exist for it and all cost round trips. So the control
delivers at least once (every ``every``-th delivery's acknowledgement is
"lost" and the unit comes again). ``--guarantee`` also takes the planted
faults: ``at_most_once`` (a put acknowledged and lost) and ``altered`` (a
unit changed where it is handed out). Needs no chip and touches no JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def stand_in_logs(plan, delivered, logdir: str, workers: int, seconds: float,
                  warm_s: float) -> list:
    """Log ``delivered`` (rows of id, work_us, tag) as ``workers`` clients
    would have: dealt round-robin, one unit a fetch, on a made-up clock
    whose window is ``[warm_s, warm_s + seconds]``. Returns the clients'
    exit codes."""
    import numpy as np

    from benchmarks.reduce import records

    os.makedirs(logdir, exist_ok=True)
    span = t_end = warm_s + seconds
    for w in range(workers):
        mine = delivered[w::workers]
        k = len(mine)
        t_ret = (np.arange(k) + 1) * (span / max(k, 1))
        units = np.zeros(k, dtype=records.UNIT)
        units["id"], units["work_us"], units["tag"] = mine.T
        units["t_put"] = 0.0
        units["t_end"] = t_end
        units["t_call"] = t_ret - 1e-4
        units["t_ret"] = t_ret
        units["t_done"] = t_ret + 1e-6 * units["work_us"]
        fetches = np.zeros(k + 1, dtype=records.FETCH)
        fetches["t_call"][:k] = units["t_call"]
        fetches["t_ret"][:k] = units["t_ret"]
        fetches["n_got"][:k] = 1
        fetches["rc"][:k] = 1
        fetches[k] = (span, span + 0.2, 0, -999999998)  # exhaustion
        # worker ranks start at 1: rank 0 is the producer
        records.write_worker_log(logdir, w + 1, units, fetches)
    records.write_producer_log(logdir, len(plan), 0.0, 1.0, t_end)
    return [0] * (workers + 1)


def judge(cell: str, seed: int, seconds: float, guarantee: str,
          every: int = 1000) -> dict:
    from benchmarks.reduce import records
    from benchmarks.reference import compare, pool
    from benchmarks.spec import Spec
    from benchmarks.traffic.generate import make_plan

    spec = Spec(ROOT)
    config, mix = spec.config(cell), spec.traffic(cell)
    plan = make_plan(config, mix, seed, seconds)
    logdir = tempfile.mkdtemp(prefix="control-", dir=os.environ.get("TMPDIR"))
    try:
        rcs = stand_in_logs(plan, pool.deliveries(plan, guarantee, every),
                            logdir, config["app_ranks"] - 1, seconds,
                            config["warm_s"])
        logs = records.read_logs(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    numbers = compare.compare(pool.deliveries(plan), logs, rcs, 0, len(plan))
    return {"cell": cell, "seed": seed, "guarantee": guarantee,
            "units": int(len(plan)), "correct": compare.verdict(numbers),
            "compared": compare.compared(numbers)}


def main(argv=None) -> int:
    from benchmarks.spec import Spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12, 13])
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--guarantee", default="at_least_once")
    args = ap.parse_args(argv)
    seconds = args.seconds or Spec(ROOT).run_seconds
    failed_to_fail = 0
    for seed in args.seeds:
        out = judge(args.workload, seed, seconds, args.guarantee)
        print(json.dumps(out))
        wrong = out["correct"] != (args.guarantee == "exactly_once")
        failed_to_fail += wrong
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
