#!/usr/bin/env python3
"""The control of a failover cell, at the cell's own size: the plain
replicated pool (``reference/replicated_pool.py``) put through the cell's
story — half the plan put and acknowledged, the primary dead with the
producer's pipeline in flight, the buddy promoted from its mirror, the
unacknowledged puts re-sent under their ids, the rest of the plan put into
the pool that is left, and all of it drained — once as it should be and
once with each stated guarantee broken, logged as the clients would log it
(``control.py``'s stand-in) and judged by the comparison every run is
judged by. The sound pool has to come out correct with every number 0 and
each broken one not correct, on every seed.

    python3 benchmarks/control_failover.py --workload <cell> [--seeds 11 12 13] [--seconds <s>]

Needs no chip and touches no JAX.
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


def judge(cell: str, seed: int, seconds: float, guarantee: str,
          every: int = 1000) -> dict:
    from benchmarks.control import stand_in_logs
    from benchmarks.reduce import records
    from benchmarks.reference import compare, pool, replicated_pool
    from benchmarks.spec import Spec
    from benchmarks.traffic.generate import make_plan

    spec = Spec(ROOT)
    config, mix = spec.config(cell), spec.traffic(cell)
    plan = make_plan(config, mix, seed, seconds)
    logdir = tempfile.mkdtemp(prefix="control-", dir=os.environ.get("TMPDIR"))
    try:
        rcs = stand_in_logs(
            plan, replicated_pool.deliveries(
                plan, guarantee, every, in_flight=int(mix["flush_every"])),
            logdir, config["app_ranks"] - 1, seconds, config["warm_s"])
        logs = records.read_logs(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    numbers = compare.compare(pool.deliveries(plan), logs, rcs, 0, len(plan))
    return {"cell": cell, "seed": seed, "guarantee": guarantee,
            "units": int(len(plan)), "correct": compare.verdict(numbers),
            "compared": compare.compared(numbers)}


def main(argv=None) -> int:
    from benchmarks.reference.replicated_pool import GUARANTEES
    from benchmarks.spec import Spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[11, 12, 13])
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    seconds = args.seconds or Spec(ROOT).run_seconds
    wrong = 0
    for seed in args.seeds:
        for guarantee in GUARANTEES:
            out = judge(args.workload, seed, seconds, guarantee)
            print(json.dumps(out))
            wrong += out["correct"] != (guarantee == "replicated")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
