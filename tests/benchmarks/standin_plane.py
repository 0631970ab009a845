"""A plane for the tests: the plain reference pool stands in for the
world, with one fault planted where the configuration's ``fault`` says.
It skips the look for a chip (it reports the planner's facts a sound run
would) so that everything after the world — logs, window, comparison,
readers, result — runs as in a real run."""

import numpy as np

from benchmarks.control import stand_in_logs
from benchmarks.reduce import records
from benchmarks.reference import greedy, pool

FAULTS = ("none", "at_least_once", "at_most_once", "altered",
          "client_failed", "unacked_put", "solve_altered", "t_end_altered")


def run(ctx) -> dict:
    fault = ctx.config.get("fault", "none")
    plan = np.fromfile(ctx.plan_path, dtype=records.PLAN)
    guarantee = fault if fault in pool.GUARANTEES else "exactly_once"
    delivered = pool.deliveries(plan, guarantee, every=50)
    rcs = stand_in_logs(plan, delivered, ctx.logdir,
                        ctx.config["app_ranks"] - 1, ctx.seconds,
                        ctx.config["warm_s"])
    if fault == "client_failed":
        rcs[3] = 6
    if fault == "unacked_put":
        records.write_producer_log(ctx.logdir, len(plan) - 1, 0.0, 1.0,
                                   ctx.config["warm_s"] + ctx.seconds)
    if fault == "t_end_altered":
        logs = records.read_logs(ctx.logdir)
        mine = logs.units[logs.unit_rank == 1].copy()
        mine["t_end"][0] += 1.0
        records.write_worker_log(ctx.logdir, 1, mine,
                                 logs.fetches[logs.fetch_rank == 1])
    nt, nr = ctx.config["solve_shape"]
    inputs = greedy.seeded_snapshot(ctx.seed, nt, nr, 1, -(2**31) + 1)
    got = greedy.greedy_assign(*inputs, -(2**31) + 1)
    if fault == "solve_altered":
        got[int(np.flatnonzero(got >= 0)[0])] = -1
    return {
        "device": {"platform": "standin", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 0},
        "facts": {"platform": "tpu", "path": "standin", "device_solves": 1,
                  "host_solves": 0, "device_failures": 0},
        "flight": None, "client_rcs": rcs, "world_s": 1.0, "t_world": 0.0,
        "solve_inputs": inputs, "solve_got": got, "pad_prio": -(2**31) + 1,
        "trace_dir": None, "trace_window_s": None,
    }
