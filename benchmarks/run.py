#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, in a
traced run, ``breakdown``), then ``compared``: every number the
comparison looked at beside its limit. With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Everything else is on earlier lines and in ``chiprun_out/bench/<cell>/``.

This process never imports JAX. It starts the run proper as one child in
a session of its own — the child, or a rank it forks, owns the chip, as
the cell's plane decides (``planes/<plane>.py``) — and kills that whole
session on every way out, so no rank, daemon or listener outlives a run.
A run that finds no TPU, too few chips, a planner that solved on the
host or kept its table on fewer chips than the cell has, a plane that
handed over no solve check, or no ``adlb_tpu`` beside it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RUN_LIMIT_S = 1150.0  # a checkout's first run builds and compiles


def scratch_dir(root: str, cell: str) -> str:
    return os.path.join(root, ".bench_scratch", cell)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--t0", type=float, default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


# ---------------------------------------------------------------- the parent


def parent(args) -> int:
    from benchmarks.spec import Spec, SpecError

    if importlib.util.find_spec("adlb_tpu") is None:
        print("benchmark: adlb_tpu is not beside BENCHMARK.json — there is "
              "no system to measure", file=sys.stderr)
        return 2
    try:
        spec = Spec(ROOT)
        spec.cell(args.workload)
    except (SpecError, OSError, KeyError, json.JSONDecodeError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    scratch = scratch_dir(ROOT, args.workload)
    shutil.rmtree(scratch, ignore_errors=True)  # nothing stale is read
    os.makedirs(scratch)
    result_path = os.path.join(scratch, "result.json")
    seconds = args.seconds if args.seconds is not None else spec.run_seconds
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--child",
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", repr(float(seconds)), "--trace", str(args.trace),
           "--t0", repr(T_START)]

    def on_signal(signum, _frame):
        raise SystemExit(128 + signum)

    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, on_signal)
    sys.stdout.flush()
    proc = subprocess.Popen(cmd, cwd=ROOT, start_new_session=True)
    try:
        try:
            rc = proc.wait(timeout=RUN_LIMIT_S)
        except subprocess.TimeoutExpired:
            print(f"benchmark: no end within {RUN_LIMIT_S:.0f}s",
                  file=sys.stderr)
            rc = 124
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if rc != 0:
        print(f"benchmark: the run failed (exit {rc}); no result",
              file=sys.stderr)
        return rc if rc > 0 else 1
    with open(result_path) as f:
        result = json.load(f)
    for name, pair in result["compared"].items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


# ----------------------------------------------------------------- the child


def check_planner(facts: dict, chips: int) -> None:
    """A run whose planner was not the chip did not measure this cell; nor
    did one of a cell on several chips whose planner kept its task table
    on fewer (``balancer_mesh="auto"`` plans on one device where it sees
    one). One-chip planners report no ``table_devices``."""
    if (facts.get("platform") != "tpu" or facts.get("host_solves") != 0
            or facts.get("device_failures") != 0
            or not facts.get("device_solves")):
        raise SystemExit(
            f"benchmark: the planner reports {json.dumps(facts)} — not "
            f"every round on a TPU; this run measured another system")
    if chips > 1 and facts.get("table_devices") != chips:
        raise SystemExit(
            f"benchmark: the planner's task table lay on "
            f"{facts.get('table_devices')} device(s) — it did not spread "
            f"its table over the cell's {chips} chips")


def solve_mismatch(rec: dict) -> int:
    """Entries in which the device program's answer differs from the plain
    reference's. A plane hands over one of two forms: ``solve_inputs``,
    ``solve_got`` and ``pad_prio`` (the one-chip dense table, answered
    here by ``reference/greedy.py``), or ``solve_check = {"want", "got"}``
    (the answer of its own module under ``reference/`` beside the device
    program's, two integer arrays of one shape; ``want`` may be a function
    of no arguments, called here, so that the reference runs after the
    window and outside set-up). A plane that hands over neither leaves
    its device program unchecked: the run has no result."""
    import numpy as np

    from benchmarks.reference import greedy

    dense = rec.get("solve_inputs") is not None
    own = rec.get("solve_check") is not None
    if dense == own:
        raise SystemExit(
            f"benchmark: the plane handed over "
            f"{'both forms of' if dense else 'no'} solve check — the device "
            f"program's answer cannot be compared")
    if dense:
        want = greedy.greedy_assign(*rec["solve_inputs"], rec["pad_prio"])
        return int((want != rec["solve_got"]).sum())
    want = rec["solve_check"]["want"]
    want = np.asarray(want() if callable(want) else want)
    got = np.asarray(rec["solve_check"]["got"])
    if (want.shape != got.shape or want.dtype.kind not in "iu"
            or got.dtype.kind not in "iu"):
        raise SystemExit(
            f"benchmark: the plane's solve check holds {want.dtype}"
            f"{list(want.shape)} against {got.dtype}{list(got.shape)} — "
            f"not two integer arrays of one shape")
    return int((want != got).sum())


def finish(spec, args, ctx, rec: dict, t_start: float) -> dict:
    """Everything after the world: reduce the logs, compare with the
    reference, read the metrics, build the result. ``rec`` is what the
    plane returned, ``ctx.plan`` what the producer was given."""
    from benchmarks.reduce import records, xplane
    from benchmarks.reduce.window import Window
    from benchmarks.reference import compare, pool

    cell, config, mix = args.workload, ctx.config, ctx.mix
    logs = records.read_logs(ctx.logdir)
    if logs.producer is None:
        raise SystemExit("benchmark: the producer left no record — the "
                         "world did not run to its end")
    window = Window(logs, ctx.seconds, config["app_ranks"] - 1,
                    config["servers"], bool(mix.get("needs_backlog")))
    ctx.say(window.describe())
    ctx.say(f"set-up {window.t0 - t_start:.2f}s: world called at "
            f"{rec['t_world'] - t_start:.2f}s, first put "
            f"{float(logs.producer['t_first']) - rec['t_world']:.2f}s later, "
            f"then warm_s {config['warm_s']:g}s")

    # the reference runs last: the window has closed, the peak is read
    plan = ctx.plan
    expected = pool.deliveries(plan)
    numbers = compare.compare(expected, logs, rec["client_rcs"],
                              solve_mismatch(rec), len(plan))
    correct = compare.verdict(numbers)

    trace = None
    if args.trace and rec.get("trace_dir"):
        trace = xplane.load(xplane.find_trace_file(rec["trace_dir"]))
    records_for_readers = {
        "cell": cell, "config": config, "mix": mix, "seconds": ctx.seconds,
        "window": window, "logs": logs, "facts": rec["facts"],
        "flight": rec.get("flight"), "world_s": rec["world_s"],
        "trace": trace, "trace_window_s": rec.get("trace_window_s"),
        "device": rec["device"], "setup_s": window.t0 - t_start,
        "bench_dir": spec.bench_dir,
    }
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in spec.metrics(kind, cell):
        value = spec.reader(entry["name"])(records_for_readers)
        if value is None:
            if kind == "end_to_end":
                raise SystemExit(f"benchmark: end-to-end metric "
                                 f"{entry['name']} has no value")
            continue  # nothing to read: the metric is left out
        metrics[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    device = dict(rec["device"])
    result = {"correct": bool(correct), "attempted": int(len(plan)),
              "failed": int(numbers["missing_units"]
                            + numbers["duplicated_units"]
                            + numbers["altered_units"]
                            + numbers["unacked_puts"]),
              "metrics": metrics, "device": device}
    if args.trace:
        busy = xplane.busy_s(trace) if trace is not None else None
        if not busy or not rec.get("trace_window_s"):
            raise SystemExit("benchmark: the trace shows no operation on "
                             "the device")
        device["busy_s"] = busy
        device["window_s"] = rec["trace_window_s"]
        result["breakdown"] = {"device_ops": xplane.top_ops(trace),
                               "idle_gaps": xplane.idle_gaps(trace)}
    result["unsteady"] = window.flags
    result["compared"] = compare.compared(numbers)
    return result


def child(args, root: str = ROOT) -> int:
    from benchmarks.spec import Spec
    from benchmarks.traffic.generate import make_plan

    spec = Spec(root)
    cell = spec.cell(args.workload)
    scratch = scratch_dir(root, args.workload)
    logdir = os.path.join(scratch, "logs")
    os.makedirs(logdir, exist_ok=True)

    def say(text: str) -> None:
        print(f"[{args.workload}] {text}", flush=True)

    ctx = types.SimpleNamespace(
        config=spec.config(args.workload), mix=spec.traffic(args.workload),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        chips=cell["chips"], scratch=scratch, logdir=logdir,
        plan_path=os.path.join(scratch, "plan.bin"), say=say)
    ctx.plan = make_plan(ctx.config, ctx.mix, args.seed, args.seconds)
    ctx.plan.tofile(ctx.plan_path)
    say(f"seed {args.seed}: {len(ctx.plan)} units, window {args.seconds:g}s, "
        f"trace {args.trace}")
    rec = spec.plane(args.workload).run(ctx)
    say(f"world {rec['world_s']:.1f}s; planner {json.dumps(rec['facts'])}")
    check_planner(rec["facts"], cell["chips"])
    t_start = args.t0 if args.t0 is not None else T_START
    result = finish(spec, args, ctx, rec, t_start)
    say(f"result: {json.dumps(result)}")
    out_dir = os.path.join(root, "chiprun_out", "bench", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    name = f"run-seed{args.seed}-trace{args.trace}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f)
    with open(os.path.join(scratch, "result.json"), "w") as f:
        json.dump(result, f)
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    return child(args) if args.child else parent(args)


if __name__ == "__main__":
    sys.exit(main())
