#!/usr/bin/env python3
"""Run a cell the way the driver's check runs it: the benchmark's own
command, N times, each a new process, back to back in one checkout that
holds only what git would commit.

    python3 benchmarks/rehearse.py --unpack .bench_rehearse/tree     # here, needs git
    python3 .bench_rehearse/tree/benchmarks/rehearse.py --cell <cell> --runs 6

``--unpack`` writes ``git archive $(git write-tree)`` of the index into a
directory (``git add -A`` first; ``.gitignore`` lists ``.bench_rehearse/``).
The second form runs from the tree the script itself lies in, so calling
the unpacked copy rehearses the unpacked tree: seeds ``--first-seed`` ..
+N-1, the last ``--trace-runs`` of them with ``--trace 1``. After each run
it prints the exit code, whether the last line of standard output is the
contract's object (its keys, the cell's metrics by name, the device's
keys), whether the window was flagged unsteady, and the processes and
listening TCP ports that were not there before the run.
Exit code 0 only when every run passed every check.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}
ALLOWED = REQUIRED | {"breakdown", "compared", "unsteady"}
DEVICE = {"platform", "kind", "count", "memory_peak_bytes"}


def unpack(dest: str) -> int:
    tree = subprocess.run(["git", "write-tree"], cwd=ROOT, check=True,
                          capture_output=True, text=True).stdout.strip()
    subprocess.run(["rm", "-rf", dest], check=True)
    os.makedirs(dest)
    archive = subprocess.Popen(["git", "archive", tree], cwd=ROOT,
                               stdout=subprocess.PIPE)
    subprocess.run(["tar", "-x", "-C", dest], stdin=archive.stdout,
                   check=True)
    if archive.wait() != 0:
        return 1
    print(f"unpacked tree {tree} into {dest}")
    return 0


def processes() -> dict:
    """pid -> command line of every process we can see."""
    out = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out[int(pid)] = f.read().replace(b"\0", b" ").decode(
                    "utf-8", "replace").strip()
        except OSError:
            pass
    return out


def listeners() -> set:
    """Local TCP ports in LISTEN state."""
    ports = set()
    for path in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            with open(path) as f:
                for line in f.readlines()[1:]:
                    fields = line.split()
                    if fields[3] == "0A":
                        ports.add(int(fields[1].rsplit(":", 1)[1], 16))
        except OSError:
            pass
    return ports


def check_line(line: str, spec, cell: str, trace: int) -> list:
    """What is wrong with a result line; empty when nothing is."""
    try:
        obj = json.loads(line)
    except ValueError:
        return [f"last line is not JSON: {line[:120]!r}"]
    if not isinstance(obj, dict):
        return ["last line is not an object"]
    wrong = []
    keys = set(obj)
    if REQUIRED - keys:
        wrong.append(f"missing keys {sorted(REQUIRED - keys)}")
    if keys - ALLOWED:
        wrong.append(f"unknown keys {sorted(keys - ALLOWED)}")
    if wrong:
        return wrong
    if obj["correct"] is not True:
        wrong.append(f"correct={obj['correct']}")
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"]: m["unit"] for m in spec.metrics(kind, cell)}
    got = obj["metrics"]
    if trace:
        if not got or set(got) - set(names):
            wrong.append(f"per-layer metrics {sorted(got)}")
    elif set(got) != set(names):
        wrong.append(f"metrics {sorted(got)} != {sorted(names)}")
    for name, m in got.items():
        if set(m) != {"value", "unit"} or m["unit"] != names.get(name) \
                or not isinstance(m["value"], (int, float)):
            wrong.append(f"metric {name}: {m}")
    need = DEVICE | ({"busy_s", "window_s"} if trace else set())
    if need - set(obj["device"]):
        wrong.append(f"device lacks {sorted(need - set(obj['device']))}")
    if trace and not obj["device"].get("busy_s", 0) > 0:
        wrong.append("busy_s is not above 0")
    if list(obj)[-1] != "compared":
        wrong.append("compared does not come last")
    if obj.get("unsteady"):
        wrong.append(f"window unsteady: {obj['unsteady']}")
    return wrong


def rehearse(cell: str, runs: int, seconds, first_seed: int,
             trace_runs: int) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks.spec import Spec

    spec = Spec(ROOT)
    doc = spec.doc
    spec.cell(cell)
    seconds = seconds if seconds is not None else spec.run_seconds
    bad = 0
    for i in range(runs):
        seed = first_seed + i
        trace = 1 if i >= runs - trace_runs else 0
        cmd = list(doc["command"]) + [
            "--workload", cell, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
        before_p, before_l = processes(), listeners()
        t0 = time.monotonic()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                              env={**os.environ, "BENCH_RUN": f"r{i}"})
        took = time.monotonic() - t0
        time.sleep(0.5)
        left_p = {pid: c for pid, c in processes().items()
                  if pid not in before_p and pid != os.getpid() and c}
        left_l = sorted(listeners() - before_l)
        lines = proc.stdout.strip().splitlines()
        wrong = [f"exit {proc.returncode}"] if proc.returncode else []
        if proc.returncode == 0:
            wrong += check_line(lines[-1] if lines else "", spec, cell, trace)
        if left_p:
            wrong.append(f"left processes {left_p}")
        if left_l:
            wrong.append(f"left listeners {left_l}")
        steady = [ln for ln in lines if "] window:" in ln]
        print(f"run {i}: seed {seed} trace {trace} exit {proc.returncode} "
              f"in {took:.1f}s — {'OK' if not wrong else 'WRONG: ' + '; '.join(wrong)}")
        for ln in steady[-1:]:
            print(f"    {ln}")
        if lines and proc.returncode == 0:
            print(f"    {lines[-1][:1400]}")
        if wrong:
            bad += 1
            print("    stdout tail: " + " | ".join(lines[-6:])[-1500:])
            print("    stderr tail: " + proc.stderr[-1500:].replace("\n", " | "))
        sys.stdout.flush()
    print(f"rehearsal of {cell}: {runs - bad} of {runs} runs passed every "
          f"check, from {ROOT}")
    return 1 if bad else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--unpack", metavar="DIR")
    ap.add_argument("--cell")
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--first-seed", type=int, default=0)
    ap.add_argument("--trace-runs", type=int, default=1)
    args = ap.parse_args(argv)
    if args.unpack:
        return unpack(os.path.abspath(args.unpack))
    if not args.cell:
        ap.error("--cell or --unpack")
    return rehearse(args.cell, args.runs, args.seconds, args.first_seed,
                    args.trace_runs)


if __name__ == "__main__":
    sys.exit(main())
