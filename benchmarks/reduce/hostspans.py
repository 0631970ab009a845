"""The planner's own spans on the device trace's clock.

The program wraps the sidecar's loop, the planning round and the solve in
``adlb.*`` spans (``adlb_tpu/runtime/trace.py``); inside a profiler session
each is a ``TraceAnnotation`` on a host plane of the same ``.xplane.pb``
as the device planes. From that file this module takes, for the planner's
thread (the host line that holds the ``adlb.*`` events):

* the **window**: from the first to the last ``adlb.*`` event — what the
  trace can see of the planner. A span that was open when the session
  began or ended is not recorded, so this is the traced window less at
  most one span at each edge;
* **self time**: every instant of the window under its innermost span, so
  the names add up to the window and nothing is counted twice;
* the overlap of each name with the device's **idle intervals** (the
  complement of ``xplane.busy_intervals`` of the first device plane);
* the planning rounds (``adlb.round.plan``) with the spans inside each;
* a **clock check**: the share of the solve program's ``XLA Modules``
  events that start inside an ``adlb.solve.call`` or ``adlb.solve.wait``
  span. The device runs a solve only between its dispatch and the end of
  the wait, so under 95% the two clocks do not line up and ``analyse``
  returns None: every reader then reports nothing rather than a number
  laid over the wrong instants. On this runtime the profiler's device
  plane stands about a millisecond before its host plane (a program
  "starts" before its dispatch), so the check first moves the device
  plane by the one offset the solves themselves allow (``clock_offset``,
  at most 5 ms), says by how much, and checks with that.

``analyse(run)`` is what the metric readers call; it loads the trace once
a run, prints the composition of the idle time, of a planning round and of
the ten longest idle gaps on earlier lines, and keeps the result in
``run``. A program without the spans (a parent commit) gives None.
"""

from __future__ import annotations

import os
import statistics
from bisect import bisect_right

from benchmarks.reduce import xplane

PREFIX = "adlb."
#: the solve program's name in the trace, as ``metrics/solve_kernel_ms.py``
PROGRAM = "greedy_assign"
#: a module event may start in the microseconds between the ``call`` span's
#: end and the ``wait`` span's start: spans closer than this are joined
BRIDGE_NS = 50_000
CLOCK_CHECK_MIN = 0.95
#: the farthest the device plane's clock may stand from the host plane's
#: and still be laid over it (``clock_offset``)
MAX_OFFSET_NS = 5_000_000
NO_SPAN = "(no span)"
#: the spans directly inside a planning round (``engine.py::_plan``)
PLAN_CHILDREN = ("adlb.round.view", "adlb.solve", "adlb.round.mark",
                 "adlb.round.migrations", "adlb.round.account")
_KEY = "_hostspans"


def trace_path(run: dict) -> str | None:
    """The run's ``.xplane.pb``: ``run.py`` keeps a traced run's profile
    under ``<checkout>/.bench_scratch/<cell>/trace``."""
    root = os.path.dirname(run["bench_dir"])
    trace_dir = os.path.join(root, ".bench_scratch", run["cell"], "trace")
    try:
        return xplane.find_trace_file(trace_dir)
    except FileNotFoundError:
        return None


def planner_events(trace: dict) -> list:
    """The ``adlb.*`` events of the host line that holds most of them:
    the planner's thread. ``[[name, start_ns, duration_ns], ...]``."""
    best: list = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(xplane.HOST_PREFIX):
            continue
        for line in plane["lines"]:
            events = [e for e in line["events"] if e[0].startswith(PREFIX)]
            if len(events) > len(best):
                best = events
    return sorted(best, key=lambda e: (e[1], -e[2]))


def innermost(events: list) -> list:
    """Nested spans flattened to disjoint ``[start, end, name]`` segments,
    each named after the innermost span that covers it. ``events`` are
    sorted by start, the longer first among equal starts."""
    out: list = []
    stack: list = []  # [name, end] of the spans that are open
    cursor = 0

    def emit(upto: int) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            out.append([cursor, upto, stack[-1][0]])
        cursor = max(cursor, upto)

    for name, start, dur in events:
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        end = start + dur
        if stack:  # a child never outlives its parent
            end = min(end, stack[-1][1])
        stack.append([name, end])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def complement(intervals: list, lo: int, hi: int) -> list:
    """What ``[lo, hi]`` holds outside the sorted, disjoint ``intervals``."""
    out, cursor = [], lo
    for start, end in intervals:
        if end <= lo or start >= hi:
            continue
        if start > cursor:
            out.append([cursor, start])
        cursor = max(cursor, end)
    if cursor < hi:
        out.append([cursor, hi])
    return out


def overlap_by_name(intervals: list, segments: list) -> dict:
    """Nanoseconds of the sorted, disjoint ``intervals`` under each name of
    the sorted, disjoint ``segments``."""
    out: dict = {}
    j = 0
    for lo, hi in intervals:
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < hi:
            start, end, name = segments[k]
            part = min(hi, end) - max(lo, start)
            if part > 0:
                out[name] = out.get(name, 0) + part
            k += 1
    return out


def rounds(events: list, parent: str = "adlb.round.plan") -> list:
    """One dict per ``parent`` event: its duration under ``parent`` and the
    summed duration of each span that lies inside it (at any depth)."""
    starts = [e[1] for e in events]
    out = []
    for i, (name, start, dur) in enumerate(events):
        if name != parent:
            continue
        inside = {parent: dur}
        for child, c_start, c_dur in events[i + 1:bisect_right(
                starts, start + dur)]:
            if c_start + c_dur <= start + dur + 1:
                inside[child] = inside.get(child, 0) + c_dur
        out.append(inside)
    return out


def joined(events: list, names: tuple, bridge_ns: int = BRIDGE_NS) -> list:
    """Union of the spans called ``names``, neighbours nearer than
    ``bridge_ns`` joined: sorted ``[start, end]`` pairs."""
    merged: list = []
    for _name, start, dur in (e for e in events if e[0] in names):
        if merged and start - merged[-1][1] <= bridge_ns:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def clock_offset(spans: list, solves: list,
                 limit_ns: int = MAX_OFFSET_NS) -> int:
    """Nanoseconds to add to the device plane's times to lay them on the
    host plane's clock. ``spans`` are the joined ``adlb.solve.call`` /
    ``.wait`` intervals, ``solves`` the solve program's module events. A
    solve runs between its dispatch and the end of the wait that saw it
    finish, so each module event bounds the offset from both sides; the
    answer is the least move, within ``limit_ns`` of zero, in the range
    that most of them agree on: zero where the planes already line up (the
    profiler aligns them itself, to about a millisecond on this runtime)."""
    starts = [s[0] for s in spans]
    marks = []
    for _name, start, dur in solves:
        # the few spans that begin no later than limit_ns after the solve
        i = bisect_right(starts, start + limit_ns)
        for lo, hi in spans[max(i - 3, 0):i]:
            low = max(lo - start, -limit_ns)
            high = min(hi - (start + dur), limit_ns)
            if low <= high:
                marks += [(low, 0, 1), (high, 1, -1)]
    best, best_range, depth = 0, (0, 0), 0
    marks.sort()
    for (at, _closing, step), nxt in zip(marks, marks[1:] + [(0, 0, 0)]):
        depth += step
        if depth > best:
            best, best_range = depth, (at, nxt[0])
    low, high = best_range
    return 0 if low <= 0 <= high else (low if low > 0 else high)


def clock_check(spans: list, solves: list, offset_ns: int = 0):
    """Share of the solve program's module events that, moved by
    ``offset_ns``, start inside an ``adlb.solve.call`` or ``.wait`` span;
    None when there is no such module event to check."""
    ends = [s[1] for s in spans]
    inside = 0
    for _name, start, _dur in solves:
        i = bisect_right(ends, start + offset_ns)
        if i < len(spans) and spans[i][0] <= start + offset_ns:
            inside += 1
    return inside / len(solves) if solves else None


def reduce(trace: dict) -> dict | None:
    """Everything the readers need, from a loaded trace (host events at
    every duration); None when it holds no ``adlb.*`` event or no device
    plane."""
    events = planner_events(trace)
    devices = xplane.device_planes(trace)
    if not events or not devices:
        return None
    lo = events[0][1]
    hi = max(e[1] + e[2] for e in events)
    solves = [m for m in xplane._line(devices[0], xplane.MODULES_LINE)
              if PROGRAM in m[0] and lo <= m[1] < hi]
    on_device = joined(events, ("adlb.solve.call", "adlb.solve.wait"))
    offset = clock_offset(on_device, solves)
    segments = innermost(events)
    self_ns: dict = {}
    for start, end, name in segments:
        self_ns[name] = self_ns.get(name, 0) + end - start
    busy = [[start + offset, end + offset] for start, end in
            xplane.busy_intervals(xplane._line(devices[0], xplane.OPS_LINE))]
    idle = complement(busy, lo, hi)
    idle_ns = sum(e - s for s, e in idle)
    idle_by = overlap_by_name(idle, segments)
    gaps = []
    for start, end in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        by = overlap_by_name([[start, end]], segments)
        by[NO_SPAN] = end - start - sum(by.values())
        gaps.append({"seconds": (end - start) * 1e-9, "by": {
            k: v / (end - start) for k, v in by.items() if v > 0}})
    durations: dict = {}
    for name, _start, dur in events:
        durations.setdefault(name, []).append(dur)
    return {
        "window_ns": hi - lo, "events": len(events),
        "clock_offset_ns": offset,
        "clock_check": clock_check(on_device, solves, offset),
        "clock_check_unmoved": clock_check(on_device, solves),
        "self_ns": self_ns,
        "total_ns": {k: sum(v) for k, v in durations.items()},
        "count": {k: len(v) for k, v in durations.items()},
        "median_ns": {k: statistics.median(v) for k, v in durations.items()},
        "idle_ns": idle_ns, "idle_by": idle_by,
        "idle_named_ns": sum(idle_by.values()),
        "gaps": gaps,
        "rounds": rounds(events),
        "round_ns": [r["adlb.round"] for r in rounds(events, "adlb.round")
                     if "adlb.round.plan" in r],
    }


def _shares(by: dict, whole: float, n: int = 6) -> str:
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return ", ".join(f"{k} {100.0 * v / whole:.1f}%" for k, v in top)


def describe(red: dict) -> list:
    """The earlier lines of a traced run: what the device's idle time, a
    planning round and the longest idle gaps are made of."""
    window = red["window_ns"]
    lines = [
        f"spans: {red['events']} adlb.* events over {window * 1e-9:.3f}s of "
        f"the planner's thread; clock check: device plane moved by "
        f"{red['clock_offset_ns'] * 1e-6:+.3f} ms, "
        f"{100.0 * red['clock_check']:.1f}% of solve programs then start "
        f"inside adlb.solve.call/.wait "
        f"({100.0 * red['clock_check_unmoved']:.1f}% unmoved)",
        "spans: planner's thread by innermost span: "
        + _shares(red["self_ns"], window, 8),
        f"spans: device idle {red['idle_ns'] * 1e-9:.3f}s, of it under: "
        + _shares({**red["idle_by"],
                   NO_SPAN: red["idle_ns"] - red["idle_named_ns"]},
                  red["idle_ns"], 8),
    ]
    for name in sorted(red["count"]):
        lines.append(
            f"spans: {name}: {red['count'][name]} in the window, median "
            f"{red['median_ns'][name] * 1e-6:.3f} ms, total "
            f"{red['total_ns'][name] * 1e-6:.1f} ms, self "
            f"{red['self_ns'].get(name, 0) * 1e-6:.1f} ms")
    planned = red["rounds"]
    if planned:
        covered = statistics.median(
            sum(r.get(k, 0) for k in PLAN_CHILDREN) / r["adlb.round.plan"]
            for r in planned)
        lines.append(
            f"spans: {len(planned)} planning rounds, "
            f"{sum('adlb.round.migrations' in r for r in planned)} pumped, "
            f"{sum('adlb.solve' in r for r in planned)} solved; the spans "
            f"inside adlb.round.plan cover {100.0 * covered:.1f}% of it in "
            f"the median round")
    if red["round_ns"]:
        lines.append(
            f"spans: median adlb.round over rounds that planned "
            f"{statistics.median(red['round_ns']) * 1e-6:.3f} ms")
    for gap in red["gaps"]:
        lines.append(f"spans: idle gap {gap['seconds']:.4f}s: "
                     + _shares(gap["by"], 1.0, 5))
    return lines


def attach(run: dict, trace: dict) -> dict | None:
    """Reduce ``trace`` (host events at every duration), say on earlier
    lines what it shows, and keep the reduction in ``run`` for the
    readers; None, and the reason on an earlier line, when the program
    emits no ``adlb.*`` span or the clock check fails."""
    run[_KEY] = None
    red = reduce(trace)
    cell = run.get("cell", "")
    if red is None:
        print(f"[{cell}] spans: the trace holds no adlb.* event — the "
              f"program has no spans; the span metrics are left out",
              flush=True)
        return None
    if red["clock_check"] is None or red["clock_check"] < CLOCK_CHECK_MIN:
        print(f"[{cell}] spans: clock check failed — {red['clock_check']} of "
              f"the solve programs start inside adlb.solve.call/.wait (need "
              f"{CLOCK_CHECK_MIN}) with the device plane moved by "
              f"{red['clock_offset_ns']} ns, the most allowed being "
              f"{MAX_OFFSET_NS}; host spans and device events are not on "
              f"one clock, the span metrics are left out", flush=True)
        return None
    for line in describe(red):
        print(f"[{cell}] {line}", flush=True)
    run[_KEY] = red
    return red


def analyse(run: dict) -> dict | None:
    """The reduction of this run's trace, made once a run; None when the
    run was not traced or ``attach`` found nothing to stand on."""
    if _KEY not in run:
        run[_KEY] = None
        path = trace_path(run) if run.get("trace") is not None else None
        if path is not None:
            attach(run, xplane.load(path, host_min_ns=0))
    return run[_KEY]


def median_ms(run: dict, name: str) -> float | None:
    """Median duration of the spans called ``name``, in milliseconds."""
    red = analyse(run)
    if red is None or name not in red["median_ns"]:
        return None
    return red["median_ns"][name] * 1e-6
