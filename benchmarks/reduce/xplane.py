"""From a profiler trace to device numbers: busy time, time per program,
the operations that took most, and the longest idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
nothing but JAX, into plain lists; everything else works on those, so a
small recorded trace kept as JSON checks the arithmetic
(``tests/benchmarks``).

A trace is ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation that ran, their ``XLA Modules`` line one per program run.
"""

from __future__ import annotations

import glob
import os
from bisect import bisect_left

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"


def find_trace_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, host_min_ns: int = 200_000) -> dict:
    """Device planes whole; of host planes only events of at least
    ``host_min_ns`` (they name what the host did during an idle gap)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith(HOST_PREFIX):
            continue
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if device or ev.duration_ns >= host_min_ns
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PREFIX)]


def busy_intervals(events: list) -> list:
    """Union of the events' intervals, as sorted ``[start, end]`` pairs."""
    merged: list = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_s(trace: dict) -> float | None:
    """Seconds in which an operation ran, averaged over the device planes;
    None when the trace has no device plane."""
    planes = device_planes(trace)
    if not planes:
        return None
    total = 0
    for plane in planes:
        total += sum(e - s for s, e in busy_intervals(_line(plane, OPS_LINE)))
    return total / len(planes) * 1e-9


def program_runs(trace: dict, match: str) -> tuple:
    """(runs, device seconds) of the programs whose module name contains
    ``match``, on the first device plane. A program's device time is the
    time of the operations that ran inside its module events."""
    planes = device_planes(trace)
    if not planes:
        return 0, 0.0
    mods = [e for e in _line(planes[0], MODULES_LINE) if match in e[0]]
    ops = sorted(_line(planes[0], OPS_LINE), key=lambda e: e[1])
    starts = [o[1] for o in ops]
    total = 0
    for _name, start, dur in mods:
        inside = ops[bisect_left(starts, start):
                     bisect_left(starts, start + dur)]
        total += sum(e - s for s, e in busy_intervals(inside))
    return len(mods), total * 1e-9


def top_ops(trace: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the operations that took most device
    time, summed by name over the device planes."""
    by_name: dict = {}
    for plane in device_planes(trace):
        for name, _start, dur in _line(plane, OPS_LINE):
            by_name[name] = by_name.get(name, 0) + dur
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in top]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """``[[what the host was doing, seconds], ...]``: the longest gaps
    between device operations on the first device plane, each named after
    the host event that covers most of it, if one covers half or more,
    else ``unattributed`` (host code with no span of its own)."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = busy_intervals(_line(planes[0], OPS_LINE))
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:n]
    host = [ev for p in trace["planes"] if p["name"].startswith(HOST_PREFIX)
            for line in p["lines"] for ev in line["events"]]
    out = []
    for length, g0, g1 in gaps:
        best, best_overlap = "unattributed", length / 2.0
        for name, start, dur in host:
            overlap = min(g1, start + dur) - max(g0, start)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        out.append([best, length * 1e-9])
    return out
