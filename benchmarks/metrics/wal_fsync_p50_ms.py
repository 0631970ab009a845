"""Median ``adlb.wal.fsync`` in the traced window: one group commit of the
write-ahead log (``flush`` and ``fsync`` of the segment) on the reactor
thread of the process that was traced, the master rank's, which is the
producer's home server and so the hot one. The span is on a host line of
its own, not the planner's, so every host line is searched."""

import statistics

from benchmarks.reduce import hostspans, xplane

SPAN = "adlb.wal.fsync"


def median_ms(trace: dict):
    """Over a loaded trace that holds host events of every duration."""
    took = [event[2] for plane in trace["planes"]
            if plane["name"].startswith(xplane.HOST_PREFIX)
            for line in plane["lines"] for event in line["events"]
            if event[0] == SPAN]
    return statistics.median(took) * 1e-6 if took else None


def read(run):
    if run.get("trace") is None:
        return None
    path = hostspans.trace_path(run)
    if path is None:
        return None
    return median_ms(xplane.load(path, host_min_ns=0))
