"""Median ``adlb.repl.flush`` in the traced window: one sending turn of
``Server._flush_repl`` (the buffered entries taken, one ``SS_REPL`` frame
put on the wire to the ring buddy) on the reactor thread of the process
that was traced, which after the death is the promoted master's and so the
hot server's. The span is on a host line of its own, not the planner's, so
every host line is searched, as ``wal_fsync_p50_ms`` does."""

import statistics

from benchmarks.reduce import hostspans, xplane

SPAN = "adlb.repl.flush"


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
