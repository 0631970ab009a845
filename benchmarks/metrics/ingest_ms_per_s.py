"""Milliseconds the planner's thread spent draining its inbox
(``adlb.sidecar.ingest``: decode, merge, hungry broadcasts) per second of
the traced window."""

from benchmarks.reduce import hostspans


def read(run):
    red = hostspans.analyse(run)
    if red is None or "adlb.sidecar.ingest" not in red["total_ns"]:
        return None
    return red["total_ns"]["adlb.sidecar.ingest"] * 1e-6 \
        / (red["window_ns"] * 1e-9)
