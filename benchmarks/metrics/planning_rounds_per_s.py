"""Rounds that passed the engine's gate (``adlb.round.plan``: they solved
or pumped) per second of the traced window."""

from benchmarks.reduce import hostspans


def read(run):
    red = hostspans.analyse(run)
    if red is None:
        return None
    return red["count"].get("adlb.round.plan", 0) / (red["window_ns"] * 1e-9)
