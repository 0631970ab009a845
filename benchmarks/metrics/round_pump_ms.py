"""The tuple walk of a pump round: median, over the planning rounds of
the traced window that pumped (they hold an ``adlb.round.migrations``), of
``adlb.round.view`` + ``adlb.round.migrations``."""

import statistics

from benchmarks.reduce import hostspans


def read(run):
    red = hostspans.analyse(run)
    if red is None:
        return None
    pumped = [r.get("adlb.round.view", 0) + r["adlb.round.migrations"]
              for r in red["rounds"] if "adlb.round.migrations" in r]
    return statistics.median(pumped) * 1e-6 if pumped else None
