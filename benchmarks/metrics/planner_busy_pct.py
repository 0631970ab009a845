"""Share of the traced window in which the planner's thread was neither
blocked in its inbox (``adlb.sidecar.wait``) nor sleeping out the round gap
(``adlb.sidecar.pace``), by the spans' self time on the profiler's clock."""

from benchmarks.reduce import hostspans


def read(run):
    red = hostspans.analyse(run)
    if red is None:
        return None
    resting = sum(red["self_ns"].get(name, 0)
                  for name in ("adlb.sidecar.wait", "adlb.sidecar.pace"))
    return 100.0 * (red["window_ns"] - resting) / red["window_ns"]
