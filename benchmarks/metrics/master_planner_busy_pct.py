"""Share of the traced window in which the in-server planner's thread
(``_BalancerWorker``, in the master rank's process) was neither waiting on
its doorbell (``adlb.master.wait``) nor sleeping out the round gap
(``adlb.master.pace``), by the spans' self time on the profiler's clock.
The sidecar's twin is ``planner_busy_pct``."""

from benchmarks.reduce import hostspans

RESTING = ("adlb.master.wait", "adlb.master.pace")


def read(run):
    red = hostspans.analyse(run)
    if red is None or not any(name in red["count"] for name in RESTING):
        return None
    resting = sum(red["self_ns"].get(name, 0) for name in RESTING)
    return 100.0 * (red["window_ns"] - resting) / red["window_ns"]
