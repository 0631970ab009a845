"""1 - (union of the device operations' intervals) / traced window."""

from benchmarks.reduce import xplane


def read(run):
    if run.get("trace") is None or not run.get("trace_window_s"):
        return None
    busy = xplane.busy_s(run["trace"])
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / run["trace_window_s"])
