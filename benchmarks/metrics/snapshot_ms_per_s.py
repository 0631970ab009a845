"""Milliseconds a second that the hot daemon's reactor spent in
``send_snapshot`` and ``flush_event_deltas`` (the walk and sort of its
whole queue for the planner, wherever it was called from), over the whole
seconds inside the window, from ``by_second`` of the daemon's flight
artefact. A snapshot that outlasts ``balancer_interval`` starves every
frame behind it (PERF.md, PR 38): this is the number that shows it."""

from benchmarks.reduce import daemons


def read(run):
    red = daemons.analyse(run)
    win = red and red["hot_window"]
    if not win:
        return None
    return win["s"]["snapshot"] * 1e3 / win["seconds"]
