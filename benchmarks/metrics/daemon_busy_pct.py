"""Share of the window in which the hot daemon's reactor thread (the
producer's home server: every put enters it) was neither asleep in epoll
nor looking at connections that brought nothing: 100 x (1 - ``asleep`` -
``poll``) over the whole seconds inside the window, from ``by_second`` of
the daemon's own flight artefact (``reduce/daemons.py``). The native twin
of ``reactor_busy_pct``; that one counts the ring scans as busy, this one
does not."""

from benchmarks.reduce import daemons


def read(run):
    red = daemons.analyse(run)
    win = red and red["hot_window"]
    if not win:
        return None
    resting = win["s"]["asleep"] + win["s"]["poll"]
    return 100.0 * (1.0 - resting / win["seconds"])
