"""Microseconds of the hot daemon's reactor a fetch frame
(``FA_RESERVE``, ``FA_GET_RESERVED``, ``FA_GET_COMMON``): the handlers'
self time over their count, in the whole seconds inside the window, from
``by_second`` of the daemon's flight artefact. The snapshots a reserve
sets off are not in it (their own phase)."""

from benchmarks.reduce import daemons


def read(run):
    red = daemons.analyse(run)
    return daemons.per_frame_us(red and red["hot_window"], "fetch")
