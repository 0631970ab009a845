"""Share of the device's idle time, within the traced window, that lies
under some ``adlb.*`` span of the planner's thread: how much of "the chip
idles" the program can put a name to."""

from benchmarks.reduce import hostspans


def read(run):
    red = hostspans.analyse(run)
    if red is None or not red["idle_ns"]:
        return None
    return 100.0 * red["idle_named_ns"] / red["idle_ns"]
