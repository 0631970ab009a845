"""Milliseconds of each second of the window that the reactor thread of
the producer's home server (the hot one) spent in ``Server._flush_wal``:
writing the buffered log records out and, when a group commit was due, the
commit itself with the release of the acknowledgements it covers. From the
server's own counter, ``wal_flush_by_second`` of
``Server.finalize_stats()`` (seconds by CLOCK_MONOTONIC second, a stretch
split where it straddles one), over the whole seconds that lie inside the
window, as ``reactor_busy_pct`` takes the reactor's busy seconds."""

import math

from benchmarks.reduce import servers


def read(run):
    by_second = (servers.home(run) or {}).get("wal_flush_by_second")
    if not by_second:
        return None
    first = math.ceil(run["window"].t0)
    last = math.floor(run["window"].t_end)  # exclusive
    if last <= first:
        return None
    spent = sum(by_second.get(str(sec), 0.0) for sec in range(first, last))
    return 1e3 * spent / (last - first)
