"""Milliseconds of each second of the window that the reactor thread of
the hot server (the promoted buddy: ``reduce/failover.py::hot``) spent in
the ``Server._flush_repl`` turns that sent a frame: taking the buffered
entries and putting one ``SS_REPL`` frame on the wire to its own ring
buddy, ahead of the acknowledgements it covers. From the server's own
counter, ``repl_flush_by_second`` of ``Server.finalize_stats()`` (seconds
by CLOCK_MONOTONIC second, a stretch split where it straddles one), over
the whole seconds that lie inside the window, as ``wal_flush_ms_per_s``
takes the log's."""

from benchmarks.reduce import failover


def read(run):
    by_second = (failover.hot(run) or {}).get("repl_flush_by_second")
    seconds = failover.window_seconds(run)
    if not by_second or seconds is None:
        return None
    spent = sum(by_second.get(str(sec), 0.0) for sec in seconds)
    return 1e3 * spent / len(seconds)
