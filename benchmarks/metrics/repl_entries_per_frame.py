"""Replication entries per ``SS_REPL`` frame at the hot server (the
promoted buddy: ``reduce/failover.py::hot``) over the world:
``repl_entries / repl_frames`` of ``Server.finalize_stats()``, how far one
frame to the ring buddy is amortised. A put is acknowledged only behind
the frame that carries its entry, so a flood of single puts reads near 1;
the promotion's re-log of the adopted shard goes out as one frame and the
window's consumes and removes ride the reactor's turns."""

from benchmarks.reduce import failover


def read(run):
    hot = failover.hot(run) or {}
    if not hot.get("repl_frames"):
        return None
    return hot.get("repl_entries", 0) / hot["repl_frames"]
