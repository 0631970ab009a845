"""Log records per group commit at the producer's home server (the hot
one) over the world that served the window: ``wal_records / wal_syncs`` of
``Server.finalize_stats()``, how far one ``fsync`` is amortised. In a
restart cell that world is the restarted one, so the flood's records are
not in it: these are the consumes, removes and puts of service and of
migration."""

from benchmarks.reduce import servers


def read(run):
    home = servers.home(run) or {}
    if not home.get("wal_syncs"):
        return None
    return home.get("wal_records", 0) / home["wal_syncs"]
