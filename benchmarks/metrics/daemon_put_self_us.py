"""Microseconds of the hot daemon's reactor a put: ``handler:FA_PUT``
seconds over its count, over the **whole world**, from the daemon's flight
artefact. One thread serves puts and fetches, so a put's handler is time a
fetch waits behind; under a synchronous producer it is also the daemon's
share of ``put_rtt_p50_ms``."""

from benchmarks.reduce import daemons


def read(run):
    red = daemons.analyse(run)
    return daemons.handler_us(red["hot"], "FA_PUT") if red else None
