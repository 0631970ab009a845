"""Microseconds of the hot daemon's reactor a frame received that went
into reading and decoding it and into sending what it set off: (``decode``
+ ``flush`` seconds) over ``frames_ring`` + ``frames_sock``, over the
**whole world**, from the daemon's flight artefact."""

from benchmarks.reduce import daemons


def read(run):
    red = daemons.analyse(run)
    if red is None:
        return None
    hot = red["hot"]
    frames = hot["frames_ring"] + hot["frames_sock"]
    if not frames:
        return None
    wire = hot["phase_s"].get("decode", 0.0) + hot["phase_s"].get("flush", 0.0)
    return wire / frames * 1e6
