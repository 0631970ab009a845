"""The producer's flood from its own side: acknowledged puts over the
seconds from its first put to its last acknowledgement, by the producer's
own clock and record (``p0.bin``). All its puts and all its time, the warm
phase with them. Listed only for mixes in which every put is one blocking
round trip (``flush_every`` 0, ``pace`` 0): pipelined, the same number is a
flush rate, and paced it is the schedule. A per-layer metric: the flood's
runs spread too widely between themselves for any bound the check allows
(``PERF.md`` section 2)."""


def read(run):
    return getattr(run.get("window"), "put_rate", None)
