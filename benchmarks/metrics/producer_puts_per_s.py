"""What a producing application feels: acknowledged puts over the seconds
from the producer's first put to its last acknowledgement, by the
producer's own clock and record (``p0.bin``). All its puts and all its
time, the warm phase with them. Listed only for mixes in which every put
is one blocking round trip (``flush_every`` 0, ``pace`` 0): pipelined, the
same number is a flush rate, and paced it is the schedule."""


def read(run):
    return getattr(run.get("window"), "put_rate", None)
