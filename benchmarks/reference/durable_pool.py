"""The plain reference of a durable pool: ``pool.py``'s work pool with a
log that outlives the process, in a few lines and independent of
``adlb_tpu``. ``put`` appends the unit to ``disk``, a list that stands for
what has been written to the log, **before** it acknowledges; ``crash``
takes the memory and leaves the disk; ``recover`` rebuilds the pool from
the disk, each record once, and stops at a record the crash cut in half.
Every acknowledged put then comes out exactly once after the restart.

``guarantee`` selects what the pool promises. ``"durable"`` is what
``hotspot-py-n64-wal`` states. The others each break it the way a tempting
shortcut would, and the comparison has to call every one not correct:

``ack_before_log``  every ``every``-th put is acknowledged while its
                    record is still in the process's buffer: lost at the
                    crash (``missing_units``);
``replay_twice``    every ``every``-th record is adopted twice by the
                    recovery (``duplicated_units``);
``torn_accepted``   the half-written last record is adopted instead of
                    being cut off (``altered_units``: a unit nobody was
                    told is in the pool, and not as it was put).
"""

from __future__ import annotations

from collections import deque

import numpy as np

GUARANTEES = ("durable", "ack_before_log", "replay_twice", "torn_accepted")
_TORN = "torn"


class DurablePool:
    def __init__(self, guarantee: str = "durable", every: int = 1000):
        if guarantee not in GUARANTEES:
            raise ValueError(f"unknown guarantee {guarantee!r}")
        self.guarantee = guarantee
        self.every = every           # how often the broken guarantee bites
        self.disk: list = []         # the log: what a crash leaves
        self.memory: deque = deque()  # the live pool, first in first out
        self._puts = 0

    def put(self, unit: tuple) -> bool:
        """Log, then store; the return value is the acknowledgement."""
        self._puts += 1
        buffered = (self.guarantee == "ack_before_log"
                    and self._puts % self.every == 0)
        if not buffered:
            self.disk.append(unit)
        self.memory.append(unit)
        return True

    def crash(self, writing: tuple | None = None) -> None:
        """Every process dies. ``writing``: a put whose record was being
        written, and so was never acknowledged: half of it is on disk."""
        self.memory.clear()
        if writing is not None:
            self.disk.append((_TORN, writing[0]))

    def recover(self) -> int:
        """Replay the disk into the pool; returns the units adopted."""
        for i, record in enumerate(self.disk):
            if record[0] == _TORN:
                if self.guarantee == "torn_accepted":
                    # the id had been written, the rest had not
                    self.memory.append((record[1], 0, 0))
                break  # nothing after a torn record is a record
            self.memory.append(record)
            if self.guarantee == "replay_twice" and (i + 1) % self.every == 0:
                self.memory.append(record)
        return len(self.memory)

    def get(self):
        return self.memory.popleft() if self.memory else None


def deliveries(plan: np.ndarray, guarantee: str = "durable",
               every: int = 1000) -> np.ndarray:
    """The cell's story on the plain pool: put the plan's units, die while
    one more put is being logged, recover, and get until exhausted.
    ``(n, 3)`` int64 rows of ``(id, work_us, tag)`` in delivery order, as
    ``pool.deliveries`` returns them."""
    pool = DurablePool(guarantee, every)
    for unit in zip(plan["id"].tolist(), plan["work_us"].tolist(),
                    plan["tag"].tolist()):
        pool.put(unit)
    pool.crash(writing=(int(plan["id"].max()) + 1, 0, 0))
    pool.recover()
    out = []
    while (unit := pool.get()) is not None:
        out.append(unit)
    return np.asarray(out, dtype=np.int64).reshape(-1, 3)
