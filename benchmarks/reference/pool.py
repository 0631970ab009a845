"""The plain reference: a work pool in a few lines, independent of
``adlb_tpu``. Same operations, same data, same answers: every unit that
was put and acknowledged comes out exactly once, to a caller that asked
for its type, highest priority first and first-in first-out within a
priority; the pool is exhausted only when nothing is left.

``guarantee`` selects what the pool promises. ``"exactly_once"`` is what
the configurations state. The others are the control and the planted
faults: each breaks one stated guarantee the way a tempting shortcut
would, and the comparison has to call every one of them not correct.
"""

from __future__ import annotations

import heapq

import numpy as np

GUARANTEES = ("exactly_once", "at_least_once", "at_most_once", "altered")


class PlainPool:
    def __init__(self, guarantee: str = "exactly_once", every: int = 1000):
        if guarantee not in GUARANTEES:
            raise ValueError(f"unknown guarantee {guarantee!r}")
        self.guarantee = guarantee
        self.every = every      # how often the broken guarantee bites
        self._heap: list = []   # (-prio, arrival, work_type, payload)
        self._arrivals = 0
        self._gets = 0

    def put(self, payload: tuple, work_type: int = 1, prio: int = 0) -> bool:
        """Store a unit; the return value is the acknowledgement."""
        self._arrivals += 1
        if self.guarantee == "at_most_once" and \
                self._arrivals % self.every == 0:
            return True  # acknowledged before it was stored, then lost
        heapq.heappush(self._heap,
                       (-prio, self._arrivals, work_type, payload))
        return True

    def get(self, types=(1,)):
        """The best unit of a wanted type, or None when exhausted."""
        skipped, got = [], None
        while self._heap:
            item = heapq.heappop(self._heap)
            if item[2] in types:
                got = item
                break
            skipped.append(item)
        for item in skipped:
            heapq.heappush(self._heap, item)
        if got is None:
            return None
        self._gets += 1
        payload = got[3]
        if self._gets % self.every == 0:
            if self.guarantee == "at_least_once":
                # the delivery's acknowledgement "was lost": deliver again
                self._arrivals += 1
                heapq.heappush(self._heap,
                               (got[0], self._arrivals, got[2], payload))
            elif self.guarantee == "altered":
                payload = (payload[0] + 1,) + tuple(payload[1:])
        return payload


def deliveries(plan: np.ndarray, guarantee: str = "exactly_once",
               every: int = 1000) -> np.ndarray:
    """Put the plan's units, one type and one priority as the cells'
    traffic has them, and get until exhausted. Returns an ``(n, 3)`` int64
    array of ``(id, work_us, tag)`` in delivery order."""
    pool = PlainPool(guarantee, every)
    for unit in zip(plan["id"].tolist(), plan["work_us"].tolist(),
                    plan["tag"].tolist()):
        pool.put(unit)
    out = []
    while (unit := pool.get()) is not None:
        out.append(unit)
    return np.asarray(out, dtype=np.int64).reshape(-1, 3)
