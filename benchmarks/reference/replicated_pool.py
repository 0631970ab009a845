"""The plain reference of a replicated pool: ``pool.py``'s work pool with a
buddy that mirrors it, in a few lines and independent of ``adlb_tpu``.
``put(unit, put_id)`` copies the unit to ``mirror``, a list that stands for
what has reached the buddy, **before** it acknowledges; ``kill_primary``
takes the primary's memory and leaves the mirror; ``promote`` makes the
mirror the pool and keeps the put ids it saw, so that a put the client
re-sends under its id, because the acknowledgement died with the primary,
is stored once. Every acknowledged put then comes out exactly once from
the pool that is left, and every unacknowledged one the client re-sent.

``guarantee`` selects what the pool promises. ``"replicated"`` is what
``hotspot-py-n64-failover`` states. The others each break it the way a
tempting shortcut would, and the comparison has to call every one not
correct:

``ack_before_mirror``  every ``every``-th put is acknowledged while its
                       entry is still in the primary's buffer: lost at the
                       death (``missing_units``);
``no_dedup``           the promoted buddy keeps no put ids: a re-sent put
                       that the mirror already held is stored again
                       (``duplicated_units``).

No consume precedes the death in the cell, so there is no variant that
replays a mirrored consume.
"""

from __future__ import annotations

from collections import deque

import numpy as np

GUARANTEES = ("replicated", "ack_before_mirror", "no_dedup")


class ReplicatedPool:
    def __init__(self, guarantee: str = "replicated", every: int = 1000):
        if guarantee not in GUARANTEES:
            raise ValueError(f"unknown guarantee {guarantee!r}")
        self.guarantee = guarantee
        self.every = every           # how often the broken guarantee bites
        self.primary: deque = deque()  # the live pool, first in first out
        self.mirror: list = []       # (put_id, unit): what a death leaves
        self.seen: set = set()       # put ids the pool that is left knows
        self.alive = True
        self._puts = 0

    def put(self, unit: tuple, put_id: int, acknowledge: bool = True) -> bool:
        """Mirror, then store; the return value is the acknowledgement.
        ``acknowledge=False``: the primary dies with the acknowledgement
        (and, in the cell, with the producer's pipeline behind it)."""
        if not self.alive:  # after the promotion: the pool that is left
            if put_id in self.seen:
                return True  # absorbed: stored before the death
            if self.guarantee != "no_dedup":
                self.seen.add(put_id)
            self.primary.append(unit)
            return True
        self._puts += 1
        buffered = (self.guarantee == "ack_before_mirror"
                    and self._puts % self.every == 0)
        if not buffered:
            self.mirror.append((put_id, unit))
        self.primary.append(unit)
        return acknowledge

    def kill_primary(self) -> None:
        """The primary's process dies: its memory is gone."""
        self.primary.clear()
        self.alive = False

    def promote(self) -> int:
        """The buddy replays its mirror into its own pool; returns the
        units adopted."""
        for put_id, unit in self.mirror:
            self.primary.append(unit)
            if self.guarantee != "no_dedup":
                self.seen.add(put_id)
        return len(self.primary)

    def get(self):
        return self.primary.popleft() if self.primary else None


def deliveries(plan: np.ndarray, guarantee: str = "replicated",
               every: int = 1000, in_flight: int = 512,
               mirrored: int = 256) -> np.ndarray:
    """The cell's story on the plain pool: the first half of the plan put
    and acknowledged; ``in_flight`` more puts on their way when the primary
    dies, the first ``mirrored`` of them already at the buddy and none
    acknowledged; the promotion; the client's re-send of every
    unacknowledged put under its id; the rest of the plan put into the
    pool that is left; get until exhausted. ``(n, 3)`` int64 rows of
    ``(id, work_us, tag)`` in delivery order, as ``pool.deliveries``
    returns them."""
    pool = ReplicatedPool(guarantee, every)
    units = list(zip(plan["id"].tolist(), plan["work_us"].tolist(),
                     plan["tag"].tolist()))
    half = (len(units) + 1) // 2
    flying = range(half, min(half + in_flight, len(units)))
    for put_id in range(half):
        assert pool.put(units[put_id], put_id)
    for put_id in flying[:mirrored]:
        pool.put(units[put_id], put_id, acknowledge=False)
    pool.kill_primary()
    pool.promote()
    for put_id in flying:  # re-sent, whether the buddy had them or not
        assert pool.put(units[put_id], put_id)
    for put_id in range(flying.stop, len(units)):
        assert pool.put(units[put_id], put_id)
    out = []
    while (unit := pool.get()) is not None:
        out.append(unit)
    return np.asarray(out, dtype=np.int64).reshape(-1, 3)
