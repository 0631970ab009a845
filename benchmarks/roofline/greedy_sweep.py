"""What one greedy assignment solve asks of a chip, counted from the
table's own sizes — the algorithm's reads, writes and eligibility tests,
the same whatever implements it — and the least time a chip needs for it.

The algorithm: order ``nt`` task slots by priority; then, task by task,
test the open requesters for eligibility and give the task to the first;
stop when ``pairs`` requesters have been matched.

- bytes: every input read once and the answer written once — task
  priority and type (4 + 4 bytes a slot), the requesters' type mask and
  valid flag (``ntypes`` + 1 bytes each), the assignment (4 bytes each).
- operations: the ordering, ``nt * ceil(log2 nt)`` comparisons, and the
  eligibility tests. The k-th matched task has to test the requesters
  still open, at least ``pairs - k`` of them, and each test is two
  integer operations (is it open and does it accept; is it the first):
  ``pairs * (pairs + 1)`` in all. That is the fewest the sweep can do
  (it assumes no task is tested in vain), so the share is not flattered.

Integer operations are held against the chip's int8 peak, the highest
integer rate it publishes; a sweep on 32-bit lanes cannot reach it, which
again keeps the share low rather than high.
"""

from __future__ import annotations

import json
import math
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_of(device_kind: str, path: str = PEAKS) -> dict:
    """The published peaks of a device kind. A device that is not in the
    table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; it has {sorted(table)}")
    return table[device_kind]


def work(nt: int, nr: int, ntypes: int, pairs: float) -> dict:
    """Bytes and integer operations of one solve."""
    sort_ops = nt * math.ceil(math.log2(max(nt, 2)))
    return {
        "bytes": nt * 8 + nr * (ntypes + 1) + nr * 4,
        "ops": sort_ops + pairs * (pairs + 1),
    }


def least_seconds(nt: int, nr: int, ntypes: int, pairs: float,
                  device_kind: str) -> tuple:
    """(seconds, bound): the least time the chip needs for one solve, and
    which of ``memory`` or ``compute`` sets it."""
    peaks, w = peaks_of(device_kind), work(nt, nr, ntypes, pairs)
    t_mem = w["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = w["ops"] / peaks["int8_ops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
