"""The comparison that decides ``correct``: what the timed path delivered,
against what the plain reference delivers for the same puts.

Every number is a count with the limit 0 — the guarantees are exact
(``configs/*.json`` "guarantees"), so the comparison is.
"""

from __future__ import annotations

import numpy as np

#: name -> limit, in the order they are printed
LIMITS = {
    "missing_units": 0,      # acknowledged, never delivered
    "duplicated_units": 0,   # deliveries beyond a unit's first
    "altered_units": 0,      # delivered, but not as it was put
    "unacked_puts": 0,       # planned puts the producer has no ack for
    "clients_failed": 0,     # exit code != 0: not ended by exhaustion
    "solve_mismatch": 0,     # device program vs plain greedy, in slots
}


def compare(expected: np.ndarray, logs, client_rcs, solve_mismatch: int,
            n_planned: int) -> dict:
    """``expected``: the reference's ``(id, work_us, tag)`` rows, ids
    distinct. ``logs``: ``records.Logs`` of the run. Returns name -> value
    for every name in ``LIMITS``."""
    exp = expected[np.argsort(expected[:, 0], kind="stable")]
    units = logs.units
    pos = np.clip(np.searchsorted(exp[:, 0], units["id"]), 0,
                  max(len(exp) - 1, 0))
    if len(exp):
        same = ((exp[pos, 0] == units["id"])
                & (exp[pos, 1] == units["work_us"])
                & (exp[pos, 2] == units["tag"]))
    else:
        same = np.zeros(len(units), dtype=bool)
    if logs.producer is not None:
        # t_end rides in every payload: one that differs was altered
        same &= units["t_end"] == logs.producer["t_end"]
    counts = np.bincount(pos[same], minlength=len(exp))
    acked = int(logs.producer["n_acked"]) if logs.producer is not None else 0
    return {
        "missing_units": int((counts == 0).sum()),
        "duplicated_units": int(np.clip(counts - 1, 0, None).sum()),
        "altered_units": int((~same).sum()),
        "unacked_puts": int(n_planned - acked),
        "clients_failed": int(sum(1 for rc in client_rcs if rc != 0)),
        "solve_mismatch": int(solve_mismatch),
    }


def verdict(numbers: dict) -> bool:
    return all(numbers[name] <= limit for name, limit in LIMITS.items())


def compared(numbers: dict) -> dict:
    """Each number beside its limit, for the result line and stderr."""
    return {name: {"value": numbers[name], "limit": limit}
            for name, limit in LIMITS.items()}
