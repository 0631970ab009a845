"""The plain reference of the planner's solve: greedy assignment written
straight from its definition, independent of ``adlb_tpu``. Tasks in
descending priority (ties: lower index first) each take the lowest-index
requester that is valid, still open and accepts the task's type."""

from __future__ import annotations

import numpy as np


def greedy_assign(task_prio, task_type, req_mask, req_valid, pad_prio):
    """Returns ``assign[NR]``: the task index given to each requester, -1
    for none. ``pad_prio`` marks padding slots of the fixed-shape table."""
    task_prio = np.asarray(task_prio)
    task_type = np.asarray(task_type)
    req_mask = np.asarray(req_mask, dtype=bool)
    open_req = np.asarray(req_valid, dtype=bool).copy()
    assign = np.full(req_mask.shape[0], -1, dtype=np.int32)
    order = np.argsort(-task_prio.astype(np.int64), kind="stable")
    left = int((open_req & req_mask.any(axis=1)).sum())
    for t in order.tolist():
        if left == 0:
            break
        tt = int(task_type[t])
        if task_prio[t] == pad_prio or tt < 0:
            continue
        ok = np.flatnonzero(open_req & req_mask[:, tt])
        if ok.size == 0:
            continue
        assign[ok[0]] = t
        open_req[ok[0]] = False
        left -= 1
    return assign


def seeded_snapshot(seed: int, nt: int, nr: int, ntypes: int, pad_prio: int):
    """A table at the world's own shape: a fifth of the task slots padding,
    four fifths of the requester slots parked, priorities that tie often."""
    rng = np.random.default_rng([seed & ((1 << 64) - 1), nt, nr])
    task_prio = rng.integers(-100, 100, size=nt).astype(np.int32)
    task_type = rng.integers(0, ntypes, size=nt).astype(np.int32)
    pad = rng.random(nt) < 0.2
    task_prio[pad] = pad_prio
    task_type[pad] = -1
    req_mask = rng.random((nr, ntypes)) < (1.0 if ntypes == 1 else 0.5)
    req_valid = rng.random(nr) < 0.8
    return task_prio, task_type, req_mask, req_valid
