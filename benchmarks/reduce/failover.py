"""What a failover plane left of the death (``<scratch>/failover.json``)
and, through it, the counters of the server that is hot in the window: in
such a cell the producer's home server is the dead one
(``reduce/servers.py::home`` names it), and its ring buddy, which adopted
its shard and its app ranks, takes every put, every consume and the
planner's load from the death on."""

from __future__ import annotations

import json
import math
import os


def _scratch(run: dict) -> str:
    root = os.path.dirname(run["bench_dir"])
    return os.path.join(root, ".bench_scratch", run["cell"])


def _load(run: dict, name: str):
    path = os.path.join(_scratch(run), name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def hot(run: dict) -> dict | None:
    """The promoted server's counters (``failover.json`` says which rank,
    ``servers.json`` holds them); None when the run left none."""
    death = _load(run, "failover.json")
    servers = _load(run, "servers.json")
    if death is None or servers is None:
        return None
    return servers.get(str(death.get("promoted")))


def window_seconds(run: dict):
    """The whole CLOCK_MONOTONIC seconds inside the window, as
    ``range``; None when there is none."""
    first = math.ceil(run["window"].t0)
    last = math.floor(run["window"].t_end)  # exclusive
    return range(first, last) if last > first else None
