"""What the plane left of the servers' own counters
(``<scratch>/servers.json``: ``Server.finalize_stats()`` by rank), for the
readers that take the producer's home server, the hot one."""

from __future__ import annotations

import json
import os


def home(run: dict) -> dict | None:
    """The counters of the producer's home server (rank 0 produces, its
    home is the first server); None when the run left none."""
    root = os.path.dirname(run["bench_dir"])
    path = os.path.join(root, ".bench_scratch", run["cell"], "servers.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f).get(str(run["config"]["app_ranks"]))
