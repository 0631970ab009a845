"""Share of the window in which the reactor thread of the producer's home
server (the hot one: every put enters it) was not asleep in its one
blocking ``recv`` a turn. What ``recv`` does awake counts as busy: on the
shm fabric the ring scan and the frame decode. From the server's own
counter, ``reactor_busy_by_second`` of ``Server.finalize_stats()`` (busy
seconds by CLOCK_MONOTONIC second, a turn split where it straddles one),
over the whole seconds that lie inside the window; the plane leaves the
counters in ``<scratch>/servers.json``."""

import json
import math
import os


def read(run):
    root = os.path.dirname(run["bench_dir"])
    path = os.path.join(root, ".bench_scratch", run["cell"], "servers.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        servers = json.load(f)
    home = run["config"]["app_ranks"]  # rank 0 produces; its home is server 0
    by_second = servers.get(str(home), {}).get("reactor_busy_by_second")
    if not by_second:
        return None
    first = math.ceil(run["window"].t0)
    last = math.floor(run["window"].t_end)  # exclusive
    if last <= first:
        return None
    busy = sum(by_second.get(str(sec), 0.0) for sec in range(first, last))
    return 100.0 * busy / (last - first)
