"""The two ``app_fn``s of a restart mix (``traffic/restart.json``): the
same producer and the same workers as ``window_app``, parted by the death
of the fleet.

World A ingests: rank 0 floods the plan with ``window_app.produce`` as it
is — its first put fixes ``t_end``, which rides in every payload, and
``p0.bin`` after its last acknowledgement is what the plane waits for —
and then sleeps; every other rank sleeps. Nothing is fetched, so nothing
is delivered before the fleet is killed. World B serves: rank 0 returns at
once, every other rank runs ``window_app.consume`` unchanged until the
recovered pool is exhausted. ``CLOCK_MONOTONIC`` is system-wide and
outlives the restart, so the window ``[t_end − seconds, t_end]`` is read
from world B's logs as in every cell.
"""

from __future__ import annotations

import time

from benchmarks.traffic import window_app


def make_apps(plan_path: str, logdir: str, warm_s: float, seconds: float,
              fetch_batch: int, flush_every: int):
    """``(ingest, serve)``: the ``app_fn`` of world A and of world B."""

    def ingest(ctx) -> int:
        if ctx.rank == 0:
            rc = window_app.produce(ctx, plan_path, logdir, warm_s, seconds,
                                    flush_every)
            if rc != 0:
                return rc
        while True:  # until the fleet is killed
            time.sleep(1.0)

    def serve(ctx) -> int:
        if ctx.rank == 0:
            return 0
        return window_app.consume(ctx, logdir, fetch_batch)

    return ingest, serve
