"""p95 of how long a parked reserve that the planner fed waited inside
its daemon: from the park (``FA_RESERVE`` found nothing) to the answer,
for the waits that a plan's ``SS_RFR_RESP`` (cause ``plan``) or a unit
that ``SS_MIGRATE_WORK`` brought (cause ``migrated``) ended. All
daemons, merged, over the **whole world** (warm phase and drain included,
not the window: a histogram has no clock), from ``park_wait_s`` of the
flight artefacts; sqrt(2) buckets, interpolated by ``quantile_of``."""

from benchmarks.reduce import daemons


def read(run):
    return daemons.fed_wait_ms(daemons.analyse(run), 0.95)
