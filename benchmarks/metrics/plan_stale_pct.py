"""Share of the plan entries the daemons received (an ``SS_PLAN_MATCH``
is one, an ``SS_PLAN_MIGRATE`` one a unit) that named a unit no longer
there to give: 100 x ``plan_stale`` / ``plan_entries``, all daemons, over
the **whole world**, from the flight artefacts. The planner's useful
outcomes to attempts."""

from benchmarks.reduce import daemons


def read(run):
    red = daemons.analyse(run)
    if red is None or not red["plan_entries"]:
        return None
    return 100.0 * red["plan_stale"] / red["plan_entries"]
