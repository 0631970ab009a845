"""Median ``adlb.master.ship`` in the traced window: the in-server
planner's sends of one round's ``SS_PLAN_MATCH`` and ``SS_PLAN_MIGRATE``
frames (with the fetch flags it looks up for the matches). The program
opens the span only for a round that ships something."""

from benchmarks.reduce import hostspans


def read(run):
    return hostspans.median_ms(run, "adlb.master.ship")
