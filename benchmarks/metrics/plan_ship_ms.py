"""Median ``adlb.sidecar.ship`` in the traced window: the sends of one
round's ``SS_PLAN_MATCH`` and ``SS_PLAN_MIGRATE`` frames. The program
opens the span only for a round that ships something."""

from benchmarks.reduce import hostspans


def read(run):
    return hostspans.median_ms(run, "adlb.sidecar.ship")
