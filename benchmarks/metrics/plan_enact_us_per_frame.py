"""Microseconds of a daemon's reactor a frame that enacts a plan
(``SS_PLAN_MATCH``, ``SS_PLAN_MIGRATE``, ``SS_MIGRATE_WORK``,
``SS_MIGRATE_ACK``, ``SS_RFR``, ``SS_RFR_RESP``): the handlers' self time
over their count, all daemons, in the whole seconds inside the window,
from ``by_second`` of the flight artefacts."""

from benchmarks.reduce import daemons


def read(run):
    red = daemons.analyse(run)
    return daemons.per_frame_us(red and red["all_window"], "enact")
