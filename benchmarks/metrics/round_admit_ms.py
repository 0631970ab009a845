"""Median ``adlb.round.admit`` in the traced window: everything a round
does up to its gate — the ledger's sync with the snapshots that changed,
the requester filter, the cross-feasibility and imbalance checks —
whether the round went on to plan or not."""

from benchmarks.reduce import hostspans


def read(run):
    return hostspans.median_ms(run, "adlb.round.admit")
