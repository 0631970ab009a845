"""Summed time the workers spent inside a fetch call, clipped to the
window, over workers x seconds."""


def read(run):
    return run["window"].worker_blocked_pct
