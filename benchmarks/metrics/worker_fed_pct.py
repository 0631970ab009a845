"""100 - ``worker_blocked_pct``: the share of the fleet's time in the window
that was NOT spent inside a fetch call. The same reading as the blocked
share, stated on the large side so that its relative spread is small: one
point of it is one point of the blocked share."""


def read(run):
    return 100.0 - run["window"].worker_blocked_pct
