"""95th percentile, over every unit delivered in the window, of
``t_delivered - max(t_put, t_fetch_call_started)``: how long a unit and a
willing worker both existed and were not yet matched."""


def read(run):
    return run["window"].match_wait_p95_ms
