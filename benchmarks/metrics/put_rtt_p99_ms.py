"""99th percentile of what the producer's synchronous puts took
(``p0.puts``): with ``put_rtt_p50_ms`` it says whether a slow producer is
a slow median (the path's wake-ups) or a tail (the host's stalls). 70,000
puts a run leave 700 beyond it."""

import numpy as np


def read(run):
    put_s = run["logs"].put_s
    return float(np.percentile(put_s, 99)) * 1e3 if len(put_s) else None
