"""Median of what the producer's synchronous puts took, each by the
producer's own clock around the blocking call (``p0.puts``): the round
trip itself, where ``flood_puts_per_s`` is its mean turned over. A
pipelined producer logs none and the metric is left out."""

import numpy as np


def read(run):
    put_s = run["logs"].put_s
    return float(np.median(put_s)) * 1e3 if len(put_s) else None
