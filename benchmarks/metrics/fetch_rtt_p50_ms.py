"""Median duration of the fetch calls that returned work in the window."""

import numpy as np


def read(run):
    fetch_s = run["window"].fetch_s
    return float(np.median(fetch_s)) * 1e3 if len(fetch_s) else None
