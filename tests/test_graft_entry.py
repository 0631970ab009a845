"""Regression tests for the driver entry points (``__graft_entry__``).

The driver's multi-chip gate imports ``__graft_entry__`` and calls
``dryrun_multichip(8)`` directly — these tests exercise exactly that path
so a green suite implies a green gate. It runs on the 8 virtual CPU
devices the conftest asks for; on fewer devices it raises.
"""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_assigns():
    fn, args = graft.entry()
    out = np.asarray(fn(*args))
    assert out.shape == (8 * 32,)  # one slot per requester
    assert (out >= 0).sum() > 0


def test_dryrun_multichip_8():
    # asserts internally: mesh solve pairs, type masks respected, and a
    # production engine round that plans both matches and migrations
    graft.dryrun_multichip(8)
