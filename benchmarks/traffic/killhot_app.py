"""The ``app_fn`` of the mix ``killhot`` (``traffic/killhot.json``): the
producer and the workers of ``window_app``, unchanged, around the death of
the producer's home server in mid-flood.

Rank 0 runs ``window_app.produce`` as it is, handed a thin proxy of its
``ctx`` that passes ``iput`` and ``put`` through and wraps ``flush_puts``:
it counts the puts each flush settled, and when the acknowledged count
first reaches half the plan it writes the marker ``<logdir>/p0.half`` (the
time and the count), which is what the plane's kill waits for. So the kill
follows the producer's progress and not a clock. Every flush's
``(t_call, t_ret, n)`` is kept in memory and written to
``<logdir>/p0.flushes`` once, at the end. Every other rank waits for the
marker ``<logdir>/killed``, which the plane writes once the killed process
is gone, and then runs ``window_app.consume`` unchanged: nothing is fetched
before the death. A worker that has not seen the marker after
``kill_wait_s`` gives up with exit code 7, so a run whose kill failed ends.
"""

from __future__ import annotations

import os
import struct
import time

import numpy as np

from benchmarks.reduce import records
from benchmarks.traffic import window_app

#: one ``flush_puts`` of the producer: when it was called, when it
#: returned, and how many puts it settled (``p0.flushes``)
FLUSH = np.dtype([("t_call", "<f8"), ("t_ret", "<f8"), ("n", "<i8")])
#: the marker ``p0.half``: when it was written and the acknowledged count
HALF = struct.Struct("<dq")
KILLED_POLL_S = 0.01


class CountingCtx:
    """``ctx`` as ``window_app.produce`` uses it, with every flush timed."""

    def __init__(self, ctx, logdir: str, n_planned: int):
        self._ctx, self._logdir = ctx, logdir
        self._half = (n_planned + 1) // 2
        self._pending = self.acked = 0
        self._marked = False
        self.flushes: list = []
        self.rank = ctx.rank
        self.put = ctx.put

    def iput(self, payload, work_type):
        self._pending += 1
        return self._ctx.iput(payload, work_type)

    def flush_puts(self):
        from adlb_tpu.types import ADLB_SUCCESS

        t_call = time.monotonic()
        rc = self._ctx.flush_puts()
        t_ret = time.monotonic()
        settled, self._pending = self._pending, 0
        self.flushes.append((t_call, t_ret, settled))
        if rc == ADLB_SUCCESS:
            self.acked += settled
            if not self._marked and self.acked >= self._half:
                self._marked = True
                with open(os.path.join(self._logdir, "p0.half.tmp"),
                          "wb") as f:
                    f.write(HALF.pack(t_ret, self.acked))
                os.replace(os.path.join(self._logdir, "p0.half.tmp"),
                           os.path.join(self._logdir, "p0.half"))
        return rc

    def write(self) -> None:
        np.asarray(self.flushes, dtype=FLUSH).tofile(
            os.path.join(self._logdir, "p0.flushes"))


def read_flushes(logdir: str) -> np.ndarray:
    path = os.path.join(logdir, "p0.flushes")
    if not os.path.exists(path):
        return np.zeros(0, dtype=FLUSH)
    return np.fromfile(path, dtype=FLUSH)


def read_half(logdir: str):
    """``(t, n_acked)`` of the marker, or None while it is not there."""
    path = os.path.join(logdir, "p0.half")
    if not os.path.exists(path):
        return None
    with open(path, "rb") as f:
        return HALF.unpack(f.read(HALF.size))


def make_app(plan_path: str, logdir: str, warm_s: float, seconds: float,
             fetch_batch: int, flush_every: int, kill_wait_s: float = 60.0):
    killed = os.path.join(logdir, "killed")

    def app(ctx) -> int:
        if ctx.rank == 0:
            n_planned = os.path.getsize(plan_path) // records.PLAN.itemsize
            proxy = CountingCtx(ctx, logdir, n_planned)
            try:
                return window_app.produce(proxy, plan_path, logdir, warm_s,
                                          seconds, flush_every)
            finally:
                proxy.write()
        give_up = time.monotonic() + kill_wait_s
        while not os.path.exists(killed):
            if time.monotonic() >= give_up:
                return 7
            time.sleep(KILLED_POLL_S)
        return window_app.consume(ctx, logdir, fetch_batch)

    return app
