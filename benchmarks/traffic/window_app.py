"""The benchmark's Python traffic client: the twin of
``clients/window_client.c`` as an ``app_fn`` for ``spawn_world``, through
the library's own Python API (``ctx.iput`` / ``ctx.flush_puts`` /
``ctx.put``, ``ctx.get_work_batch`` / ``ctx.get_work``).

Rank 0 reads the unit plan (``records.PLAN``) and puts one unit per
record, each no earlier than its due offset, synchronously or pipelined
and acknowledged every ``flush_every`` puts, as the mix says. At its
first put it fixes ``t_end = now + warm_s + seconds`` and writes it into
every payload (``records.PAYLOAD``, 32 bytes). Every other rank fetches
until the pool is exhausted; a unit costs ``sleep(work_us)`` only while
``now < t_end``. Nothing is killed or signalled.

Each rank logs to files of its own under ``logdir``, in the records
``reduce/records.py`` reads (``p0.start``, ``p0.bin``, ``w<rank>.fetch``,
``w<rank>.units``, and in a synchronous mix ``p0.puts``), buffered. Times
are ``time.monotonic()``, which is CLOCK_MONOTONIC on Linux, system-wide.
A rank returns what the C client exits with: 0 only when every put was
acknowledged (producer) or the last fetch said the pool is exhausted
(worker).
"""

from __future__ import annotations

import os
import struct
import time

import numpy as np

from benchmarks.reduce import records

TOKEN = 1
PAYLOAD = struct.Struct("<qddiI")
FETCH = struct.Struct("<ddii")
UNIT = struct.Struct("<qddiIddd")
PRODUCER = struct.Struct("<qddd")
assert (PAYLOAD.size, FETCH.size, UNIT.size, PRODUCER.size) == (
    records.PAYLOAD.itemsize, records.FETCH.itemsize, records.UNIT.itemsize,
    records.PRODUCER.itemsize)

_BUFFER = 1 << 20


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 1e-3))


def produce(ctx, plan_path: str, logdir: str, warm_s: float, seconds: float,
            flush_every: int) -> int:
    from adlb_tpu.types import ADLB_SUCCESS

    plan = np.fromfile(plan_path, dtype=records.PLAN)
    ids, dues = plan["id"].tolist(), plan["due_s"].tolist()
    works, tags = plan["work_us"].tolist(), plan["tag"].tolist()
    mono, pack = time.monotonic, PAYLOAD.pack
    t_first = t_last = mono()
    t_end = t_first + warm_s + seconds
    # the window's place in time, for whoever wants to trace inside it
    with open(os.path.join(logdir, "p0.start"), "wb") as f:
        f.write(struct.pack("<dd", t_first, t_end))
    acked = in_flight = 0
    put_s = []  # what each synchronous put took; stays empty when pipelined
    last = len(ids) - 1
    for i, unit_id in enumerate(ids):
        if dues[i] > 0:
            _sleep_until(t_first + dues[i])
        payload = pack(unit_id, mono(), t_end, works[i], tags[i])
        if flush_every > 0:
            # pipelined: the acknowledgements settle at the flush
            rc = ctx.iput(payload, TOKEN)
            in_flight += 1
            if rc == ADLB_SUCCESS and (in_flight == flush_every or i == last):
                rc = ctx.flush_puts()
                if rc == ADLB_SUCCESS:
                    acked += in_flight
                in_flight = 0
        else:
            t_put = mono()
            rc = ctx.put(payload, TOKEN)
            if rc == ADLB_SUCCESS:
                put_s.append(mono() - t_put)
                acked += 1
        if rc != ADLB_SUCCESS:
            return 3
        t_last = mono()
    if flush_every <= 0:
        np.asarray(put_s, dtype=records.PUT_S).tofile(
            os.path.join(logdir, "p0.puts"))
    with open(os.path.join(logdir, "p0.bin"), "wb") as f:
        f.write(PRODUCER.pack(acked, t_first, t_last, t_end))
    return 0


def consume(ctx, logdir: str, batch: int) -> int:
    from adlb_tpu.types import (ADLB_DONE_BY_EXHAUSTION, ADLB_NO_MORE_WORK,
                                ADLB_SUCCESS)

    mono, sleep = time.monotonic, time.sleep
    types = [TOKEN]
    with open(os.path.join(logdir, f"w{ctx.rank}.fetch"), "wb",
              buffering=_BUFFER) as ff, \
            open(os.path.join(logdir, f"w{ctx.rank}.units"), "wb",
                 buffering=_BUFFER) as uf:
        while True:
            t_call = mono()
            if batch > 1:
                rc, got = ctx.get_work_batch(types, max_units=batch)
            else:
                rc, one = ctx.get_work(types)
                got = [one]
            t_ret = mono()
            if rc != ADLB_SUCCESS:
                ff.write(FETCH.pack(t_call, t_ret, 0, rc))
                break  # NO_MORE_WORK / DONE_BY_EXHAUSTION
            ff.write(FETCH.pack(t_call, t_ret, len(got), rc))
            for work in got:
                payload = work.payload
                if len(payload) == PAYLOAD.size:
                    unit = PAYLOAD.unpack(payload)
                else:  # a payload of another length is an altered one
                    unit = (-1, 0.0, 0.0, 0, 0)
                if unit[3] > 0 and mono() < unit[2]:
                    sleep(unit[3] * 1e-6)
                uf.write(UNIT.pack(*unit, t_call, t_ret, mono()))
    return 0 if rc in (ADLB_DONE_BY_EXHAUSTION, ADLB_NO_MORE_WORK) else 6


def make_app(plan_path: str, logdir: str, warm_s: float, seconds: float,
             fetch_batch: int, flush_every: int):
    """The ``app_fn``: rank 0 produces, every other rank consumes."""

    def app(ctx) -> int:
        if ctx.rank == 0:
            return produce(ctx, plan_path, logdir, warm_s, seconds,
                           flush_every)
        return consume(ctx, logdir, fetch_batch)

    return app
