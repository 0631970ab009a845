"""The binary records of a window run: what the traffic generator hands
the producer and what the clients log (``clients/window_client.c`` states
the same layouts in C). Little-endian, fixed width, no padding."""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

#: the generator's plan, one record per unit the producer puts
PLAN = np.dtype([("id", "<i8"), ("due_s", "<f8"), ("work_us", "<i4"),
                 ("tag", "<u4")])
#: the payload a unit carries on the wire
PAYLOAD = np.dtype([("id", "<i8"), ("t_put", "<f8"), ("t_end", "<f8"),
                    ("work_us", "<i4"), ("tag", "<u4")])
#: one fetch call of a worker
FETCH = np.dtype([("t_call", "<f8"), ("t_ret", "<f8"), ("n_got", "<i4"),
                  ("rc", "<i4")])
#: one delivered unit: the payload as received, the fetch call that
#: brought it, and when its work was done
UNIT = np.dtype([("id", "<i8"), ("t_put", "<f8"), ("t_end", "<f8"),
                 ("work_us", "<i4"), ("tag", "<u4"), ("t_call", "<f8"),
                 ("t_ret", "<f8"), ("t_done", "<f8")])
#: the producer's one record
PRODUCER = np.dtype([("n_acked", "<i8"), ("t_first", "<f8"),
                     ("t_last", "<f8"), ("t_end", "<f8")])

#: what each acknowledged put of a synchronous producer took, in put order
#: (``p0.puts``; a pipelined producer writes none)
PUT_S = np.dtype("<f8")

assert (PLAN.itemsize, PAYLOAD.itemsize, FETCH.itemsize, UNIT.itemsize,
        PRODUCER.itemsize) == (24, 32, 24, 56, 32)

_WORKER = re.compile(r"w(\d+)\.units$")


@dataclasses.dataclass
class Logs:
    """Everything the clients of one run logged."""

    producer: object          # PRODUCER scalar record, or None
    units: np.ndarray         # UNIT records, every worker's
    unit_rank: np.ndarray     # the worker that logged each unit
    fetches: np.ndarray       # FETCH records
    fetch_rank: np.ndarray    # the worker that logged each fetch
    put_s: np.ndarray         # PUT_S records; none from a pipelined producer


def read_logs(logdir: str) -> Logs:
    """Read a run's log directory. A missing producer record (it died
    before its last put was acknowledged) reads as ``None``, a missing
    ``p0.puts`` (a pipelined producer) as no put times."""
    ppath = os.path.join(logdir, "p0.bin")
    producer = None
    if os.path.exists(ppath) and os.path.getsize(ppath) == PRODUCER.itemsize:
        producer = np.fromfile(ppath, dtype=PRODUCER)[0]
    units, unit_rank, fetches, fetch_rank = [], [], [], []
    for upath in sorted(glob.glob(os.path.join(logdir, "w*.units"))):
        rank = int(_WORKER.search(upath).group(1))
        u = _whole_records(upath, UNIT)
        units.append(u)
        unit_rank.append(np.full(len(u), rank, dtype=np.int32))
        f = _whole_records(upath[: -len("units")] + "fetch", FETCH)
        fetches.append(f)
        fetch_rank.append(np.full(len(f), rank, dtype=np.int32))

    def cat(parts, dtype):
        return np.concatenate(parts) if parts else np.zeros(0, dtype=dtype)

    return Logs(producer, cat(units, UNIT), cat(unit_rank, np.int32),
                cat(fetches, FETCH), cat(fetch_rank, np.int32),
                _whole_records(os.path.join(logdir, "p0.puts"), PUT_S))


def _whole_records(path: str, dtype: np.dtype) -> np.ndarray:
    """A file's whole records (a killed client can leave a torn tail); a
    file that is not there has none."""
    if not os.path.exists(path):
        return np.zeros(0, dtype=dtype)
    n = os.path.getsize(path) // dtype.itemsize
    return np.fromfile(path, dtype=dtype, count=n)


def write_worker_log(logdir: str, rank: int, units: np.ndarray,
                     fetches: np.ndarray) -> None:
    """What one worker would have logged — for the reference pool, the
    control and the tests, which stand in for the clients."""
    np.asarray(units, dtype=UNIT).tofile(
        os.path.join(logdir, f"w{rank}.units"))
    np.asarray(fetches, dtype=FETCH).tofile(
        os.path.join(logdir, f"w{rank}.fetch"))


def write_producer_log(logdir: str, n_acked: int, t_first: float,
                       t_last: float, t_end: float, put_s=None) -> None:
    """The producer's record and, where ``put_s`` is given, what each put
    of a synchronous producer took (``p0.puts``)."""
    if put_s is not None:
        np.asarray(put_s, dtype=PUT_S).tofile(os.path.join(logdir, "p0.puts"))
    rec = np.zeros(1, dtype=PRODUCER)
    rec[0] = (n_acked, t_first, t_last, t_end)
    rec.tofile(os.path.join(logdir, "p0.bin"))
