"""The durable pool against its plain reference, across a real death of
the whole fleet: a forked world of 6 app ranks and 3 Python servers with
the write-ahead log on takes 600 seeded units, every process of it is
killed by SIGKILL, the same world shape restarts on the same ``wal_dir``
and drains. What comes out has to be what ``benchmarks/reference/
durable_pool.py`` gives after ``crash()`` / ``recover()``: every unit the
producer holds an acknowledgement for, nothing twice.

CPU, no chip: the planner stays on its numpy twin, as tier-1's forked
``balancer="tpu"`` worlds do. The kill, the reaping and the sweep of
``/dev/shm`` are the benchmark plane's own (``planes/python_wal.py``), so
they are tested on real processes here. Each world has a time limit of its
own (``LIMIT_S``).
"""

import dataclasses
import os
import shutil
import struct
import time
import types

import numpy as np
import pytest

from adlb_tpu.runtime import replica, wal as walmod
from adlb_tpu.runtime.messages import Tag, msg
from adlb_tpu.runtime.server import Server
from adlb_tpu.runtime.transport import InProcFabric
from adlb_tpu.runtime.transport_tcp import probe_free_ports
from adlb_tpu.runtime.world import Config, WorldSpec
from benchmarks.planes import python_wal as plane
from benchmarks.reduce import records
from benchmarks.reference import compare, durable_pool, pool
from benchmarks.spec import ROOT, Spec
from benchmarks.traffic import restart_app, window_app
from benchmarks.traffic.generate import make_plan

LIMIT_S = 30.0  # a world that has not ended by then fails its test
SECONDS = 0.19
SMALL = {
    "app_ranks": 6, "servers": 3, "types": [1], "work_us": 2000,
    "fetch_batch": 4, "warm_s": 8.0, "fed_warm_s": 0.05,
    "config": {"balancer": "tpu", "balancer_max_tasks": 2048,
               "balancer_max_requesters": 256, "balancer_mesh": "off",
               "exhaust_check_interval": 0.2,
               "on_worker_failure": "abort", "on_server_failure": "abort"},
}
HOT = SMALL["app_ranks"]  # rank 0's home server, the first one
ACKED = "acked.bin"       # the mid-flood producer's oracle: ids, as acked


def flood_slowly(plan_path: str, logdir: str):
    """World A for a kill in mid-flood: rank 0 puts a unit a millisecond,
    flushes every 25, and appends the ids of each acknowledged flush to
    ``ACKED`` at once (a write that has reached the OS outlives SIGKILL).
    It writes no ``p0.bin``: nobody waits for its end."""

    def ingest(ctx) -> int:
        from adlb_tpu.types import ADLB_SUCCESS

        if ctx.rank == 0:
            plan = np.fromfile(plan_path, dtype=records.PLAN)
            t_end = time.monotonic() + 3600.0
            with open(os.path.join(logdir, ACKED), "ab", buffering=0) as f:
                for at in range(0, len(plan), 25):
                    part = plan[at:at + 25]
                    for unit in part:
                        payload = window_app.PAYLOAD.pack(
                            int(unit["id"]), time.monotonic(), t_end,
                            int(unit["work_us"]), int(unit["tag"]))
                        assert ctx.iput(payload, window_app.TOKEN) == \
                            ADLB_SUCCESS
                        time.sleep(1e-3)
                    assert ctx.flush_puts() == ADLB_SUCCESS
                    f.write(part["id"].astype("<i8").tobytes())
        while True:
            time.sleep(1.0)

    return ingest


def acked_ids(logdir: str) -> np.ndarray:
    path = os.path.join(logdir, ACKED)
    if not os.path.exists(path):
        return np.zeros(0, dtype="<i8")
    return np.fromfile(path, dtype="<i8", count=os.path.getsize(path) // 8)


def tear_the_tail(wal_dir: str) -> int:
    """What a crash in mid-``write`` leaves behind the last whole record:
    a frame that promises 100 bytes and holds 37. Returns the log's size
    before it."""
    path = walmod.log_path(wal_dir, HOT)
    size = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 0x12345678, 100) + b"\x01" * 37)
    return size


def restart(tmp_path, fsync_ms: float, kill_point: str, torn: bool = False):
    """Both worlds. Returns the plan, the clients' logs, world B's exit
    codes and servers' stats, and what the plane says of the kill.

    A kill in mid-flood runs under ``balancer="steal"``: the planner's
    pump moves units between servers while the flood runs, and a unit on
    its way when the fleet dies is recovered by both (a re-execution;
    ``test_a_migrated_unit_is_in_some_log_at_every_instant``), so only a
    world that moves nothing can be held to "none twice" there. At the
    last acknowledgement the pump has long placed what it places."""
    scratch = str(tmp_path)
    logdir = os.path.join(scratch, "logs")
    os.makedirs(logdir)
    mix = Spec(ROOT).traffic("hotspot-py-n64-wal.restart")
    plan = make_plan(SMALL, mix, 2**31 + 35, SECONDS)
    assert len(plan) == 600
    plan_path = os.path.join(scratch, "plan.bin")
    plan.tofile(plan_path)
    ctx = types.SimpleNamespace(config=SMALL, mix=mix, seconds=SECONDS,
                                logdir=logdir, scratch=scratch, trace=False)
    wal_dir = os.path.join(scratch, "wal")

    def cfg(flight: str):
        return dataclasses.replace(
            plane.world_config(SMALL, mix, os.path.join(scratch, flight),
                               probe_free_ports(1)[0], wal_dir),
            wal_fsync_ms=fsync_ms,
            balancer="steal" if kill_point == "mid_flood" else "tpu")

    ingest, serve = restart_app.make_apps(
        plan_path, logdir, SMALL["warm_s"], SECONDS, SMALL["fetch_batch"], 64)
    done = plane.producer_done
    if kill_point == "mid_flood":
        ingest = flood_slowly(plan_path, logdir)

        def done(logdir):
            return len(acked_ids(logdir)) >= 200

    killed = plane.ingest_and_kill(ctx, ingest, cfg("flight-a"), LIMIT_S,
                                   done)
    # world A: every rank and the helper killed, reaped, nothing left
    assert killed["killed"] == SMALL["app_ranks"] + SMALL["servers"] + 1
    assert killed["shm_swept"] > 0
    assert not [name for name in os.listdir(plane.SHM_DIR)
                if name.startswith(killed["shm_key"])]
    assert killed["t_gone"] - killed["t_kill"] < 10.0
    assert killed["log_bytes_at_kill"][f"server.{HOT}.log"] > 0
    if torn:
        size = tear_the_tail(wal_dir)
    res = plane.launch(SMALL, serve, cfg("flight"), LIMIT_S)
    if torn:  # cut off at the last whole record, then written on
        path = walmod.log_path(wal_dir, HOT)
        whole, still_torn = walmod.scan_records(path)
        assert whole and not still_torn and os.path.getsize(path) > size
    rcs = [res.app_results.get(r, -1) for r in range(SMALL["app_ranks"])]
    return plan, records.read_logs(logdir), rcs, res.server_stats, killed


def rows(units) -> list:
    return sorted(zip(units["id"].tolist(), units["work_us"].tolist(),
                      units["tag"].tolist()))


@pytest.mark.parametrize("fsync_ms,torn", [(5.0, False), (0.0, False),
                                           (5.0, True)])
def test_killed_at_the_last_acknowledgement_every_unit_comes_back_once(
        tmp_path, fsync_ms, torn):
    plan, logs, rcs, stats, killed = restart(tmp_path, fsync_ms, "last_ack",
                                             torn)
    assert int(logs.producer["n_acked"]) == len(plan) == 600
    # the kill followed the producer's record at once
    assert 0.0 <= killed["t_kill"] - killed["t_p0_seen"] < 0.05
    want = durable_pool.deliveries(plan)  # put, crash, recover, drain
    assert rows(logs.units) == sorted(map(tuple, want.tolist()))
    # and by the comparison every run of the benchmark is judged by
    numbers = compare.compare(pool.deliveries(plan), logs, rcs, 0, len(plan))
    assert compare.verdict(numbers) is True
    assert all(numbers[name] == 0 for name in compare.LIMITS)
    # the restarted servers say what they adopted, and how
    assert sum(s["wal_recovered"] for s in stats.values()) == 600
    hot = stats[HOT]
    assert hot["wal_recovered"] > 0 and hot["wal_replayed"] >= 600
    assert hot["wal_recover_s"] > 0.0
    assert hot["wal_syncs"] > 0 and hot["wal_records"] >= hot["wal_syncs"]
    assert hot["wal_bytes"] > 8 * hot["wal_records"]  # framing alone is 8
    by_second = hot["wal_flush_by_second"]
    assert 0.0 < sum(by_second.values()) < hot["reactor_busy_s"]
    assert max(by_second.values()) <= 1.0 + 1e-9
    plane.check_recovery({str(r): s for r, s in stats.items()}, 600)


@pytest.mark.parametrize("fsync_ms,torn", [(5.0, False), (0.0, True)])
def test_killed_in_mid_flood_every_acknowledged_unit_comes_back_once(
        tmp_path, fsync_ms, torn):
    plan, logs, rcs, _stats, _killed = restart(tmp_path, fsync_ms,
                                               "mid_flood", torn)
    acked = acked_ids(os.path.join(str(tmp_path), "logs"))
    assert 200 <= len(acked) < len(plan)  # the kill landed in the flood
    assert rcs == [0] * SMALL["app_ranks"]
    got = logs.units["id"]
    assert len(np.unique(got)) == len(got)          # none twice
    assert set(acked.tolist()) <= set(got.tolist())  # none lost
    # what came back beyond them was put and logged, its acknowledgement
    # still held or on its way: units of the plan, as they were put
    put = {row[0]: row for row in rows(plan)}
    assert all(put.get(row[0]) == row for row in rows(logs.units))
    # the reference, told what the producer knows: those puts, that crash
    ref = durable_pool.DurablePool()
    for unit_id in acked.tolist():
        assert ref.put(put[unit_id])
    ref.crash(writing=(max(put) + 1, 0, 0))
    assert ref.recover() == len(acked)
    assert {ref.get() for _ in range(len(acked))} <= set(rows(logs.units))


class AcksFirst(walmod.WriteAheadLog):
    """The guarantee broken underneath: acknowledgements leave as soon as
    they are asked for, and the newest records wait in the process's
    memory for a later turn."""

    LAG = 60

    def _write_out(self) -> None:
        late = self._buf[-self.LAG:]
        del self._buf[-self.LAG:]
        super()._write_out()
        self._buf.extend(late)

    def tick(self, now: float, force: bool = False) -> list:
        early, self.pending_acks = self.pending_acks, []
        return early + super().tick(now, force)


def test_acknowledged_before_written_is_called_not_correct(tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(walmod, "WriteAheadLog", AcksFirst)
    plan, logs, rcs, _stats, _killed = restart(tmp_path, 5.0, "last_ack")
    assert int(logs.producer["n_acked"]) == 600  # every put acknowledged
    numbers = compare.compare(pool.deliveries(plan), logs, rcs, 0, len(plan))
    assert compare.verdict(numbers) is False
    assert numbers["missing_units"] > 0
    assert numbers["duplicated_units"] == numbers["altered_units"] == 0
    assert rows(logs.units) != sorted(
        map(tuple, durable_pool.deliveries(plan).tolist()))


# ------------------------------------- a unit on its way between two servers


def recovered_by(wal_dir, rank: int, world) -> int:
    """How many units a server restarted on a copy of ``wal_dir`` as it
    stands would adopt."""
    copy = str(wal_dir) + f".at-{time.monotonic_ns()}"
    shutil.copytree(wal_dir, copy)
    mirror = walmod.WriteAheadLog(copy, rank, world).recover()
    return 0 if mirror is None else len(mirror.units)


def test_a_migrated_unit_is_in_some_log_at_every_instant(tmp_path):
    """The planner moves units between durable servers; whenever the fleet
    dies, each is in the source's log, the destination's, or for a moment
    both (a re-execution, the crash-recovery contract) — never neither."""
    world = WorldSpec(nranks=4, nservers=2, types=(1,))
    fabric = InProcFabric(4)
    cfg = Config(wal_dir=str(tmp_path), wal_fsync_ms=10_000.0)
    src = Server(world, cfg, fabric.endpoint(2))
    dst = Server(world, cfg, fabric.endpoint(3))

    def frames(rank: int, tag) -> list:
        out = []
        while (m := fabric.endpoints[rank].recv(timeout=0.0)) is not None:
            if m.tag is tag:
                out.append(m)
        return out

    def removes() -> int:
        whole, _torn = walmod.scan_records(walmod.log_path(str(tmp_path), 2))
        return sum(op == replica.OP_REMOVE for op, _body in whole)

    for i in range(4):
        src._handle(msg(Tag.FA_PUT, 0, payload=b"unit-%d" % i, work_type=1,
                        prio=0, target_rank=-1, answer_rank=-1, common_len=0,
                        common_server=-1, common_seqno=-1, put_id=i))
    src._flush_wal(force=True)
    going = [u.seqno for u in src.wq.units()][:3]
    # 1. the source ships three: out of its wq, still in its log
    src._handle(msg(Tag.SS_PLAN_MIGRATE, 2, dest=3, seqnos=going, mig_id=1))
    src._flush_wal(force=True)
    assert src.wq.count == 1 and removes() == 0
    assert (recovered_by(tmp_path, 2, world),
            recovered_by(tmp_path, 3, world)) == (4, 0)
    # 2. the destination takes them in and holds its acknowledgement for
    # the commit that makes them durable
    (batch,) = frames(3, Tag.SS_MIGRATE_WORK)
    dst._handle(batch)
    assert dst.wq.count == 3 and frames(2, Tag.SS_MIGRATE_ACK) == []
    dst._flush_wal(force=True)
    (ack,) = frames(2, Tag.SS_MIGRATE_ACK)
    assert (recovered_by(tmp_path, 2, world),
            recovered_by(tmp_path, 3, world)) == (4, 3)  # three twice
    # 3. on the acknowledgement the source's log lets go of them
    src._handle(ack)
    src._flush_wal(force=True)
    assert removes() == 3 and src._migrate_moved == {}
    assert (recovered_by(tmp_path, 2, world),
            recovered_by(tmp_path, 3, world)) == (1, 3)
