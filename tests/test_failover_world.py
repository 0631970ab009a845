"""The replicated pool against its plain reference, across a real death of
the master alone in mid-flood: a forked world of 6 app ranks and 3 Python
servers with ``on_server_failure="failover"`` takes 600 seeded units; when
the producer holds acknowledgements for half of them the benchmark plane
(``benchmarks/planes/python_failover.py``) kills the one OS process of the
producer's home server, which is the master; its ring buddy promotes from
its mirror, the producer re-sends what was in flight and puts the rest,
and five workers, which start at the death, drain the pool. What comes out
has to be what ``benchmarks/reference/replicated_pool.py`` gives: every
unit of the plan, nothing twice.

CPU, no chip: the planner stays on its numpy twin, as tier-1's forked
``balancer="tpu"`` worlds do. The kill, the reaping, the refusals and the
death's numbers are the plane's own, so they are tested on real processes
here; the counters and spans this deployment reads are tested on servers
in one process at the end. Each world has a time limit of its own
(``LIMIT_S``).
"""

import dataclasses
import json
import os
import time
import types

import numpy as np
import pytest

from adlb_tpu.runtime.messages import Tag, msg
from adlb_tpu.runtime.server import Server
from adlb_tpu.runtime.transport import InProcFabric
from adlb_tpu.runtime.transport_tcp import probe_free_ports
from adlb_tpu.runtime.world import Config, WorldSpec
from benchmarks.planes import python_failover as plane
from benchmarks.reduce import records
from benchmarks.reference import compare, pool, replicated_pool
from benchmarks.spec import ROOT, Spec
from benchmarks.traffic import killhot_app
from benchmarks.traffic.generate import make_plan

LIMIT_S = 60.0  # a world that has not ended by then fails its test
SECONDS = 0.19
SMALL = {
    "app_ranks": 6, "servers": 3, "types": [1], "work_us": 2000,
    "fetch_batch": 4, "warm_s": 8.0, "fed_warm_s": 0.05,
    "config": {"balancer": "tpu", "balancer_max_tasks": 2048,
               "balancer_max_requesters": 256, "balancer_mesh": "off",
               "exhaust_check_interval": 0.2,
               "on_worker_failure": "abort",
               "on_server_failure": "failover"},
}
DEAD, BUDDY = SMALL["app_ranks"], SMALL["app_ranks"] + 1


def kill_the_master(tmp_path, balancer: str = "tpu", flush_every: int = 64,
                    client_wait: float | None = None, late_s: float = 0.0):
    """One world. Returns the plan, the clients' logs, the exit codes, the
    ``WorldResult``, the plane's killer and what the plane says of the
    death. The producer puts a unit a millisecond (the plan's due times),
    so that the kill, within 50 ms of the half, lands in the flood. With
    ``late_s`` rank 3, which is homed with the master, makes its first
    call that long after the death."""
    scratch = str(tmp_path)
    logdir = os.path.join(scratch, "logs")
    os.makedirs(logdir)
    ops_dir = os.path.join(scratch, "ops")
    os.makedirs(ops_dir)
    mix = Spec(ROOT).traffic("hotspot-py-n64-failover.killhot")
    plan = make_plan(SMALL, mix, 2**31 + 37, SECONDS)
    assert len(plan) == 600
    plan["due_s"] = np.arange(len(plan)) * 1e-3
    plan_path = os.path.join(scratch, "plan.bin")
    plan.tofile(plan_path)
    config = dict(SMALL, config=dict(SMALL["config"], balancer=balancer))
    cfg = plane.world_config(config, mix, os.path.join(scratch, "flight"),
                             probe_free_ports(1)[0], ops_dir)
    if client_wait is not None:
        cfg = dataclasses.replace(cfg, failover_client_wait=client_wait)
    mixed = killhot_app.make_app(plan_path, logdir, SMALL["warm_s"], SECONDS,
                                 SMALL["fetch_batch"], flush_every,
                                 kill_wait_s=LIMIT_S / 2)

    def app(ctx) -> int:
        if late_s and ctx.rank == 3:
            while not os.path.exists(os.path.join(logdir, "killed")):
                time.sleep(0.01)
            time.sleep(late_s)
        return mixed(ctx)

    killer = plane.Killer(logdir, DEAD, half_wait_s=LIMIT_S / 2)
    killer.start()
    try:
        res = plane.launch(config, app, cfg, LIMIT_S)
    finally:
        killer.stop.set()
        killer.join(10.0)
    got = plane.collect(config, res)
    logs = records.read_logs(logdir)
    promoted = plane.check_failover(config, killer, res, got["servers"],
                                    got["facts"], logs)
    ctx = types.SimpleNamespace(config=config, mix=mix, seconds=SECONDS,
                                logdir=logdir, scratch=scratch)
    death = plane.failover_numbers(ctx, killer, promoted, got["servers"],
                                   res, logs)
    death["flight"] = plane.read_flight(os.path.join(scratch, "flight"),
                                        promoted)
    with open(os.path.join(ops_dir, "ops_endpoint.json")) as f:
        death["ops_endpoint"] = json.load(f)
    return plan, logs, got, res, killer, death


def rows(units) -> list:
    return sorted(zip(units["id"].tolist(), units["work_us"].tolist(),
                      units["tag"].tolist()))


@pytest.mark.parametrize("balancer", ["tpu", "steal"])
def test_the_master_killed_in_mid_flood_every_unit_comes_out_once(
        tmp_path, balancer):
    plan, logs, got, res, killer, death = kill_the_master(tmp_path, balancer)
    # one process was killed, the master's, inside the flood, and reaped
    assert killer.exitcode == -9 and res.server_casualties == [DEAD]
    assert 0.0 <= death["kill_after_half_s"] < 0.05
    assert 300 <= death["n_acked_at_kill"] < 600
    assert not killer.flood_done_at_kill
    assert sorted(res.server_stats) == [BUDDY, BUDDY + 1]
    # every put acknowledged, every client ended as it should
    assert int(logs.producer["n_acked"]) == 600
    assert got["client_rcs"] == [0] * SMALL["app_ranks"]
    # nothing was delivered before the death
    assert (logs.units["t_ret"] >= killer.t_kill).all()
    # the reference, put through the same story
    want = replicated_pool.deliveries(plan, in_flight=64, mirrored=32)
    assert rows(logs.units) == sorted(map(tuple, want.tolist()))
    # and by the comparison every run of the benchmark is judged by
    numbers = compare.compare(pool.deliveries(plan), logs,
                              got["client_rcs"], 0, len(plan))
    assert compare.verdict(numbers) is True
    assert all(numbers[name] == 0 for name in compare.LIMITS)
    # the promoted server says what it did: one promotion, by the ring
    # buddy, which is the master afterwards
    hot = got["servers"][str(BUDDY)]
    assert death["promoted"] == BUDDY and hot["num_failovers"] == 1.0
    assert hot["failover_lost"] == 0.0
    assert 0.0 < death["master_promote_ms"] <= death["promote_ms"]
    assert "solver" in res.server_stats[BUDDY]
    assert death["ops_endpoint"]["master"] == BUDDY
    assert death["ops_endpoint"]["epoch"] >= 2
    # it adopted what the producer held acknowledgements for, less what
    # the first master's pump had placed on the two others (up to 32
    # each), and at most the pipeline beyond
    acked = death["n_acked_at_kill"]
    assert acked - 2 * 32 <= death["adopted"] <= acked + 2 * 64
    assert 0 <= death["deduped_puts"] <= death["resent_puts"] <= 64
    assert death["adopted"] + death["resent_puts"] \
        - death["deduped_puts"] <= 600
    # the stream's account: the buddy applied what the dead master sent,
    # and sends its own from the promotion on (the adopted shard re-logged)
    assert hot["repl_applied"] >= death["adopted"]
    assert hot["repl_entries"] >= death["adopted"]
    assert hot["repl_frames"] > 0 and hot["repl_bytes"] > 0
    assert 0.0 < hot["repl_flush_s"] < hot["reactor_busy_s"]
    assert sum(hot["repl_flush_by_second"].values()) == pytest.approx(
        hot["repl_flush_s"])
    third = got["servers"][str(BUDDY + 1)]
    assert third["repl_applied"] == hot["repl_entries"]  # its new mirror
    assert third["failover_adopted"] == 0
    # the producer felt the death as one long flush, and not for long
    assert 0.0 < death["producer_stall_s"] < 10.0
    assert death["puts_after_per_s"] > 0
    # the promoted master wrote its registry at a normal end, as the
    # first master would have, with the promotion's spans in it
    spans = {k for k in death["flight"]["metrics"]["histograms"]
             if k.startswith("span_s")}
    assert {"span_s{name=adlb.failover.promote}",
            "span_s{name=adlb.failover.promote_master}",
            "span_s{name=adlb.repl.flush}"} <= spans


def test_a_worker_whose_home_died_before_its_first_call_is_rehomed_at_once(
        tmp_path):
    """Rank 3 is homed with the master and makes its first call after the
    death: it has to learn its new home in the time the promotion takes,
    not after its reconnects have timed out."""
    _plan, logs, _got, _res, killer, death = kill_the_master(tmp_path)
    first = logs.fetches[logs.fetch_rank == 3]["t_ret"].min()
    assert first - killer.t_kill < 5.0  # 60-75 s before this PR
    assert death["first_remote_s"] < 5.0


def test_a_first_call_after_the_clients_window_has_closed_still_finds_home(
        tmp_path):
    """The promoted server re-announces the takeover for
    ``failover_client_wait`` seconds. Rank 3 sleeps through all of that:
    the note it was sent at the promotion waits in its queue, and its
    first call, to a home that refuses the connection, applies it."""
    plan, logs, got, _res, killer, _death = kill_the_master(
        tmp_path, client_wait=1.0, late_s=3.0)
    mine = logs.fetches[logs.fetch_rank == 3]
    assert len(mine) and mine["t_call"].min() - killer.t_kill >= 3.0
    assert mine["t_ret"].min() - mine["t_call"].min() < 2.0
    assert got["client_rcs"] == [0] * SMALL["app_ranks"]
    numbers = compare.compare(pool.deliveries(plan), logs,
                              got["client_rcs"], 0, len(plan))
    assert all(numbers[name] == 0 for name in compare.LIMITS)


# ------------------- the counters and spans, on servers in one process


def _world():
    return WorldSpec(nranks=5, nservers=3, types=(1,))


def _put(server, src: int, put_id: int, **more):
    server._handle(msg(Tag.FA_PUT, src, payload=b"unit-%d" % put_id,
                       work_type=1, prio=0, target_rank=-1, answer_rank=-1,
                       common_len=0, common_server=-1, common_seqno=-1,
                       put_id=put_id, **more))


FAILOVER_NAMES = ("repl_frames", "repl_entries", "repl_bytes", "repl_applied",
                  "failover_adopted", "failover_resent_puts",
                  "failover_deduped_puts")


def test_an_unconfigured_world_mints_none_of_the_failover_meters():
    fabric = InProcFabric(5)
    srv = Server(_world(), Config(), fabric.endpoint(2))
    _put(srv, 0, 1)
    srv._flush_repl()
    snap = srv.metrics.snapshot()
    names = set(snap["counters"]) | set(snap["histograms"])
    assert not [n for n in names if n.startswith(FAILOVER_NAMES)
                or "adlb.repl" in n or "adlb.failover" in n
                or n.startswith("repl_flush_s")]
    stats = srv.finalize_stats()
    assert not [k for k in stats if isinstance(k, str)
                and k.startswith(("repl_", "failover_", "master_failover"))]
    assert not hasattr(srv, "_m_repl_frames")
    # and no replication frame goes to the ring buddy
    sent = []
    while (m := fabric.endpoints[3].recv(timeout=0.0)) is not None:
        sent.append(m.tag)
    assert Tag.SS_REPL not in sent


def test_the_stream_counts_frames_entries_bytes_and_its_seconds():
    fabric = InProcFabric(5)
    cfg = Config(on_server_failure="failover")
    primary = Server(_world(), cfg, fabric.endpoint(2))
    buddy = Server(_world(), cfg, fabric.endpoint(3))
    for put_id in range(4):
        _put(primary, 0, put_id)  # one frame a put, ahead of its ack
    primary.repl.log_app_done(1)
    primary.repl.log_app_done(0)
    primary._flush_repl()         # two entries, one frame
    primary._flush_repl()         # nothing buffered: no frame, no span
    value = primary.metrics.value
    assert (value("repl_frames"), value("repl_entries")) == (5, 6)
    frames = []
    while (m := fabric.endpoints[3].recv(timeout=0.0)) is not None:
        if m.tag is Tag.SS_REPL:
            frames.append(m)
            buddy._handle(m)
    assert len(frames) == 5
    assert value("repl_bytes") == sum(len(m.blob) for m in frames)
    assert buddy.metrics.value("repl_applied") == 6
    hist = primary.metrics.snapshot()["histograms"]
    assert hist["repl_flush_s"]["count"] == 5
    assert hist["span_s{name=adlb.repl.flush}"]["count"] == 5
    stats = primary.finalize_stats()
    assert (stats["repl_frames"], stats["repl_entries"]) == (5, 6)
    assert stats["repl_flush_s"] == pytest.approx(
        sum(stats["repl_flush_by_second"].values()))
    assert "master_failover_mttr_ms" not in stats  # nobody was promoted


def test_a_promotion_counts_what_it_adopted_and_the_resent_puts_it_absorbed():
    fabric = InProcFabric(5)
    cfg = Config(on_server_failure="failover")
    master = Server(_world(), cfg, fabric.endpoint(2))
    deputy = Server(_world(), cfg, fabric.endpoint(3))
    for put_id in range(5):
        _put(master, 0, put_id)
    while (m := fabric.endpoints[3].recv(timeout=0.0)) is not None:
        if m.tag is Tag.SS_REPL:
            deputy._handle(m)
    deputy._server_tail_drained.add(2)  # the dead master's EOF was seen
    deputy._handle(msg(Tag.SS_SERVER_DEAD, 4, rank=2, epoch=1))
    assert deputy.is_master and deputy.wq.count == 5
    value = deputy.metrics.value
    assert value("failover_adopted") == 5 and value("failover_promoted") == 1
    # a put the mirror held, re-sent under its id: absorbed; one it did
    # not hold: stored; a new put through the takeover map: neither
    _put(deputy, 0, 4, fo_from=2, fo_resend=1)
    _put(deputy, 0, 5, fo_from=2, fo_resend=1)
    _put(deputy, 0, 6, fo_from=2)
    assert deputy.wq.count == 7
    assert (value("failover_resent_puts"),
            value("failover_deduped_puts")) == (2, 1)
    hist = deputy.metrics.snapshot()["histograms"]
    assert hist["span_s{name=adlb.failover.promote}"]["count"] == 1
    assert hist["span_s{name=adlb.failover.promote_master}"]["count"] == 1
    stats = deputy.finalize_stats()
    assert stats["failover_adopted"] == 5
    assert stats["master_failover_mttr_ms"] >= 0.0
    assert "solver" in stats  # the planner's facts are the deputy's now
    # the adopted shard went on to the deputy's own buddy, re-logged
    assert stats["repl_entries"] >= 5 + 2
