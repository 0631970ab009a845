"""What the native daemon says of itself (``serverd.cpp``: ``Phases``,
``WaitHist``, ``write_flight``, ``write_trace``): the flight artefact a
daemon leaves at its end when the world has a flight directory, and its
Chrome file under ``ADLB_TRACE``.

Two worlds, each run once. ``steal_world`` is a whole small world of
Python clients over two daemons, with both channels on. ``planned`` is two
daemons between ranks that the test plays itself over ``TcpEndpoint`` (two
apps and the planner's pseudo-rank), so that every park ends by a cause the
test chose: a plan's ``SS_PLAN_MATCH``, migrated work, a local put.
"""

import glob
import json
import os
import shutil
import struct
import time

import pytest

from adlb_tpu.native import daemon as daemon_mod
from adlb_tpu.obs.metrics import quantile_of
from adlb_tpu.runtime.messages import Tag, msg
from adlb_tpu.runtime.transport_tcp import (
    TcpEndpoint, local_addr_map, spawn_world)
from adlb_tpu.runtime.world import Config, WorldSpec
from adlb_tpu.types import ADLB_SUCCESS, InfoKey

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no C++ toolchain"
)

LIMIT_S = 60.0
T = 1
PUTS = 120
GROUPS = ["asleep", "poll", "decode", "flush", "put", "fetch", "enact",
          "snapshot", "other"]
CAUSES = ["local", "migrated", "plan", "steal"]
COUNTERS = ["conns_unix", "conns_tcp", "waits_polled", "waits_slept",
            "frames_ring", "frames_sock", "bells_rung", "bells_elided"]


def artefacts(flight_dir) -> dict:
    """``{rank: artefact}`` of the daemons that wrote into ``flight_dir``."""
    docs = {}
    for path in glob.glob(os.path.join(str(flight_dir), "flight-*.json")):
        with open(path) as f:
            doc = json.load(f)
        assert os.path.basename(path) == (
            f"flight-serverd-r{doc['rank']}-p{doc['pid']}.json")
        docs[doc["rank"]] = doc
    return docs


def merged_wait(doc) -> tuple:
    hists = doc["park_wait_s"].values()
    return sum(h["n"] for h in hists), sum(h["sum"] for h in hists)


# ------------------------------------------------------ a whole small world


def _flood_then_drain(ctx):
    """Rank 0 puts PUTS units, then everyone drains until exhaustion. It
    puts late, so that the two workers' first reserves park and are served
    by what it puts."""
    if ctx.rank == 0:
        time.sleep(0.3)
        for i in range(PUTS):
            ctx.put(struct.pack("<q", i), T)
        time.sleep(2.2)  # the world holds a whole second of its clock
    n = 0
    while True:
        rc, r = ctx.reserve([T])
        if rc != ADLB_SUCCESS:
            return n
        ctx.get_reserved(r.handle)
        n += 1


@pytest.fixture(scope="module")
def steal_world(tmp_path_factory):
    """(result, {rank: artefact}, trace prefix) of a steal-mode world of
    three Python clients over two daemons, with a flight directory and
    ``ADLB_TRACE`` in the launcher's environment."""
    tmp = tmp_path_factory.mktemp("steal")
    prefix = str(tmp / "t")
    before = os.environ.get("ADLB_TRACE")
    os.environ["ADLB_TRACE"] = prefix  # the daemons inherit it
    try:
        res = spawn_world(
            3, 2, [T], _flood_then_drain, timeout=LIMIT_S,
            cfg=Config(server_impl="native", exhaust_check_interval=0.2,
                       flight_dir=str(tmp / "flight")))
    finally:
        if before is None:
            del os.environ["ADLB_TRACE"]
        else:
            os.environ["ADLB_TRACE"] = before
    assert sum(res.app_results.values()) == PUTS
    return res, artefacts(tmp / "flight"), prefix


def test_a_world_with_a_flight_dir_leaves_one_artefact_a_daemon(steal_world):
    res, docs, _prefix = steal_world
    assert sorted(docs) == sorted(res.server_stats) == [3, 4]
    for rank, doc in docs.items():
        assert (doc["role"], doc["clock"], doc["reason"], doc["schema"]) == (
            "serverd", "CLOCK_MONOTONIC", "exit", 1)
        assert doc["groups"] == GROUPS
        assert sorted(doc["park_wait_s"]) == sorted(CAUSES)
        assert 0 < doc["t_start"] < doc["t_end"] <= time.monotonic()
        # one file holds all the daemon counted: the trailer's by-name
        # counters are in it, with the trailer's values
        for key in COUNTERS:
            assert doc[key] == res.server_stats[rank][key], key


def test_every_instant_is_under_one_phase(steal_world):
    """The phases of the whole world sum to its length; the groups of every
    whole second sum to that second, and of the first and the last to what
    the daemon lived of them."""
    _res, docs, _prefix = steal_world
    for doc in docs.values():
        span = doc["t_end"] - doc["t_start"]
        assert sum(doc["phase_s"].values()) == pytest.approx(span, abs=1e-6)
        seconds = sorted(int(s) for s in doc["by_second"])
        assert seconds == list(range(int(doc["t_start"]),
                                     int(doc["t_end"]) + 1))
        assert len(seconds) >= 3  # at least one whole second
        for sec in seconds:
            rec = doc["by_second"][str(sec)]
            lived = min(sec + 1, doc["t_end"]) - max(sec, doc["t_start"])
            assert sum(rec["s"]) == pytest.approx(lived, abs=1e-3)
            assert all(s >= 0 for s in rec["s"])
        for g, name in enumerate(GROUPS):
            total = sum(rec["s"][g] for rec in doc["by_second"].values())
            of_group = sum(
                s for p, s in doc["phase_s"].items() if _group(p) == name)
            assert total == pytest.approx(of_group, abs=1e-6), name


def _group(phase: str) -> str:
    """The group of a phase, as the issue's table has it."""
    if not phase.startswith("handler:"):
        return {"periodic:snapshot": "snapshot",
                "periodic:other": "other"}.get(phase, phase)
    tag = phase[len("handler:"):]
    if tag in ("FA_PUT", "FA_PUT_COMMON", "FA_BATCH_DONE",
               "FA_DID_PUT_AT_REMOTE"):
        return "put"
    if tag in ("FA_RESERVE", "FA_GET_RESERVED", "FA_GET_COMMON"):
        return "fetch"
    if tag in ("SS_PLAN_MATCH", "SS_PLAN_MIGRATE", "SS_MIGRATE_WORK",
               "SS_MIGRATE_ACK", "SS_RFR", "SS_RFR_RESP"):
        return "enact"
    return "other"


def test_a_handlers_count_is_the_frames_of_its_tag(steal_world):
    res, docs, _prefix = steal_world
    assert sum(d["phase_n"].get("handler:FA_PUT", 0)
               for d in docs.values()) == PUTS
    assert sum(d["phase_n"].get("handler:FA_GET_RESERVED", 0)
               for d in docs.values()) == PUTS
    for rank, doc in docs.items():
        assert doc["phase_n"]["handler:FA_RESERVE"] == \
            res.server_stats[rank][int(InfoKey.NUM_RESERVES)]
        # by group and second too: the frames of `put` are the puts
        g = GROUPS.index("put")
        assert sum(rec["n"][g] for rec in doc["by_second"].values()) == \
            doc["phase_n"].get("handler:FA_PUT", 0)
        # a look that slept, or that found the frame while polling
        assert doc["phase_n"]["asleep"] <= doc["waits_slept"]
        assert doc["phase_n"]["decode"] <= (
            doc["waits_slept"] + doc["waits_polled"])


def test_loop_top_time_is_the_handlers_and_their_flush(steal_world):
    """``K_LOOP_TOP_TIME`` (an Info key of the reference's API) covers the
    handlers and the flush of a turn that got a frame: without a planner,
    whose snapshots are a phase of their own, exactly those phases."""
    res, docs, _prefix = steal_world
    for rank, doc in docs.items():
        covered = doc["phase_s"]["flush"] + sum(
            s for p, s in doc["phase_s"].items() if p.startswith("handler:"))
        assert res.server_stats[rank][int(InfoKey.LOOP_TOP_TIME)] == \
            pytest.approx(covered, abs=1e-6)
        assert "periodic:snapshot" not in doc["phase_s"]


def test_park_waits_are_avg_time_on_rq_by_cause(steal_world):
    res, docs, _prefix = steal_world
    ended = 0
    for rank, doc in docs.items():
        n, total = merged_wait(doc)
        ended += n
        avg = res.server_stats[rank][int(InfoKey.AVG_TIME_ON_RQ)]
        assert avg == pytest.approx(total / n if n else 0.0, rel=1e-12)
        assert n <= res.server_stats[rank][
            int(InfoKey.NUM_RESERVES_PUT_ON_RQ)]
        # nobody plans in a steal-mode world, nobody migrates
        assert doc["park_wait_s"]["plan"]["n"] == 0
        assert doc["park_wait_s"]["migrated"]["n"] == 0
        assert doc["plan_entries"] == doc["plan_stale"] == 0
        # and no snapshot: the index is kept only for a planner
        assert doc["snap_walked"] == doc["snap_shipped"] == 0
    assert ended >= 2  # the workers' first reserves parked, and were served


def test_under_adlb_trace_the_daemon_writes_a_chrome_file(steal_world):
    _res, docs, prefix = steal_world
    for rank, doc in docs.items():
        with open(f"{prefix}.{rank}.trace.json") as f:
            events = json.load(f)
        (clock,) = [e for e in events if e["name"] == "adlb:clock"]
        assert clock["ph"] == "M"
        assert clock["args"]["clock"] == "CLOCK_MONOTONIC"
        assert clock["args"]["dropped"] == 0
        spans = [e for e in events if e["ph"] == "X"]
        assert clock["args"]["events"] == len(spans)
        assert {e["pid"] for e in events} == {1}  # the servers' lane
        assert {e["tid"] for e in spans} == {rank}
        names = {e["name"] for e in spans}
        assert {"srv:FA_PUT", "srv:FA_RESERVE", "srv:decode",
                "srv:flush"} <= names
        assert not names & {"srv:asleep", "srv:poll", "srv:periodic:other"}
        # the events are the artefact's stretches, on its clock
        puts = [e for e in spans if e["name"] == "srv:FA_PUT"]
        assert len(puts) == doc["phase_n"]["handler:FA_PUT"]
        assert sum(e["dur"] for e in puts) * 1e-6 == pytest.approx(
            doc["phase_s"]["handler:FA_PUT"], rel=1e-3)
        assert all(doc["t_start"] <= e["ts"] * 1e-6 <= doc["t_end"]
                   for e in spans)


def test_obs_report_summarises_a_daemons_artefact(steal_world):
    """``scripts/obs_report.py <flight-dir>``: each daemon's phases by
    share and its park waits by cause, where it printed an empty ring."""
    import subprocess
    import sys

    _res, docs, prefix = steal_world
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "obs_report.py"),
         os.path.join(os.path.dirname(prefix), "flight")],
        capture_output=True, text=True, timeout=LIMIT_S)
    assert out.returncode == 0, out.stderr
    assert "flight artifacts: 2" in out.stdout
    for rank in docs:
        assert f"rank {rank} [serverd] reason='exit'" in out.stdout
    assert out.stdout.count("reactor phases over") == 2
    assert "asleep" in out.stdout and "handler:FA_PUT" in out.stdout
    waits = [line for line in out.stdout.splitlines()
             if "park waits by cause:" in line]
    assert len(waits) == 2 and any("p50" in line for line in waits)
    for doc in docs.values():
        assert (f"waits_polled {doc['waits_polled']}, waits_slept "
                f"{doc['waits_slept']}, frames_ring") in out.stdout


def test_without_a_flight_dir_and_a_prefix_nothing_is_written(
        tmp_path, monkeypatch):
    monkeypatch.delenv("ADLB_FLIGHT_DIR", raising=False)
    monkeypatch.delenv("ADLB_TRACE", raising=False)
    monkeypatch.chdir(tmp_path)
    res = spawn_world(
        3, 2, [T], _drain_only, timeout=LIMIT_S,
        cfg=Config(server_impl="native", exhaust_check_interval=0.2))
    assert sum(res.app_results.values()) == 8
    assert os.listdir(tmp_path) == []
    # and the trailer is what it was: the Info keys and the eight counters
    for stats in res.server_stats.values():
        assert sorted(map(str, stats)) == sorted(
            [str(k) for k in range(1, 13)] + COUNTERS)


def _drain_only(ctx):
    if ctx.rank == 0:
        for i in range(8):
            ctx.put(struct.pack("<q", i), T)
    n = 0
    while True:
        rc, r = ctx.reserve([T])
        if rc != ADLB_SUCCESS:
            return n
        ctx.get_reserved(r.handle)
        n += 1


def test_the_flight_dir_of_the_environment_serves_too(tmp_path, monkeypatch):
    """``ADLB_FLIGHT_DIR`` opts a world in whose ``Config`` names none, as
    it does for the Python ranks (``obs/flight.py``)."""
    monkeypatch.setenv("ADLB_FLIGHT_DIR", str(tmp_path / "from env"))
    monkeypatch.delenv("ADLB_TRACE", raising=False)
    res = spawn_world(
        3, 2, [T], _drain_only, timeout=LIMIT_S,
        cfg=Config(server_impl="native", exhaust_check_interval=0.2))
    assert sum(res.app_results.values()) == 8
    docs = artefacts(tmp_path / "from env")
    assert sorted(docs) == [3, 4]
    assert sum(d["phase_n"]["handler:FA_PUT"] for d in docs.values()) == 8


# ------------------------------- two daemons among ranks the test plays


APP_PUTS, APP_PARKS, PLANNER = 0, 1, 4
HOLDER, HOME = 2, 3  # app 0's home server holds the work, app 1's parks it


class Planned:
    """Daemons 2 and 3 of a tpu-mode world; the test is app 0 (puts into
    its home, server 2), app 1 (parks at its home, server 3) and the
    planner (pseudo-rank 4, which the daemons send their snapshots to)."""

    def __init__(self, flight_dir, types=(T,), **cfg):
        self.world = WorldSpec(nranks=4, nservers=2, types=types)
        cfg = Config(server_impl="native", balancer="tpu",
                     flight_dir=str(flight_dir), exhaust_check_interval=30.0,
                     **cfg)
        self.procs = {r: daemon_mod.spawn_daemon(self.world, cfg, r)
                      for r in (HOLDER, HOME)}
        addr = {r: ("127.0.0.1", p) for r, (_h, p) in
                local_addr_map(5).items()}
        for r, p in self.procs.items():
            addr[r] = ("127.0.0.1", daemon_mod.read_hello(p, r))
        self.eps = {r: TcpEndpoint(r, addr, binary_peers={HOLDER, HOME})
                    for r in (APP_PUTS, APP_PARKS, PLANNER)}
        for p in self.procs.values():
            daemon_mod.send_addrs(p, addr)
        self.rqseqno = 0

    def expect(self, rank, tag, timeout=LIMIT_S):
        """The next frame of ``tag`` that rank ``rank`` receives."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = self.eps[rank].recv(0.5)
            if m is not None and m.tag is tag:
                return m
        raise AssertionError(f"rank {rank} got no {tag} in {timeout}s")

    def put(self, src, server, prio=0, target_rank=-1, work_type=T):
        self.eps[src].send(server, msg(
            Tag.FA_PUT, src, payload=b"12345678", work_type=work_type,
            prio=prio, target_rank=target_rank, answer_rank=-1, common_len=0,
            common_server=-1, common_seqno=-1))
        assert self.expect(src, Tag.TA_PUT_RESP).rc == ADLB_SUCCESS

    def park(self):
        """App 1 reserves at its home, which holds nothing: it parks."""
        self.rqseqno += 1
        self.eps[APP_PARKS].send(HOME, msg(
            Tag.FA_RESERVE, APP_PARKS, rqseqno=self.rqseqno, req_types=[T],
            hang=True))
        time.sleep(0.05)  # the reserve is parked before what ends its wait

    def served(self):
        resp = self.expect(APP_PARKS, Tag.TA_RESERVE_RESP)
        assert resp.rc == ADLB_SUCCESS
        return resp

    def inventory(self):
        """The seqnos server 2 reports in its next snapshot with any."""
        while True:
            m = self.expect(PLANNER, Tag.SS_STATE)
            if m.src == HOLDER and len(m.tasks_flat):
                return [int(s) for s in m.tasks_flat[0::4]]

    def finish(self):
        for app, home in ((APP_PUTS, HOLDER), (APP_PARKS, HOME)):
            self.eps[app].send(home, msg(Tag.FA_LOCAL_APP_DONE, app))
        out = {}
        for r, p in self.procs.items():
            stats, _abort, rc = daemon_mod.collect_stats(p, timeout=LIMIT_S)
            assert rc == 0, (r, rc)
            out[r] = stats
        return out

    def close(self):
        for ep in self.eps.values():
            ep.close()
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
            p.wait()
            p.stdout.close()
            p.stdin.close()


@pytest.fixture(scope="module")
def planned(tmp_path_factory):
    """(stats, artefacts) of the world in which three parks of app 1 ended,
    in this order, by a plan's match, by migrated work and by a local put;
    the planner also sent one stale match and one stale seqno to migrate."""
    tmp = tmp_path_factory.mktemp("planned")
    w = Planned(tmp)
    try:
        for _ in range(3):
            w.put(APP_PUTS, HOLDER)
        seqnos = w.inventory()
        assert len(seqnos) == 3
        # 1. a plan's SS_PLAN_MATCH at the holder, answered to the home
        w.park()
        for seqno in (seqnos[0], 999_999):  # the second names no unit
            w.eps[PLANNER].send(HOLDER, msg(
                Tag.SS_PLAN_MATCH, PLANNER, seqno=seqno, for_rank=APP_PARKS,
                req_home=HOME, rqseqno=w.rqseqno))
        assert w.served().handle[1] == HOLDER  # a handle: the unit stayed
        # 2. a unit migrated to the home
        w.park()
        w.eps[PLANNER].send(HOLDER, msg(
            Tag.SS_PLAN_MIGRATE, PLANNER, dest=HOME,
            seqnos=[seqnos[1], 888_888], mig_id=1))
        assert w.served().handle[1] == HOME
        # 3. a put into the home
        w.park()
        w.put(APP_PUTS, HOME)
        assert w.served().handle[1] == HOME
        stats = w.finish()
    finally:
        w.close()
    return stats, artefacts(tmp)


def test_each_park_counts_under_what_ended_it(planned):
    stats, docs = planned
    assert sorted(docs) == [HOLDER, HOME]
    waits = docs[HOME]["park_wait_s"]
    assert {c: waits[c]["n"] for c in CAUSES} == {
        "plan": 1, "migrated": 1, "local": 1, "steal": 0}
    assert merged_wait(docs[HOLDER])[0] == 0
    for cause in ("plan", "migrated", "local"):
        h = waits[cause]
        assert 0.0 < h["sum"] < LIMIT_S
        assert sum(h["counts"]) == h["n"] == 1
        # in the shape obs/metrics.py::quantile_of reads: the one wait lies
        # in the bucket the quantile answers from
        q = quantile_of(h["bounds"], h["counts"], h["n"], 0.5)
        assert q / 2 ** 0.5 <= h["sum"] <= q * 2 ** 0.5
    n, total = merged_wait(docs[HOME])
    assert n == 3
    assert stats[HOME][int(InfoKey.AVG_TIME_ON_RQ)] == pytest.approx(
        total / n, rel=1e-12)
    assert stats[HOME][int(InfoKey.NUM_RESERVES_PUT_ON_RQ)] == 3


def test_the_histograms_buckets_step_by_sqrt2_from_a_microsecond(planned):
    _stats, docs = planned
    h = docs[HOME]["park_wait_s"]["plan"]
    assert len(h["counts"]) == len(h["bounds"]) + 1
    assert h["bounds"][0] == pytest.approx(1e-6)
    assert h["bounds"][-1] >= 100.0 > h["bounds"][-2]
    for lo, hi in zip(h["bounds"], h["bounds"][1:]):
        assert hi / lo == pytest.approx(2 ** 0.5, rel=1e-6)


def test_plan_entries_and_the_stale_among_them_are_counted(planned):
    _stats, docs = planned
    holder, home = docs[HOLDER], docs[HOME]
    # two matches (one stale) and a migrate of two seqnos (one stale)
    assert (holder["plan_entries"], holder["plan_stale"]) == (4, 2)
    assert (home["plan_entries"], home["plan_stale"]) == (0, 0)
    assert holder["phase_n"]["handler:SS_PLAN_MATCH"] == 2
    assert holder["phase_n"]["handler:SS_PLAN_MIGRATE"] == 1
    assert holder["phase_n"]["handler:SS_MIGRATE_ACK"] == 1
    assert home["phase_n"]["handler:SS_MIGRATE_WORK"] == 1
    assert home["phase_n"]["handler:SS_RFR_RESP"] == 1
    assert holder["phase_n"]["handler:FA_PUT"] == 3
    assert home["phase_n"]["handler:FA_PUT"] == 1
    assert home["phase_n"]["handler:FA_RESERVE"] == 3


def test_a_snapshot_is_a_phase_of_its_own_wherever_it_is_sent_from(planned):
    """A reserve that parks sets a snapshot off (``maybe_event_snapshot``),
    migrated work another: they count under ``periodic:snapshot`` and the
    group ``snapshot``, not under the handler that sent them, and the
    handler is counted once."""
    stats, docs = planned
    home = docs[HOME]
    assert home["phase_n"]["periodic:snapshot"] >= 3
    assert home["phase_s"]["periodic:snapshot"] > 0
    g = GROUPS.index("snapshot")
    assert sum(rec["n"][g] for rec in home["by_second"].values()) == \
        home["phase_n"]["periodic:snapshot"]
    # the Info key still holds them: it is the turn's time, not a phase's
    covered = home["phase_s"]["flush"] + sum(
        s for p, s in home["phase_s"].items() if p.startswith("handler:"))
    loop_top = stats[HOME][int(InfoKey.LOOP_TOP_TIME)]
    assert covered <= loop_top + 1e-6
    assert loop_top <= covered + home["phase_s"]["periodic:snapshot"] + 1e-6
