"""The sidecar's path from the wire to the ledger, replayed: a recorded
sequence of ``SS_STATE`` / ``SS_STATE_DELTA`` frames goes through the
codec, ``decode_snapshot`` and ``merge_delta``; what the snapshots yield
must be what the row-by-row code this path replaced built (kept below as
the plain reference), the cap and the ``delta_seq`` bump must hold, and
the ledger must count every rebuild as array-shaped. Plus the bound on
the engine's plan ledgers (``PlanEngine._account``) and the reader of the
benchmark's ``round_admit_ms``."""

import copy
import glob
import json
import os
import statistics

import numpy as np
import pytest

import tests.conftest  # noqa: F401  (forces the CPU platform)

from adlb_tpu.balancer import engine as engine_mod
from adlb_tpu.balancer.engine import PlanEngine
from adlb_tpu.balancer.ledger import SnapshotStore, TaskTable
from adlb_tpu.balancer.sidecar import decode_snapshot, merge_delta, run_sidecar
from adlb_tpu.obs.metrics import Registry
from adlb_tpu.runtime import codec as codec_mod
from adlb_tpu.runtime.messages import Tag, msg
from adlb_tpu.runtime.world import Config, WorldSpec
from tests.test_ledger_parity import _Clock

T1, T2 = 1, 2
CAP = 16   # balancer_max_tasks of the replay
JOBS = 3   # balancer_max_jobs
S0, S1, S2, STRANGER = 200, 201, 202, 209

DECODERS = [pytest.param(codec_mod.decode_binary_py, id="py")]
if codec_mod._load_c_codec():
    DECODERS.append(pytest.param(codec_mod._c_decode, id="c"))


# ---- the plain reference: the parent commit's code, row by row ----------

def ref_decode_snapshot(fields: dict, stamp: float) -> dict:
    tf = fields.get("tasks_flat") or []
    tasks = [
        (tf[i], tf[i + 1], tf[i + 2], tf[i + 3]) for i in range(0, len(tf), 4)
    ]
    rf = fields.get("reqs_flat") or []
    reqs = []
    i = 0
    while i < len(rf):
        rank, rqseqno, ntypes = rf[i], rf[i + 1], rf[i + 2]
        i += 3
        if ntypes < 0:
            types = None
        else:
            types = [int(t) for t in rf[i:i + ntypes]]
            i += ntypes
        reqs.append((rank, rqseqno, types))
    ma = fields.get("mig_acks")
    return {
        "tasks": tasks,
        "reqs": reqs,
        "nbytes": fields.get("nbytes", 0),
        "consumers": fields.get("consumers", 0),
        "stamp": stamp,
        "mig_acks": (
            {ma[i]: ma[i + 1] for i in range(0, len(ma), 2)}
            if ma is not None else None
        ),
    }


def ref_merge_delta(snap: dict, f: dict) -> None:
    if f.get("seqnos") is not None:
        jbs = f.get("jobs") or [0] * len(f["seqnos"])
        units = zip(f["seqnos"], f["work_types"], f["prios"],
                    f["work_lens"], jbs)
    else:
        units = [(f["seqno"], f["work_type"], f["prio"], f["work_len"], 0)]
    for sq, wt, pr, ln, jb in units:
        if len(snap["tasks"]) >= CAP:
            break
        if jb:
            if not 0 <= jb < JOBS:
                continue  # overflow namespace
            snap["tasks"].append((sq, wt, pr, ln, jb))
        else:
            snap["tasks"].append((sq, wt, pr, ln))
    snap["nbytes"] = f.get("nbytes", snap["nbytes"])
    snap["delta_seq"] = snap.get("delta_seq", 0) + 1


# ---- the recorded sequence ----------------------------------------------

def flat(units):
    return [x for u in units for x in u]


def units(first, n, wtype=T1):
    return [(first + i, wtype if i % 3 else T2, 5 - i % 4, 8 + i)
            for i in range(n)]


#: (tag, src, fields); None ends a batch: the planner takes a round
SCRIPT = [
    (Tag.SS_STATE, S0, dict(tasks_flat=flat(units(1000, CAP)), reqs_flat=[],
                            nbytes=4096, consumers=2, mig_acks=[S1, 3])),
    (Tag.SS_STATE, S1, dict(tasks_flat=[], nbytes=0, consumers=2,
                            reqs_flat=[40, 1, 1, T1, 41, 7, -1,
                                       42, 2, 2, T1, T2])),
    (Tag.SS_STATE, S2, dict(tasks_flat=flat(units(3000, 3)), reqs_flat=[],
                            nbytes=64, consumers=1)),
    None,
    # a batched put-event: appended in place, no stamp change
    (Tag.SS_STATE_DELTA, S2, dict(seqnos=[3003, 3004], work_types=[T1, T2],
                                  prios=[9, -2], work_lens=[8, 8],
                                  nbytes=80)),
    # at its cap already: nothing is appended, the sequence still moves
    (Tag.SS_STATE_DELTA, S0, dict(seqnos=[1999], work_types=[T1], prios=[1],
                                  work_lens=[8], nbytes=5000)),
    # no baseline for this sender yet: ignored
    (Tag.SS_STATE_DELTA, STRANGER, dict(seqnos=[1], work_types=[T1],
                                        prios=[1], work_lens=[8])),
    None,
    # jobs ride along: five-wide rows, the default namespace stays four
    # wide to its readers, an overflow namespace stays off the table
    (Tag.SS_STATE_DELTA, S2, dict(seqnos=[3005, 3006, 3007, 3008],
                                  work_types=[T1, T1, T2, T1],
                                  prios=[1, 2, 3, 4], work_lens=[8] * 4,
                                  jobs=[0, 2, JOBS, 1], nbytes=112)),
    # the single-unit shape of older daemons
    (Tag.SS_STATE_DELTA, S1, dict(seqno=2000, work_type=T2, prio=7,
                                  work_len=24, nbytes=24)),
    None,
    # a delta that crosses the cap is cut at it
    (Tag.SS_STATE_DELTA, S2, dict(seqnos=list(range(3100, 3120)),
                                  work_types=[T1] * 20, prios=[0] * 20,
                                  work_lens=[8] * 20, nbytes=999)),
    # jobs present and all default: rows stay four wide
    (Tag.SS_STATE_DELTA, S1, dict(seqnos=[2001], work_types=[T1], prios=[0],
                                  work_lens=[8], jobs=[0])),
    None,
    # fresh full snapshots replace what the deltas grew
    (Tag.SS_STATE, S2, dict(tasks_flat=flat(units(3200, 5)), reqs_flat=[],
                            nbytes=40, consumers=1, mig_acks=[])),
    (Tag.SS_STATE, S0, dict(tasks_flat=flat(units(1100, 7)),
                            reqs_flat=[43, 1, 1, T2], nbytes=56,
                            consumers=2)),
    None,
]


def replay(decode, on_round):
    """Feed SCRIPT through the codec and the sidecar's two functions into
    a SnapshotStore, the reference beside it from the fields as they were
    written; ``on_round(store, reference, touched)`` at every batch end."""
    store: SnapshotStore = SnapshotStore()
    reference: dict = {}
    touched: set = set()
    for step in SCRIPT:
        if step is None:
            on_round(store, reference, touched)
            touched = set()
            continue
        tag, src, fields = step
        m = decode(codec_mod.encode_binary(msg(tag, src, **fields)))
        assert (m.tag, m.src) == (tag, src)
        if tag is Tag.SS_STATE:
            assert isinstance(m.data["tasks_flat"], np.ndarray)
            assert isinstance(m.data["reqs_flat"], list)  # keeps its type
            store[src] = decode_snapshot(m)
            reference[src] = ref_decode_snapshot(fields,
                                                 store[src]["stamp"])
            touched.add(src)
        else:
            snap = store.get(src)
            if snap is None:
                continue
            before = snap.get("delta_seq", 0)
            merge_delta(snap, m, CAP, JOBS)
            store.bump(src)
            ref_merge_delta(reference[src], fields)
            assert snap["delta_seq"] == before + 1
            touched.add(src)
        for rank, snap in store.items():
            ref = reference[rank]
            table = snap["tasks"]
            assert isinstance(table, TaskTable)
            assert table.rows.dtype == np.int64
            assert len(table) <= CAP
            assert list(table) == ref["tasks"], (step, rank)
            assert all(type(x) is int for tk in table for x in tk)
            assert {k: v for k, v in snap.items() if k != "tasks"} == {
                k: v for k, v in ref.items() if k != "tasks"}, (step, rank)
    return store, reference


@pytest.mark.parametrize("decode", DECODERS)
def test_replayed_frames_yield_the_reference_tuples(decode):
    rounds = []
    store, reference = replay(decode, lambda *a: rounds.append(1))
    assert len(rounds) == 5
    # the cut at the cap, the jobs, the single-unit shape: as the
    # reference has them, and really exercised
    assert len(reference[S2]["tasks"]) == 5 and len(reference[S0]["tasks"]) == 7
    assert (2000, T2, 7, 24) in reference[S1]["tasks"]


@pytest.mark.parametrize("decode", DECODERS)
def test_replay_counts_every_rebuild_as_array_and_plans_alike(
        decode, monkeypatch):
    """The engine behind the replay: every task-side rebuild of its
    ledger read an array (``ledger_syncs{input="array"}``), the rows it
    read are counted, and its plans are those of an engine fed the
    reference's tuple lists."""
    clock = _Clock()
    monkeypatch.setattr(engine_mod, "time", clock)
    reg, reg_ref = Registry(), Registry()

    def mk(registry):
        eng = PlanEngine(types=(T1, T2), max_tasks=CAP, max_requesters=8,
                         max_jobs=JOBS, host_threshold_reqs=0,
                         metrics=registry)
        eng.PUMP_INTERVAL = 0.0
        return eng

    eng, eng_ref = mk(reg), mk(reg_ref)
    want = {"rebuilds": 0, "rows": 0, "planned": 0, "saw_cap": False,
            "saw_jobs": False}

    def on_round(store, reference, touched):
        at = clock.t
        plan = eng.round(store, None)
        clock.t = at
        assert plan == eng_ref.round(copy.deepcopy(reference), None)
        want["planned"] += bool(plan[0] or plan[1])
        want["rebuilds"] += len(touched)
        want["rows"] += sum(len(store[r]["tasks"]) for r in touched)
        want["saw_cap"] |= any(len(s["tasks"]) == CAP
                               for s in store.values())
        want["saw_jobs"] |= any(len(t) > 4 for s in store.values()
                                for t in s["tasks"])
        counters = reg.snapshot()["counters"]
        assert counters.get("ledger_syncs{input=array}") == want["rebuilds"]
        assert counters.get("ledger_rows_synced", 0) == want["rows"]
        assert "ledger_syncs{input=tuples}" not in counters

    replay(decode, on_round)
    assert want["planned"] >= 2 and want["saw_cap"] and want["saw_jobs"]
    assert eng._ledger.syncs_by_input == {"array": want["rebuilds"],
                                          "tuples": 0}
    # the reference engine read tuple lists, and says so
    ref_counters = reg_ref.snapshot()["counters"]
    assert "ledger_syncs{input=array}" not in ref_counters
    assert ref_counters["ledger_syncs{input=tuples}"] > 0
    assert "span_s{name=adlb.round.admit.sync}" in reg.snapshot()[
        "histograms"]


def test_the_sidecar_loop_replays_into_its_flight_artefact(tmp_path):
    """The same path inside ``run_sidecar``: frames in, plans out, and the
    flight artefact carries the counters."""
    world = WorldSpec(nranks=6, nservers=2, types=(T1, T2))
    s0, s1 = world.server_ranks

    def frame(tag, src, **fields):
        return codec_mod.decode_binary(
            codec_mod.encode_binary(msg(tag, src, **fields)))

    class ScriptedEp:
        def __init__(self):
            self.script = [
                frame(Tag.SS_STATE, s0, tasks_flat=flat(units(100, 2)),
                      reqs_flat=[], nbytes=16, consumers=1),
                frame(Tag.SS_STATE, s1, tasks_flat=[],
                      reqs_flat=[0, 1, 1, T1, 1, 2, -1], nbytes=0,
                      consumers=2),
                None,
                frame(Tag.SS_STATE_DELTA, s0, seqnos=[102], work_types=[T1],
                      prios=[3], work_lens=[8], nbytes=24),
                None,
                frame(Tag.DS_END, s0),
                frame(Tag.DS_END, s1),
            ]
            self.sent = []

        def recv(self, timeout=None):
            return self.script.pop(0) if self.script else None

        def send(self, dest, m, **kw):
            self.sent.append((dest, m.tag))

        def close(self):
            pass

    ep = ScriptedEp()
    cfg = Config(balancer="tpu", balancer_min_gap=0.0,
                 flight_dir=str(tmp_path), solver_host_threshold=0)
    facts = run_sidecar(world, cfg, ep)
    assert facts["rounds"] == 2
    assert (s0, Tag.SS_PLAN_MATCH) in ep.sent
    (path,) = glob.glob(os.path.join(str(tmp_path), "flight-sidecar-*.json"))
    with open(path) as f:
        counters = json.load(f)["metrics"]["counters"]
    # round 1 rebuilt both servers' task sides, round 2 the one a delta
    # touched: 2 + 0 rows, then 3
    assert counters["ledger_syncs{input=array}"] == 3
    assert counters["ledger_rows_synced"] == 5
    assert "ledger_syncs{input=tuples}" not in counters


# ---- the bound on the plan ledgers ---------------------------------------

def counted(marks):
    """Count the delete hook's calls of a ``_Marks`` dict."""
    calls = []
    hook = marks._on_del

    def on_del(key):
        calls.append(key)
        if hook is not None:
            hook(key)

    marks._on_del = on_del
    return calls


@pytest.mark.parametrize("host_ledger", ["array", "py"])
def test_account_expires_exactly_the_marks_past_the_cutoff(host_ledger):
    """Over 4,096 marks, ``_account`` deletes those planned at or before
    ``t_planned - 5`` through the dict's hooks, one call a mark, and stops
    at the first live one: no hook call and no change for the others."""
    eng = PlanEngine(types=(T1,), max_tasks=8, max_requesters=4,
                     host_ledger=host_ledger)
    t_planned = 10_000.0
    cutoff = t_planned - 5.0
    for i in range(3000):  # expired: 2,999.x seconds to 5 s old
        eng._planned_tasks[(10, i)] = cutoff - (3000 - i) * 1e-3
    eng._planned_tasks[(10, 3000)] = cutoff  # on the cut-off: expired
    for i in range(3001, 5000):  # live
        eng._planned_tasks[(10, i)] = cutoff + (i - 3000) * 1e-3
    # planned again later: the old place in the dict must not expire it
    eng._planned_tasks[(10, 5)] = t_planned
    for i in range(10):
        eng._planned_reqs[(11, i, 1)] = cutoff - 1.0 + i * 0.2  # 0..5 old
    task_calls = counted(eng._planned_tasks)
    req_calls = counted(eng._planned_reqs)
    live_before = {k: v for k, v in eng._planned_tasks.items() if v > cutoff}
    eng._account({}, [], [], t_planned, t_planned)
    assert dict(eng._planned_tasks) == live_before
    assert (10, 5) in eng._planned_tasks and len(live_before) == 2000
    assert sorted(task_calls) == sorted(
        (10, i) for i in range(3001) if i != 5)
    assert len(task_calls) == 3000  # the expired, each once; no live one
    assert req_calls == [(11, i, 1) for i in range(6)]
    assert all(v > cutoff for v in eng._planned_reqs.values())
    # under the bound nothing is visited at all
    task_calls.clear()
    eng._account({}, [], [], t_planned + 100.0, t_planned + 100.0)
    assert task_calls == [] and len(eng._planned_tasks) == 2000


def test_expired_marks_leave_the_ledgers_columns():
    """The expiry goes through the hooks: a row whose mark expired reads
    unplanned again in the array ledger's column."""
    eng = PlanEngine(types=(T1,), max_tasks=8, max_requesters=4)
    snaps = {10: {"tasks": [(1, T1, 5, 8), (2, T1, 4, 8)], "reqs": [],
                  "consumers": 1, "stamp": 50.0, "task_stamp": 50.0}}
    eng._ledger.sync(snaps, 60.0)
    eng._planned_tasks[(10, 1)] = 100.0
    for i in range(5000):
        eng._planned_tasks[(12, i)] = 101.0 + i * 1e-3
    assert eng._ledger.elig_tasks(10) == [(2, T1, 4, 8)]
    eng._account(snaps, [], [], 106.0, 106.0)  # cut-off 101.0
    assert (10, 1) not in eng._planned_tasks
    assert eng._ledger.elig_tasks(10) == [(1, T1, 5, 8), (2, T1, 4, 8)]
    assert eng._ledger._srv[10].t_planned.tolist() == [-1.0, -1.0]


# ---- the benchmark's reader of the admission span ------------------------

RECORDED = os.path.join(os.path.dirname(__file__), "benchmarks", "data",
                        "xplane_spans_small.json")


def test_round_admit_ms_reads_the_admission_span():
    from benchmarks.reduce import hostspans
    from benchmarks.spec import ROOT, Spec

    spec = Spec(ROOT)
    read = spec.reader("round_admit_ms")
    with open(RECORDED) as f:
        recorded = json.load(f)
    run = {"cell": "hotspot-native-n64.bulk", "trace": recorded}
    hostspans.attach(run, recorded)
    admits = [e[2] for e in hostspans.planner_events(recorded)
              if e[0] == "adlb.round.admit"]
    assert len(admits) > 10
    assert read(run) == pytest.approx(statistics.median(admits) * 1e-6)
    # a program without the spans, and an untraced run: left out
    bare = copy.deepcopy(recorded)
    for plane in bare["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if not e[0].startswith("adlb.")]
    none = {"cell": "c", "trace": bare}
    assert hostspans.attach(none, bare) is None and read(none) is None
    assert read({"cell": "c", "trace": None}) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "round_admit_ms"]
    assert entry == [{
        "name": "round_admit_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "planner host",
        "moves": "worker_fed_pct",
        "workloads": ["hotspot-native-n128.bulk", "hotspot-native-n64.bulk",
                      "hotspot-py-n64.bulk"],
    }]


# ---- the rendezvous ports a native world's ranks bind --------------------

def test_probed_ports_are_free_on_every_local_address(monkeypatch):
    """A rank binds the wildcard address (``libadlb.cpp``), so a port that
    something holds on another of the host's addresses must not be handed
    out: on the chip's VM that was the TPU runtime's 8431, the rank died
    on bind, and the world hung at its end (my chip runs, PR 27)."""
    import socket

    from adlb_tpu.runtime import transport_tcp as tcp

    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        floor = int(f.read().split()[0])
    lo, hi = max(1024, floor - 12000), floor - 100
    if floor < 13000 + 2 * 8:
        pytest.skip("no static range below the ephemeral floor here")
    held = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        # some port of the static range, held on an address that is not
        # the one the probe is asked about
        for port in range(lo + 500, lo + 600):
            try:
                held.bind(("127.0.0.2", port))
                break
            except OSError:
                continue
        else:
            pytest.skip("could not hold a port on 127.0.0.2")
        held.listen(1)
        # start the probe's walk right below the held port
        span = hi - lo
        monkeypatch.setattr(tcp, "_PORT_PROBE_CALLS", iter([0] * 4))
        want = (port - 1 - lo) % span
        pid = next(p for p in range(1, 200000) if (p * 40503) % span == want)
        monkeypatch.setattr("os.getpid", lambda: pid)
        ports = tcp.probe_free_ports(8, "127.0.0.1")
    finally:
        held.close()
    assert port not in ports
    assert len(set(ports)) == 8 and min(ports) > port - 1
    assert max(ports) < port + 40  # the walk did start there
