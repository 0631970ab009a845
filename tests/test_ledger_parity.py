"""Host-tier ledger parity: the array-resident ledger must be
indistinguishable from the retained pure-Python twin.

Contract (see balancer/ledger.py): identical kept-requester and
eligible-task sets — and therefore identical matches AND migrations —
across randomized sequences of full snapshot restamps, in-place task
deltas (``delta_seq`` bumps, no stamp change), dead-rank requester
patches (``req_seq`` bumps), server death/rejoin, credit suppression,
plan-mark expiry (pruning), and direct plan-dict pokes.  Checked with
the single-device solver and the sharded solver at mesh sizes 1/2/8,
plus a no-realloc guard on the resident arrays and the sharded solver's
no-retrace guard under view ingest.

The wall-clock window knobs (SUPPRESS_TTL, INFLOW_*, PARK_RECENT,
LOOK_GROW_WINDOW) are pinned to deterministic extremes: the two engines
run sequentially, so their round clocks differ by one solve — a credit
or park sitting exactly on a window edge would flip between them for
timing, not semantics.
"""

import copy
import time

import numpy as np
import pytest

import tests.conftest  # noqa: F401  (forces the 8-device CPU platform)

import jax
from jax.sharding import Mesh

from adlb_tpu.balancer.distributed import DistributedAssignmentSolver
from adlb_tpu.balancer.engine import PlanEngine

TYPES = (1, 2, 3, 4)

# the multi-job fuzz arm: 3 planned namespaces with deliberately
# lopsided fair-share weights, so the weighted-score path (priority
# bias folded at pack time, jobdim.weight_bias) is part of the parity
# bar, not just the job-isolation masks
MAX_JOBS = 3
JOB_WEIGHTS = {1: 3.0, 2: 0.25}


def _mk_engine(host_ledger, solver=None, max_jobs=1, job_weights=None):
    eng = PlanEngine(types=TYPES, max_tasks=12, max_requesters=6,
                     host_ledger=host_ledger, max_jobs=max_jobs,
                     job_weights=job_weights)
    if solver is not None:
        eng.solver = solver
    eng.PUMP_INTERVAL = 0.0
    eng.INFLOW_MIN_AGE = 0.0
    eng.INFLOW_TTL = 1e9
    eng.SUPPRESS_TTL = 1e9
    eng.PARK_RECENT = 1e9
    eng.LOOK_GROW_WINDOW = 1e9
    return eng


def _rand_job(rng, J):
    """Job column draw: mostly the default namespace, a spread over the
    planned ones, and a rare overflow id (== J, i.e. >= max_jobs) to
    exercise the planner-invisible skip identically on both arms."""
    if J <= 1 or rng.random() < 0.4:
        return 0
    if rng.random() < 0.08:
        return J
    return int(rng.integers(1, J))


def _job_task(rng, seqno, J):
    """A task tuple honoring the wire rule: the 5th (job) element is
    present ONLY when the unit is outside the default namespace."""
    tk = (seqno, int(rng.choice(TYPES)), int(rng.integers(-9, 10)), 8)
    jb = _rand_job(rng, J)
    return tk + (jb,) if jb else tk


def _rand_snaps(rng, nservers, seq, stamp, J=1):
    snaps = {}
    for s in range(100, 100 + nservers):
        tasks = []
        for _ in range(int(rng.integers(0, 10))):
            seq[0] += 1
            tasks.append(_job_task(rng, seq[0], J))
        tasks.sort(key=lambda t: -t[2])
        reqs = []
        for r in range(int(rng.integers(0, 5))):
            rq = ((s - 100) * 50 + r, int(rng.integers(1, 1000)),
                  None if rng.random() < 0.2
                  else sorted({int(rng.choice(TYPES))
                               for _ in range(int(rng.integers(1, 3)))}))
            jb = _rand_job(rng, J)
            if jb:
                rq = rq + (0, jb)
            reqs.append(rq)
        snaps[s] = {"tasks": tasks, "reqs": reqs,
                    "consumers": int(rng.integers(0, 3)),
                    "stamp": stamp, "task_stamp": stamp}
    return snaps


def _bump(snaps, rank):
    """Version an in-place mutation when the dict is a SnapshotStore
    (the producer contract the runtime follows); no-op on plain dicts."""
    b = getattr(snaps, "bump", None)
    if b is not None:
        b(rank)


def _mutate(rng, pair, seq, rnd, matches, J=1):
    """One randomized world step applied identically to both engines'
    snapshot dicts: consume the plan, then a mix of delta appends,
    req-seq patches, death/rejoin, and fresh restamps."""
    t = time.monotonic()
    for snaps in pair:
        for holder, s_, rh, fr, rq in matches:
            hs = snaps.get(holder)
            if hs is not None:
                hs["tasks"] = [x for x in hs["tasks"] if x[0] != s_]
                hs["task_stamp"] = t
                _bump(snaps, holder)
            rs = snaps.get(rh)
            if rs is not None:
                rs["reqs"] = [
                    r for r in rs["reqs"]
                    if not (r[0] == fr and r[1] == rq)
                ]
                rs["stamp"] = t
                _bump(snaps, rh)
    ranks = sorted(pair[0])
    if not ranks:
        return
    # in-place task delta (no stamp bump, delta_seq carries it)
    if rng.random() < 0.7:
        tgt = int(rng.choice(ranks))
        seq[0] += 1
        unit = _job_task(rng, seq[0], J)
        for snaps in pair:
            snaps[tgt]["tasks"].append(unit)
            snaps[tgt]["delta_seq"] = snaps[tgt].get("delta_seq", 0) + 1
            _bump(snaps, tgt)
    # dead-rank req patch (req_seq bump, no stamp bump)
    if rng.random() < 0.4:
        tgt = int(rng.choice(ranks))
        dead = int(rng.integers(0, 400))
        for snaps in pair:
            kept = [r for r in snaps[tgt]["reqs"] if r[0] != dead]
            if len(kept) != len(snaps[tgt]["reqs"]):
                snaps[tgt]["reqs"] = kept
                snaps[tgt]["req_seq"] = snaps[tgt].get("req_seq", 0) + 1
                _bump(snaps, tgt)
    # server death (and a later rejoin via the restamp below)
    if rng.random() < 0.15 and len(ranks) > 2:
        tgt = int(rng.choice(ranks))
        for snaps in pair:
            snaps.pop(tgt, None)
    # fresh full restamps for a couple of servers (rejoins included)
    t2 = time.monotonic()
    for _ in range(int(rng.integers(1, 3))):
        tgt = 100 + int(rng.integers(0, 8))
        tasks = []
        for _ in range(int(rng.integers(0, 10))):
            seq[0] += 1
            tasks.append(_job_task(rng, seq[0], J))
        tasks.sort(key=lambda x: -x[2])
        rq = ((tgt - 100) * 50 + 20 + rnd, int(rng.integers(1, 1000)),
              [int(rng.choice(TYPES))])
        jb = _rand_job(rng, J)
        reqs = [rq + (0, jb) if jb else rq]
        cons = int(rng.integers(0, 3))  # drawn ONCE: both dicts identical
        for snaps in pair:
            snaps[tgt] = {"tasks": list(tasks), "reqs": list(reqs),
                          "consumers": cons, "stamp": t2, "task_stamp": t2}


def _assert_filter_parity(a, p, snapsA, snapsP):
    """Beyond plan equality: the per-rank kept/eligible row sets must
    match exactly.  Both ledgers re-filter at compare time (the py
    twin's kept lists are a round-time snapshot, the array ledger's
    columns are live — this round's plan marks already applied)."""
    now = time.monotonic()
    for e, sn in ((a, snapsA), (p, snapsP)):
        e._ledger.sync(sn, now)
        e._ledger.filter_reqs(sn, {}, now)
    for rank in snapsA:
        assert a._ledger.kept_reqs(rank) == p._ledger.kept_reqs(rank), rank
        assert a._ledger.elig_tasks(rank) == p._ledger.elig_tasks(rank), rank


def _drive(a, p, seed, rounds=14, nservers=8, J=1, reweight=None):
    rng = np.random.default_rng(seed)
    seq = [0]
    snapsA = _rand_snaps(rng, nservers, seq, time.monotonic(), J=J)
    snapsP = copy.deepcopy(snapsA)
    pair = (snapsA, snapsP)
    for rnd in range(rounds):
        if rnd == 4:
            # identical far-future in-flight credits: the suppression
            # budget path (fed types + budget) on both engines
            far = time.monotonic() + 100.0
            for e in (a, p):
                e._planned_in.setdefault(102, []).append(
                    (far, 2, 10**6, 100, frozenset({1, 2})))
        if rnd == 7 and reweight is not None:
            # live reweight mid-drive: both engines swap the same bias
            # vector (the POST /jobs/<id> weight path) and must keep
            # producing identical pair lists afterwards
            for e in (a, p):
                assert e.set_job_weights(reweight)
        mA = a.round(snapsA, None)
        mP = p.round(snapsP, None)
        assert mA == mP, (rnd, mA, mP)
        _assert_filter_parity(a, p, snapsA, snapsP)
        _mutate(rng, pair, seq, rnd, mA[0], J=J)


def test_parity_single_device_solver():
    for seed in range(4):
        a = _mk_engine("array")
        p = _mk_engine("py")
        _drive(a, p, seed)


def test_parity_single_device_solver_multi_job():
    """Job-column parity: snapshots carry a mixed job population
    (default, weighted namespaces, rare overflow ids) and both engines
    plan with lopsided fair-share weights plus a live mid-drive
    reweight — matches and kept/eligible sets must stay identical."""
    for seed in range(4):
        a = _mk_engine("array", max_jobs=MAX_JOBS, job_weights=JOB_WEIGHTS)
        p = _mk_engine("py", max_jobs=MAX_JOBS, job_weights=JOB_WEIGHTS)
        _drive(a, p, 50 + seed, J=MAX_JOBS,
               reweight={1: 0.5, 2: 2.0})


@pytest.fixture(scope="module", params=[1, 2, 8])
def mesh(request):
    devs = np.array(jax.devices()[: request.param])
    return Mesh(devs, axis_names=("s",))


def test_parity_sharded_solver(mesh):
    """Array-ledger view ingest into the sharded solver vs the py twin's
    materialized-dict path, at mesh 1/2/8 — same plans, same filters."""
    ndev = mesh.devices.size
    nservers = 2 * ndev if ndev > 4 else 8

    def dist():
        return DistributedAssignmentSolver(
            types=TYPES, max_tasks_per_server=12, max_requesters=6,
            mesh=mesh, rounds=64,
            servers_per_device=-(-nservers // ndev),
        )

    a = _mk_engine("array", dist())
    p = _mk_engine("py", dist())
    _drive(a, p, 1000 + ndev, nservers=nservers)


def test_parity_sharded_solver_multi_job(mesh):
    """The sharded solver's composite (job, type) axis vs the py twin,
    at mesh 1/2/8 — the death/rejoin churn in _mutate rides along, so
    the job column survives restamps and membership changes too."""
    ndev = mesh.devices.size
    nservers = 2 * ndev if ndev > 4 else 8

    def dist():
        return DistributedAssignmentSolver(
            types=TYPES, max_tasks_per_server=12, max_requesters=6,
            mesh=mesh, rounds=64,
            servers_per_device=-(-nservers // ndev),
            max_jobs=MAX_JOBS, job_weights=JOB_WEIGHTS,
        )

    a = _mk_engine("array", dist(), max_jobs=MAX_JOBS,
                   job_weights=JOB_WEIGHTS)
    p = _mk_engine("py", dist(), max_jobs=MAX_JOBS,
                   job_weights=JOB_WEIGHTS)
    _drive(a, p, 2000 + ndev, nservers=nservers, J=MAX_JOBS,
           reweight={1: 1.0, 2: 5.0})


def test_no_realloc_and_no_retrace_steady_state():
    """Steady rounds must neither reallocate the ledger's resident
    arrays nor retrace the sharded solver's jitted sweep."""
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, axis_names=("s",))
    eng = _mk_engine("array", DistributedAssignmentSolver(
        types=TYPES, max_tasks_per_server=12, max_requesters=6, mesh=mesh,
        rounds=16))
    rng = np.random.default_rng(3)
    seq = [0]
    snaps = _rand_snaps(rng, 8, seq, time.monotonic())
    eng.round(snaps, None)  # registration/allocation round
    led = eng._ledger
    ids = {
        n: id(getattr(led, n))
        for n in ("pk_tp", "pk_tt", "pk_rv", "pk_rm", "g_dem", "g_sup",
                  "g_taskcnt", "g_eligreq")
    }
    for rnd in range(12):
        t = time.monotonic()
        for tgt in (100, 101):
            seq[0] += 1
            snaps[tgt]["tasks"] = [
                (seq[0], int(rng.choice(TYPES)), int(rng.integers(-9, 10)),
                 8)
            ]
            snaps[tgt]["reqs"] = [
                ((tgt - 100) * 50 + rnd, int(rng.integers(1, 1000)),
                 [int(rng.choice(TYPES))])
            ]
            snaps[tgt]["stamp"] = snaps[tgt]["task_stamp"] = t
        eng.round(snaps, None)
    for n, i in ids.items():
        assert id(getattr(led, n)) == i, f"{n} reallocated mid-steady-state"
    # the engine's solver defaults to the fused device tier; whichever
    # jitted program carried the rounds must have compiled exactly once
    plan_fn = eng.solver._plan_fn or eng.solver._gather_fn
    assert plan_fn._cache_size() == 1
    assert led.patch_count > 0
    # the fast path really carried the rounds: no cadence resync yet
    assert led.resync_count == 0


def test_parity_store_driven_stamp_stampless_mix():
    """The runtime shape since the O(S) scan kill: the array engine is
    driven by a versioned SnapshotStore (every in-place mutation
    bump()ed, as server.py/sidecar.py do) while the py twin reads a
    plain dict mutated identically — with a STAMPLESS minority mixed in
    (snapshots from planes that never stamp re-derive every round by
    contract). Plans and kept/eligible sets must stay identical, and
    the store fast path must actually carry the steady rounds: full
    walks only at the cold start and on real membership churn."""
    from adlb_tpu.balancer.ledger import SnapshotStore

    for seed in (21, 22, 23):
        a = _mk_engine("array")
        p = _mk_engine("py")
        rng = np.random.default_rng(seed)
        seq = [0]
        base = _rand_snaps(rng, 8, seq, time.monotonic())
        for s in sorted(base)[::3]:  # stampless minority
            base[s].pop("stamp")
            base[s].pop("task_stamp")
        snapsA: SnapshotStore = SnapshotStore(base)
        snapsP = copy.deepcopy(base)
        pair = (snapsA, snapsP)
        rounds = 14
        for rnd in range(rounds):
            mA = a.round(snapsA, None)
            mP = p.round(snapsP, None)
            assert mA == mP, (seed, rnd, mA, mP)
            _assert_filter_parity(a, p, snapsA, snapsP)
            _mutate(rng, pair, seq, rnd, mA[0])
        led = a._ledger
        reasons = led.resync_reasons
        assert reasons.get("cold", 0) <= 1, reasons
        # deaths/rejoins in _mutate are the only legitimate full walks
        # beyond the cold one; most rounds must ride the O(changed)
        # fast path (the compare-time syncs in _assert_filter_parity
        # are same-version no-ops on the store arm)
        assert sum(reasons.values()) < rounds, reasons


def test_store_fork_isolates_concurrent_mutation():
    """The balancer worker plans over store.fork() while the reactor
    keeps mutating the live store: the fork's version marks must make
    the NEXT sync see exactly the ranks that changed after the fork —
    nothing lost, kept/eligible sets equal to a from-scratch twin's."""
    from adlb_tpu.balancer.ledger import SnapshotStore

    a = _mk_engine("array")
    p = _mk_engine("py")
    rng = np.random.default_rng(5)
    seq = [0]
    live: SnapshotStore = SnapshotStore(
        _rand_snaps(rng, 6, seq, time.monotonic()))
    plain = copy.deepcopy(dict(live))
    fork0 = live.fork()
    assert a.round(fork0, None) == p.round(plain, None)
    # concurrent-style mutations on the LIVE store after the fork (the
    # fork the round just used is untouched); the py twin's plain dict
    # gets the identical mutations
    t = time.monotonic()
    for d in (live, plain):
        d[100]["tasks"].append((10**6, 1, 9, 8))
        d[100]["delta_seq"] = d[100].get("delta_seq", 0) + 1
        d[101]["reqs"] = [(50, 999, [2])]
        d[101]["stamp"] = t
        d.pop(104)
    live.bump(100)
    live.bump(101)
    assert 104 in fork0 and 104 not in live  # fork really is isolated
    fork1 = live.fork()
    assert a.round(fork1, None) == p.round(plain, None)
    _assert_filter_parity(a, p, fork1, plain)
    # the post-fork changes arrived through the log tail, not a walk:
    # no membership/cold full pass beyond the initial one
    assert a._ledger.resync_reasons.get("cold", 0) == 1
    # (104's death IS a membership change — that one full walk is the
    # contract; nothing else may have forced one)
    assert a._ledger.resync_reasons.get("membership", 0) == 1


def test_direct_plan_dict_pokes_stay_coherent():
    """Tests (and future code) poke engine._planned_tasks/_planned_reqs
    directly; the array ledger's columns must follow via the dict
    hooks — including deletes (the prune path)."""
    a = _mk_engine("array")
    p = _mk_engine("py")
    t0 = time.monotonic()
    snaps = {
        10: {"tasks": [(1, 1, 5, 8), (2, 2, 4, 8)], "reqs": [],
             "consumers": 1, "stamp": t0, "task_stamp": t0},
        11: {"tasks": [], "reqs": [(5, 1, [1]), (6, 2, [2])],
             "consumers": 1, "stamp": t0, "task_stamp": t0},
    }
    snaps2 = copy.deepcopy(snaps)
    now = time.monotonic()
    for e, sn in ((a, snaps), (p, snaps2)):
        e._ledger.sync(sn, now)
        e._ledger.filter_reqs(sn, {}, now)
    # poke AFTER the array columns exist: mark task (10, 1) and req
    # (11, 6, 2) planned in the future — the dict hooks must keep the
    # columns live
    for e in (a, p):
        e._planned_tasks[(10, 1)] = t0 + 100.0
        e._planned_reqs[(11, 6, 2)] = t0 + 100.0
    assert a._ledger.elig_tasks(10) == p._ledger.elig_tasks(10) == [
        (2, 2, 4, 8)]
    # only the unmarked pair remains — and it is type-incompatible, so
    # no plan on either engine
    mA, mP = a.round(snaps, None), p.round(snaps2, None)
    assert mA == mP == ([], [])
    _assert_filter_parity(a, p, snaps, snaps2)
    # delete the marks (what pruning does) — both become eligible again
    for e in (a, p):
        del e._planned_tasks[(10, 1)]
        del e._planned_reqs[(11, 6, 2)]
    mA, mP = a.round(snaps, None), p.round(snaps2, None)
    assert mA == mP and len(mA[0]) == 2
    _assert_filter_parity(a, p, snaps, snaps2)


def test_pump_precheck_parity_fuzz():
    """The vectorized _maybe_imbalanced twin answers exactly like the
    Python pre-check over random synced instances (consumers, raw
    counts, windows, planned-away edges)."""
    rng = np.random.default_rng(11)
    for trial in range(30):
        eng = _mk_engine("array")
        seq = [0]
        t0 = time.monotonic()
        snaps = _rand_snaps(rng, int(rng.integers(2, 8)), seq, t0)
        # sprinkle planned-away marks over some listed tasks
        for rank, snap in snaps.items():
            for tk in snap["tasks"]:
                if rng.random() < 0.3:
                    eng._planned_tasks[(rank, tk[0])] = (
                        t0 + (1.0 if rng.random() < 0.5 else -100.0))
        # random adaptive windows
        for rank in snaps:
            if rng.random() < 0.4:
                eng._look[rank] = float(rng.integers(8, 64))
        now = time.monotonic()
        eng._ledger.sync(snaps, now)
        fast = eng._ledger.maybe_imbalanced(eng, snaps)
        assert fast is not None, "ledger should be synced here"
        assert fast == eng._maybe_imbalanced(snaps), (trial, snaps)


def test_unsynced_direct_call_falls_back():
    """maybe_imbalanced on a dict the ledger never synced returns None
    (the engine then runs the Python pre-check) — the contract the
    pre-existing direct-call unit tests rely on."""
    eng = _mk_engine("array")
    snaps = {
        10: {"tasks": [(1, 1, 1, 8)], "reqs": [], "consumers": 1},
        11: {"tasks": [], "reqs": [], "consumers": 1},
    }
    assert eng._ledger.maybe_imbalanced(eng, snaps) is None
    assert isinstance(eng._maybe_imbalanced(snaps), bool)


# --------------------------------------------------------------------------
# The two input shapes (PR 27): a snapshot's task table arrives either as
# a list of tuples (Python servers, hand-built dicts) or as an int64 array
# (``TaskTable``, what the sidecar decodes a native SS_STATE into). An
# ArrayLedger fed either, and the PyLedger, must be indistinguishable.


class _Clock:
    """Stand-in for ``engine.time``: the engines of one comparison run in
    turn, and each has to read the same instants, or their plan marks
    (and so the ``t_planned`` columns) differ by the time a solve took."""

    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        self.t += 1e-4
        return self.t


def _table(tasks):
    """The list's tuples as a TaskTable: five columns wide when some unit
    carries a job, as the sidecar's merge makes it."""
    from adlb_tpu.balancer.ledger import TaskTable

    wide = any(len(t) > 4 for t in tasks)
    rows = [t if len(t) > 4 else t + (0,) for t in tasks] if wide else tasks
    return TaskTable(
        np.array(rows, np.int64).reshape(-1, 5 if wide else 4))


class _World:
    """One engine's snapshots, mutated through the same calls whatever
    shape its task tables have."""

    def __init__(self, snaps, arrays: bool):
        from adlb_tpu.balancer.ledger import SnapshotStore

        self.arrays = arrays
        snaps = copy.deepcopy(snaps)
        if arrays:
            # the sidecar's shape: array tables in a versioned store
            for snap in snaps.values():
                snap["tasks"] = _table(snap["tasks"])
            snaps = SnapshotStore(snaps)
        self.snaps = snaps

    def tasks(self, rank):
        return list(self.snaps[rank]["tasks"])

    def set_tasks(self, rank, tasks):
        self.snaps[rank]["tasks"] = _table(tasks) if self.arrays \
            else list(tasks)
        _bump(self.snaps, rank)

    def append(self, rank, unit):
        snap = self.snaps[rank]
        if self.arrays:
            snap["tasks"].extend(np.array([unit], np.int64))
        else:
            snap["tasks"].append(unit)
        snap["delta_seq"] = snap.get("delta_seq", 0) + 1
        _bump(self.snaps, rank)

    def put(self, rank, snap):
        snap = copy.deepcopy(snap)
        if self.arrays:
            snap["tasks"] = _table(snap["tasks"])
        self.snaps[rank] = snap


UNKNOWN_TYPE = 99  # not in TYPES


def _spice(rng, tasks, reqs, seq):
    """Duplicates and unknown types, the rows the vector path has to
    treat as the row walk did: a unit listed twice, a unit of a type the
    world does not know, a requester asking for one."""
    if tasks and rng.random() < 0.3:
        tasks.append(tasks[int(rng.integers(0, len(tasks)))])
    if rng.random() < 0.3:
        seq[0] += 1
        tasks.append((seq[0], UNKNOWN_TYPE, int(rng.integers(-9, 10)), 8))
    if reqs and rng.random() < 0.15:
        r = reqs[int(rng.integers(0, len(reqs)))]
        if r[2] is not None:
            reqs[reqs.index(r)] = (r[0], r[1], r[2] + [UNKNOWN_TYPE]) + r[3:]
    tasks.sort(key=lambda t: -t[2])


def _mutate_worlds(rng, worlds, engines, clock, seq, rnd, matches, poked,
                   J, pokes):
    """One randomized step applied identically to every world: consume
    the plan, a delta append, a dead-rank patch, death and rejoin, fresh
    restamps, and plan marks set and deleted directly."""
    t = clock.monotonic()
    for w in worlds:
        for holder, s_, rh, fr, rq in matches:
            if holder in w.snaps:
                w.set_tasks(holder,
                            [x for x in w.tasks(holder) if x[0] != s_])
                w.snaps[holder]["task_stamp"] = t
            rs = w.snaps.get(rh)
            if rs is not None:
                rs["reqs"] = [r for r in rs["reqs"]
                              if not (r[0] == fr and r[1] == rq)]
                rs["stamp"] = t
                _bump(w.snaps, rh)
    ranks = sorted(worlds[0].snaps)
    if rng.random() < 0.8 and ranks:
        tgt = int(rng.choice(ranks))
        seq[0] += 1
        unit = _job_task(rng, seq[0], J)
        for w in worlds:
            w.append(tgt, unit)
    if rng.random() < 0.4 and ranks:
        tgt = int(rng.choice(ranks))
        dead = int(rng.integers(0, 400))
        for w in worlds:
            snap = w.snaps[tgt]
            kept = [r for r in snap["reqs"] if r[0] != dead]
            if len(kept) != len(snap["reqs"]):
                snap["reqs"] = kept
                snap["req_seq"] = snap.get("req_seq", 0) + 1
                _bump(w.snaps, tgt)
    if rng.random() < 0.15 and len(ranks) > 2:
        tgt = int(rng.choice(ranks))
        for w in worlds:
            w.snaps.pop(tgt, None)
    t2 = clock.monotonic()
    for _ in range(int(rng.integers(1, 3))):
        tgt = 100 + int(rng.integers(0, 8))
        tasks = []
        for _ in range(int(rng.integers(0, 10))):
            seq[0] += 1
            tasks.append(_job_task(rng, seq[0], J))
        rq = ((tgt - 100) * 50 + 20 + rnd, int(rng.integers(1, 1000)),
              [int(rng.choice(TYPES))])
        jb = _rand_job(rng, J)
        reqs = [rq + (0, jb) if jb else rq]
        _spice(rng, tasks, reqs, seq)
        snap = {"tasks": tasks, "reqs": reqs,
                "consumers": int(rng.integers(0, 3)),
                "stamp": t2, "task_stamp": t2}
        for w in worlds:
            w.put(tgt, snap)
    if not pokes:
        return
    # plan marks poked in and out directly (what
    # test_direct_plan_dict_pokes_stay_coherent does by hand)
    for key in [k for k in poked if rng.random() < 0.5]:
        poked.remove(key)
        for e in engines:
            d = e._planned_tasks if len(key) == 2 else e._planned_reqs
            d.pop(key, None)
    for rank in sorted(worlds[0].snaps):
        snap = worlds[-1].snaps[rank]
        if snap["tasks"] and rng.random() < 0.3:
            tk = snap["tasks"][int(rng.integers(0, len(snap["tasks"])))]
            key, when = (rank, tk[0]), t2 + (100.0 if rng.random() < 0.5
                                             else -100.0)
            poked.append(key)
            for e in engines:
                e._planned_tasks[key] = when
        if snap["reqs"] and rng.random() < 0.2:
            r = snap["reqs"][0]
            key = (rank, r[0], r[1])
            poked.append(key)
            for e in engines:
                e._planned_reqs[key] = t2 + 100.0


T_COLS = ("t_seq", "t_tix", "t_prio", "t_planned", "t_elig")
R_COLS = ("r_rank", "r_seq", "r_any", "r_mask", "r_planned", "r_elig",
          "round_sup")
AGGREGATES = ("g_dem", "g_any", "g_eligreq", "g_sup", "g_taskcnt",
              "g_eligtask", "g_planned_away", "g_hasreqs", "g_consumers")


def _assert_same_ledger(la, lb, where):
    """Every resident column, aggregate and packed row of two array
    ledgers, server by server."""
    assert la.servers == lb.servers, where
    va, vb = la.view(), lb.view()
    assert va.slot_order.size == len(la.servers)
    for rank in la.servers:
        sa, sb = la._srv[rank], lb._srv[rank]
        at = (where, rank)
        for col in T_COLS + R_COLS:
            ca, cb = getattr(sa, col), getattr(sb, col)
            assert ca.dtype == cb.dtype, (at, col)
            np.testing.assert_array_equal(ca, cb, err_msg=f"{at} {col}")
        assert (sa.t_n, sa.r_n, sa.consumers, sa.r_dups, sa.r_unknown) == (
            sb.t_n, sb.r_n, sb.consumers, sb.r_dups, sb.r_unknown), at
        assert la._task_index(sa) == lb._task_index(sb), at
        assert sa.t_dups == sb.t_dups, at
        for g in AGGREGATES:
            np.testing.assert_array_equal(
                getattr(la, g)[sa.slot], getattr(lb, g)[sb.slot],
                err_msg=f"{at} {g}")
        n = int(va.pk_tn[sa.slot])
        assert n == int(vb.pk_tn[sb.slot]) == min(
            int(sa.t_elig.sum()), la.K), at
        for pk in ("pk_tp", "pk_tt", "pk_rv", "pk_rm"):
            np.testing.assert_array_equal(
                getattr(va, pk)[sa.slot], getattr(vb, pk)[sb.slot],
                err_msg=f"{at} {pk}")
        np.testing.assert_array_equal(
            va.pk_ts[sa.slot, :n], vb.pk_ts[sb.slot, :n], err_msg=str(at))
        assert va.pk_rrefs[sa.slot] == vb.pk_rrefs[sb.slot], at
        refs = [va.task_ref(sa.slot, i) for i in range(la.K)]
        assert refs == [vb.task_ref(sb.slot, i) for i in range(la.K)], at
        assert refs[n:] == [None] * (la.K - n), at
        assert all(r == (rank, int(q)) for r, q in
                   zip(refs[:n], sa.t_seq[np.flatnonzero(sa.t_elig)])), at


def _drive_shapes(monkeypatch, mk, seed, J=1, rounds=12, nservers=8,
                  pokes=True):
    """``mk(host_ledger)`` builds an engine. Three of them — an array
    ledger fed array-shaped snapshots, one fed the same snapshots as
    tuple lists, and the Python twin — plan the same fuzzed world."""
    from adlb_tpu.balancer import engine as engine_mod

    clock = _Clock()
    monkeypatch.setattr(engine_mod, "time", clock)
    rng = np.random.default_rng(seed)
    seq = [0]
    base = _rand_snaps(rng, nservers, seq, clock.monotonic(), J=J)
    for snap in base.values():
        _spice(rng, snap["tasks"], snap["reqs"], seq)
    stampless = sorted(base)[1]  # re-derived every round, by contract
    base[stampless].pop("stamp")
    base[stampless].pop("task_stamp")
    engines = [mk("array"), mk("array"), mk("py")]
    worlds = [_World(base, True), _World(base, False), _World(base, False)]
    a, t, p = engines
    poked: list = []
    planned = 0
    for rnd in range(rounds):
        at = clock.t
        plans = []
        for e, w in zip(engines, worlds):
            clock.t = at  # every engine reads the same instants
            plans.append(e.round(w.snaps, None))
        assert plans[0] == plans[1] == plans[2], (seed, rnd, plans)
        planned += bool(plans[0][0] or plans[0][1])
        now = clock.monotonic()
        for e, w in zip(engines, worlds):
            e._ledger.sync(w.snaps, now)
            e._ledger.filter_reqs(w.snaps, {}, now)
        for rank in worlds[0].snaps:
            kept = [e._ledger.kept_reqs(rank) for e in engines]
            elig = [e._ledger.elig_tasks(rank) for e in engines]
            assert kept[0] == kept[1] == kept[2], (seed, rnd, rank)
            assert elig[0] == elig[1] == elig[2], (seed, rnd, rank)
            assert all(type(x) is int for tk in elig[0] for x in tk)
        _assert_same_ledger(a._ledger, t._ledger, (seed, rnd))
        _mutate_worlds(rng, worlds, engines, clock, seq, rnd, plans[0][0],
                       poked, J, pokes)
    assert planned >= rounds // 3, "the fuzz hardly planned anything"
    la, lt = a._ledger, t._ledger
    assert la.syncs_by_input["tuples"] == 0 < la.syncs_by_input["array"]
    assert lt.syncs_by_input["array"] == 0 < lt.syncs_by_input["tuples"]
    # the versioned store lets the array arm skip unchanged servers; the
    # plain dict's key compare must come to the same rebuilds
    assert la.rows_synced <= lt.rows_synced


@pytest.fixture(scope="module")
def mesh2():
    return Mesh(np.array(jax.devices()[:2]), axis_names=("s",))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("jobs", [1, MAX_JOBS])
@pytest.mark.parametrize("solver", ["single", "sharded"])
def test_parity_of_the_two_input_shapes(monkeypatch, mesh2, solver, jobs,
                                        seed):
    weights = JOB_WEIGHTS if jobs > 1 else None

    def mk(host_ledger):
        dist = None
        if solver == "sharded":
            dist = DistributedAssignmentSolver(
                types=TYPES, max_tasks_per_server=12, max_requesters=6,
                mesh=mesh2, rounds=64, servers_per_device=4,
                max_jobs=jobs, job_weights=weights)
        return _mk_engine(host_ledger, dist, max_jobs=jobs,
                          job_weights=weights)

    # marks poked in directly go with the single-device solver only: the
    # sharded solver's tuple path (the Python twin's) re-reads a server
    # when the engine's own ledger stamp moved, which a poke does not
    # move — so there the twin lags a poke, at the parent commit too
    _drive_shapes(monkeypatch, mk, 3000 + 10 * jobs + seed, J=jobs,
                  pokes=solver == "single")


def test_task_table_reads_like_the_list_it_stands_for():
    """Whoever indexes, slices, iterates or measures a TaskTable gets
    what the list of tuples gave: Python ints, four wide, five for a
    unit outside the default namespace; appends keep earlier views."""
    from adlb_tpu.balancer.ledger import TaskTable

    units = [(7, 1, 5, 8), (8, 2, -3, 16), (9, 1, 0, 8)]
    tt = TaskTable(np.array(units, np.int64).reshape(-1))  # a frame's flat
    assert len(tt) == 3 and list(tt) == units and tt == units
    assert tt[0] == units[0] and tt[-1] == units[-1] and tt[1:] == units[1:]
    assert tt[:2] == units[:2] and all(
        type(x) is int for tk in tt for x in tk)
    with pytest.raises(IndexError):
        tt[3]
    before = tt.rows
    assert not before.flags.writeable or before.base is not None
    tt.extend(np.array([(10, 3, 1, 8, 2)], np.int64))  # widens to 5
    tt.extend(np.array([(11, 3, 1, 8)], np.int64))
    assert list(tt) == units + [(10, 3, 1, 8, 2), (11, 3, 1, 8)]
    assert before.shape == (3, 4) and before.tolist() == [
        list(u) for u in units]
    assert len(TaskTable(())) == 0 and list(TaskTable([])) == []
    assert not TaskTable(()) and TaskTable(()) == []
