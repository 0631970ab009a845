"""Tests for the jitted global assignment solve and the end-to-end TPU
balancer mode (snapshot -> solve -> plan -> enactment)."""

from adlb_tpu.api import run_world
from adlb_tpu.balancer.solve import AssignmentSolver
from adlb_tpu.runtime.world import Config, WorldSpec
from adlb_tpu.types import (
    ADLB_DONE_BY_EXHAUSTION,
    ADLB_NO_MORE_WORK,
    ADLB_SUCCESS,
)

T1, T2 = 1, 2


def _world(ns=2):
    return WorldSpec(nranks=4 + ns, nservers=ns, types=(T1, T2))


def test_solver_basic_match():
    s = AssignmentSolver(types=(T1, T2), max_tasks=8, max_requesters=4)
    snapshots = {
        10: {"tasks": [(100, T1, 5, 1)], "reqs": []},
        11: {"tasks": [], "reqs": [(0, 1, [T1])]},
    }
    pairs = s.solve(snapshots, None)
    assert pairs == [(10, 100, 11, 0, 1)]


def test_solver_type_mask_respected():
    s = AssignmentSolver(types=(T1, T2), max_tasks=8, max_requesters=4)
    snapshots = {
        10: {"tasks": [(100, T2, 99, 1)], "reqs": []},
        11: {"tasks": [], "reqs": [(0, 1, [T1])]},
    }
    assert s.solve(snapshots, None) == []
    # any-type requester (None mask) takes it
    snapshots[11]["reqs"] = [(0, 2, None)]
    assert s.solve(snapshots, None) == [(10, 100, 11, 0, 2)]


def test_solver_priority_wins():
    s = AssignmentSolver(types=(T1,), max_tasks=8, max_requesters=4)
    snapshots = {
        10: {"tasks": [(1, T1, 1, 1), (2, T1, 9, 1), (3, T1, 5, 1)], "reqs": []},
        11: {"tasks": [], "reqs": [(0, 1, [T1])]},
    }
    pairs = s.solve(snapshots, None)
    assert pairs == [(10, 2, 11, 0, 1)]  # highest priority task chosen


def test_a_device_solve_has_the_worlds_shape_whoever_has_reported():
    """A world's first rounds see only the servers that have reported.
    Their device solve is padded to the world's server count, so it runs
    the program every later round runs and not one of its own shape, and
    plans what the unpadded solve plans (dict and ledger-view paths)."""
    import random

    from adlb_tpu.balancer.engine import PlanEngine

    rng = random.Random(7)
    snapshots = {
        10: {"tasks": [(i, rng.choice((T1, T2)), rng.randrange(9), 1)
                       for i in range(8)], "reqs": []},
        12: {"tasks": [(50 + i, T1, i, 1) for i in range(3)],
             "reqs": [(r, r, rng.choice(([T1], [T2], None)))
                      for r in range(4)]},
    }
    kw = dict(types=(T1, T2), max_tasks=8, max_requesters=4,
              host_threshold_reqs=0)
    plain, padded = AssignmentSolver(**kw), AssignmentSolver(nservers=5, **kw)
    shapes = []
    fn = padded._device_assign()
    padded._device_fn = lambda *a: shapes.append(
        [x.shape for x in a]) or fn(*a)
    want = plain.solve(snapshots, None)
    assert want and padded.solve(snapshots, None) == want
    assert shapes == [[(40,), (40,), (20, 2), (20,)]]
    # more servers than the world began with (scale-out): no padding
    more = {r: {"tasks": [], "reqs": []} for r in range(20, 26)}
    more.update(snapshots)
    assert padded.solve(more, None) == plain.solve(more, None)
    assert shapes[-1] == [(64,), (64,), (32, 2), (32,)]
    # the engine hands its solver the count, and the view path pads too
    engine = PlanEngine(nservers=5, backend="xla", **kw)
    assert engine.solver.nservers == 5
    engine.solver._device_fn = padded._device_fn
    for snap in snapshots.values():
        snap.update(stamp=1.0, nbytes=8, consumers=1)
    engine.round(dict(snapshots), WorldSpec(nranks=9, nservers=5,
                                            types=(T1, T2)))
    assert engine.solver.device_solve_count == 1
    assert shapes[-1] == [(40,), (40,), (20, 2), (20,)]


def test_solver_many_to_many_no_double_assignment():
    s = AssignmentSolver(types=(T1,), max_tasks=16, max_requesters=16)
    snapshots = {
        10: {"tasks": [(i, T1, i, 1) for i in range(10)], "reqs": []},
        11: {"tasks": [], "reqs": [(r, r, [T1]) for r in range(6)]},
    }
    pairs = s.solve(snapshots, None)
    assert len(pairs) == 6
    seqnos = [p[1] for p in pairs]
    assert len(set(seqnos)) == 6  # no task assigned twice
    assert set(seqnos) == set(range(4, 10))  # the 6 highest priorities move


def test_tpu_mode_end_to_end():
    """Full world in balancer=tpu mode: untargeted cross-server movement is
    planner-driven; answers flow back; known answer checked."""
    NTASK = 30

    def app(ctx):
        if ctx.rank == 0:
            for i in range(NTASK):
                assert ctx.put(str(i).encode(), T1, work_prio=i) == ADLB_SUCCESS
            total = 0
            for _ in range(NTASK):
                rc, r = ctx.reserve([T2])
                assert rc == ADLB_SUCCESS
                rc, buf = ctx.get_reserved(r.handle)
                total += int(buf)
            ctx.set_problem_done()
            return total
        n = 0
        while True:
            rc, r = ctx.reserve([T1])
            if rc != ADLB_SUCCESS:
                assert rc in (ADLB_NO_MORE_WORK, ADLB_DONE_BY_EXHAUSTION)
                return n
            rc, buf = ctx.get_reserved(r.handle)
            ctx.put(str(int(buf) * 3).encode(), T2, target_rank=0)
            n += 1

    res = run_world(
        4, 3, [T1, T2], app,
        cfg=Config(balancer="tpu", balancer_max_tasks=64, balancer_max_requesters=16),
        timeout=300.0,
    )
    assert res.app_results[0] == 3 * sum(range(NTASK))
    # workers collectively processed everything
    assert sum(res.app_results[r] for r in range(1, 4)) == NTASK


def test_migration_hysteresis():
    """Fair-share migrations fire only below half share: servers hovering
    near their share must not shuffle inventory (a GIL/message tax on
    already-balanced compute-bound workloads), while a starved server
    still gets supplied immediately."""
    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=16, max_requesters=4)
    # near-balanced: 5 vs 4 with equal consumers -> no moves
    snaps = {
        10: {"tasks": [(i, T1, 1, 8) for i in range(5)], "reqs": [],
             "consumers": 1},
        11: {"tasks": [(i, T1, 1, 8) for i in range(4)], "reqs": [],
             "consumers": 1},
    }
    _, migs = eng.round(snaps, None)
    assert migs == []
    # starved: 8 vs 0 -> the empty server is under half share and is
    # supplied ahead of demand (anticipatory pre-positioning; the
    # round-4 experiment of gating this on recent parking was reverted —
    # see engine._plan_migrations)
    eng2 = PlanEngine(types=(T1,), max_tasks=16, max_requesters=4)
    snaps2 = {
        10: {"tasks": [(i, T1, 1, 8) for i in range(8)], "reqs": [],
             "consumers": 1},
        11: {"tasks": [], "reqs": [], "consumers": 1},
    }
    _, migs2 = eng2.round(snaps2, None)
    assert migs2 and migs2[0][0] == 10 and migs2[0][1] == 11


def test_hungry_gates_put_snapshots(monkeypatch):
    """A world whose cross-rank traffic is all TARGETED (gfmc's collector
    shape: answers only ever arrive as targeted puts) must not pay an
    event snapshot per put — only the parked-reserve events plus the slow
    idle heartbeat remain."""
    from adlb_tpu.runtime import server as srv

    calls = {"n": 0}
    orig = srv.Server._send_snapshot

    def counting(self, reqs_only=False):
        calls["n"] += 1
        orig(self, reqs_only=reqs_only)

    monkeypatch.setattr(srv.Server, "_send_snapshot", counting)
    NTASK = 300

    def app(ctx):
        import time as _t

        if ctx.rank == 0:
            for i in range(NTASK):
                # targeted straight at rank 1: matches at its home server,
                # never enters a balancer snapshot
                assert (
                    ctx.put(str(i).encode(), T1, work_prio=1, target_rank=1)
                    == ADLB_SUCCESS
                )
            rc, r = ctx.reserve([T2])  # consumer's all-done ack
            assert rc == ADLB_SUCCESS
            ctx.get_reserved(r.handle)
            ctx.set_problem_done()
            return 0
        # let the producer run ahead so consuming never parks (each park
        # legitimately sends an ungated event snapshot, like steal's RFR)
        _t.sleep(0.5)
        n = 0
        for _ in range(NTASK):
            rc, r = ctx.reserve([T1])
            assert rc == ADLB_SUCCESS
            ctx.get_reserved(r.handle)
            n += 1
        ctx.put(b"done", T2, target_rank=0)
        rc, _ = ctx.reserve([T1])  # parks until NO_MORE_WORK
        assert rc != ADLB_SUCCESS
        return n

    res = run_world(
        2, 2, [T1, T2], app,
        cfg=Config(balancer="tpu", balancer_max_tasks=64,
                   balancer_max_requesters=16),
        timeout=300.0,
    )
    assert res.app_results[1] == NTASK
    # ungated, this would be >= NTASK/2 (150) snapshots — one per couple
    # of puts; gated it is a few parks + the slow idle heartbeat. The
    # heartbeat count scales with wall-clock, and under host load the
    # world runs 2-3x longer (measured: the old < 40 bound sat exactly
    # at the boundary ~half the time on a busy host, at this PR's base
    # commit too) — 60 keeps the full gated/ungated discrimination
    # without the load sensitivity.
    assert calls["n"] < 60, calls["n"]


def test_hungry_tracker_drop_arms_shrink():
    """An ended source's parked types must stop being 'hungry' after the
    grace period even if no further snapshots arrive (DS_END path)."""
    from adlb_tpu.balancer.hungry import HungryTracker

    tr = HungryTracker(shrink_grace=0.0)
    out = tr.update(10, [(0, 1, [T1])])
    assert out is not None and out[0] is True and out[1] == [T1]
    tr.drop(10)
    import time as _t

    flushed = tr.flush(_t.monotonic() + 1.0)
    assert flushed is not None
    hungry, req_types, grew = flushed
    assert hungry is False and not grew


def test_solve_gated_when_supply_is_local_only():
    """A parked requester whose wanted type has supply only on its OWN
    server must not trigger the global solve: the data plane's immediate
    local matching covers it, and the solve's same-server pairs are
    dropped anyway. Cross-server supply must still solve."""
    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=16, max_requesters=4)
    calls = []
    inner = eng.solver.solve
    eng.solver.solve = lambda *a, **k: (calls.append(1), inner(*a, **k))[1]
    local_only = {
        10: {"tasks": [(1, T1, 5, 8)], "reqs": [(0, 1, [T1])],
             "consumers": 1},
    }
    matches, _ = eng.round(local_only, None)
    assert matches == [] and calls == []
    cross = {
        10: {"tasks": [(1, T1, 5, 8)], "reqs": [], "consumers": 1},
        11: {"tasks": [], "reqs": [(0, 1, [T1])], "consumers": 1},
    }
    matches, _ = eng.round(cross, None)
    assert calls and matches == [(10, 1, 11, 0, 1)]


def test_migration_inflow_credited_until_fresh_snapshot():
    """Units planned toward a destination count as its inventory until the
    destination ships a FRESH task snapshot — otherwise every round chains
    another phantom top-up to a server that is already being fed."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=64, max_requesters=4)
    # the transit window and TTL compare against real wall-clock; pin
    # them so a CI scheduler pause between rounds cannot expire the
    # credit mid-test
    eng.INFLOW_MIN_AGE = 1e9
    eng.INFLOW_TTL = 1e9
    eng.PUMP_INTERVAL = 0.0  # credit semantics under test, not pacing
    t0 = _time.monotonic()
    snaps = {
        10: {"tasks": [(i, T1, 1, 8) for i in range(40)], "reqs": [],
             "consumers": 1, "stamp": t0, "task_stamp": t0},
        11: {"tasks": [], "reqs": [], "consumers": 1, "stamp": t0,
             "task_stamp": t0},
    }
    _, migs = eng.round(snaps, None)
    assert migs, "starved server must be supplied"
    # same stale snapshots again: the in-flight batch covers 11's need
    _, migs2 = eng.round(snaps, None)
    assert migs2 == []
    # a fresh-but-instant snapshot (captured before the batch could have
    # LANDED) must not wipe the credit either
    t1 = _time.monotonic()
    snaps[11] = {"tasks": [], "reqs": [], "consumers": 1, "stamp": t1,
                 "task_stamp": t1}
    snaps[10] = dict(snaps[10], stamp=t1, task_stamp=t1)
    _, migs2b = eng.round(snaps, None)
    assert migs2b == []
    # past the transit window, a fresh drained snapshot clears the credit
    # -> supply again (pin the window instead of sleeping through it)
    eng.INFLOW_MIN_AGE = 0.0
    t2 = _time.monotonic()
    snaps[11] = {"tasks": [], "reqs": [], "consumers": 1, "stamp": t2,
                 "task_stamp": t2}
    snaps[10] = dict(snaps[10], stamp=t2, task_stamp=t2)
    _, migs3 = eng.round(snaps, None)
    assert migs3


def test_migration_window_grows_on_fast_drain():
    """A destination that keeps draining its top-ups faster than the
    re-plan round trip gets a doubling transfer window, so batch sizes
    converge on the drain rate instead of trickling fixed-size refills
    (batches are O(1) messages regardless of size)."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=512, max_requesters=4)
    # the growth criterion is "re-triggered within the window"; pin it so
    # a slow CI machine cannot flip growth into decay mid-test, and drop
    # the in-flight transit crediting (tested elsewhere) so each fresh
    # snapshot re-triggers immediately
    sizes = _run_four_topups(eng, dest_parked=True)
    assert sizes[-1] > sizes[0], sizes
    assert sizes == sorted(sizes), sizes


def _run_four_topups(eng, dest_parked: bool):
    """Four quick pump rounds against a deep source and a dest holding a
    couple of units (fully empty would hit the starved full-share path).
    ``dest_parked`` controls whether the dest has a parked requester —
    window growth is reserved for destinations whose workers actually
    outpace their supply. Returns the per-round shipped batch sizes."""
    import time as _time

    eng.LOOK_GROW_WINDOW = 1e9
    eng.INFLOW_MIN_AGE = 0.0
    eng.PUMP_INTERVAL = 0.0  # window mechanics under test, not pacing
    sizes = []
    for i in range(4):
        t = _time.monotonic()
        snaps = {
            10: {"tasks": [(1000 * i + j, T1, 1, 8) for j in range(400)],
                 "reqs": [], "consumers": 1, "stamp": t, "task_stamp": t},
            11: {"tasks": [(1000 * i + 900 + j, T1, 1, 8) for j in range(2)],
                 "reqs": [(5, i + 1, [T1])] if dest_parked else [],
                 "consumers": 1, "stamp": t, "task_stamp": t},
        }
        _, migs = eng.round(snaps, None)
        if dest_parked:
            assert migs and migs[0][1] == 11
        sizes.append(sum(len(q) for _, _, q, _ in migs))
    return sizes


def test_window_growth_gated_on_recent_parking():
    """A destination fed while its workers never measurably wait keeps
    its window at the floor: bursty-but-balanced pools must not have
    their transfer batches inflated (the round-4 churn bound — the feed
    itself stays on, see engine._plan_migrations). An already-inflated
    window DECAYS under gated triggers instead of staying pinned."""
    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=512, max_requesters=8)
    sizes = _run_four_topups(eng, dest_parked=False)
    assert all(s > 0 for s in sizes), sizes  # still fed (pre-positioning)
    assert eng._window(11) == float(eng.LOOKAHEAD), eng._look
    # parked phase: the window inflates on quick re-triggers
    eng2 = PlanEngine(types=(T1,), max_tasks=512, max_requesters=8)
    _run_four_topups(eng2, dest_parked=True)
    grown = eng2._window(11)
    assert grown > eng2.LOOKAHEAD, eng2._look
    # quiet phase (stale parked stamp): still fed, but the window decays
    eng2.PARK_RECENT = -1.0  # make the last park immediately "old"
    sizes2 = _run_four_topups(eng2, dest_parked=False)
    assert all(s > 0 for s in sizes2), sizes2
    assert eng2._window(11) < grown, eng2._look


def test_starved_destination_gets_full_share_immediately():
    """A destination with a parked requester, zero inventory, and zero
    inflow (hotspot's empty servers) must receive its full fair share in
    ONE batch — not window-sized refills that ramp from the lookahead
    floor while its workers idle a re-plan round trip at a time (the
    round-2 hotspot regression)."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=512, max_requesters=8)
    t = _time.monotonic()
    snaps = {
        10: {"tasks": [(j, T1, 1, 8) for j in range(400)], "reqs": [],
             "consumers": 2, "stamp": t, "task_stamp": t},
        11: {"tasks": [], "reqs": [(5, 1, [T1])], "consumers": 2,
             "stamp": t, "task_stamp": t},
    }
    matches, migs = eng.round(snaps, None)
    shipped = sum(len(q) for _, dest, q, _ in migs if dest == 11)
    # one unit goes via the match; of the remaining 399 the source keeps
    # its own ceil-share (200) and ships the rest. The old window-capped
    # first batch was LOOKAHEAD*consumers = 16.
    assert len(matches) == 1 and shipped == 199, (matches, migs)
    # the window is seeded at the shipped scale: a follow-up deficit tops
    # up at fair-share size instead of re-ramping from the floor
    assert eng._window(11) >= 99, eng._look
    # an empty server whose workers are all mid-compute (no parked
    # requester — tsp's transient dips) stays on the window-capped path
    eng2 = PlanEngine(types=(T1,), max_tasks=512, max_requesters=8)
    snaps2 = {
        10: {"tasks": [(j, T1, 1, 8) for j in range(400)], "reqs": [],
             "consumers": 2, "stamp": t, "task_stamp": t},
        11: {"tasks": [], "reqs": [], "consumers": 2, "stamp": t,
             "task_stamp": t},
    }
    _, migs2 = eng2.round(snaps2, None)
    shipped2 = sum(len(q) for _, dest, q, _ in migs2 if dest == 11)
    assert 0 < shipped2 <= eng2.LOOKAHEAD * 2, migs2


def test_migration_spares_locally_demanded_unit():
    """With the solve gated off (supply local-only), migration planning
    must not ship away the unit a locally parked requester wants."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1, T2), max_tasks=16, max_requesters=4)
    t0 = _time.monotonic()
    snaps = {
        10: {"tasks": [(1, T1, 5, 8), (2, T1, 4, 8), (3, T2, 3, 8)],
             "reqs": [(0, 1, [T2])], "consumers": 1, "stamp": t0,
             "task_stamp": t0},
        11: {"tasks": [], "reqs": [], "consumers": 1, "stamp": t0,
             "task_stamp": t0},
    }
    matches, migs = eng.round(snaps, None)
    assert matches == []  # T2 supply is local to its demander: no solve
    moved = {q for _, _, qs, _ in migs for q in qs}
    assert 3 not in moved, (matches, migs)


def test_pump_knobs_config_wiring():
    """The adaptive-pump constants are per-instance engine keywords, not
    just class constants, and the engine checks them."""
    import pytest

    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=16, max_requesters=4,
                     lookahead=3, look_max=64, grow_window=0.5,
                     inflow_ttl=9.0, inflow_min_age=0.2)
    assert (eng.LOOKAHEAD, eng.LOOK_MAX, eng.LOOK_GROW_WINDOW,
            eng.INFLOW_TTL, eng.INFLOW_MIN_AGE) == (3, 64, 0.5, 9.0, 0.2)
    # class defaults untouched
    assert PlanEngine.LOOKAHEAD == 8
    def mk(**kw):
        return PlanEngine(types=(T1,), max_tasks=16, max_requesters=4, **kw)

    with pytest.raises(ValueError):
        mk(lookahead=-1)
    # look_max below the lookahead floor would let window decay pin a
    # destination's need to 0, silently disabling migrations to it
    with pytest.raises(ValueError):
        mk(look_max=0)
    with pytest.raises(ValueError):
        mk(lookahead=16, look_max=4)


def test_matched_requester_not_double_withheld():
    """A requester the solve matched cross-server this round is consumed
    by the match; withholding a second local unit for it would
    double-reserve supply and starve migration sources."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine

    t0 = _time.monotonic()
    snaps = {
        10: {"tasks": [(1, T1, 1, 8), (2, T1, 1, 8)],
             "reqs": [(5, 1, [T1])], "consumers": 0, "stamp": t0,
             "task_stamp": t0},
        11: {"tasks": [], "reqs": [], "consumers": 1, "stamp": t0,
             "task_stamp": t0},
    }
    filtered = {
        r: {"tasks": s["tasks"], "reqs": s["reqs"]} for r, s in snaps.items()
    }
    # requester (10, 5, 1) was matched cross-server this round: both units
    # stay eligible for the starved dest
    eng = PlanEngine(types=(T1,), max_tasks=64, max_requesters=8)
    migs = eng._plan_migrations(snaps, filtered, {}, t0,
                                matched_reqs={(10, 5, 1)})
    moved = {q for _, _, qs, _ in migs for q in qs}
    assert moved == {1, 2}, migs
    # unmatched, the requester still protects one locally-matchable unit
    eng2 = PlanEngine(types=(T1,), max_tasks=64, max_requesters=8)
    migs2 = eng2._plan_migrations(snaps, filtered, {}, t0)
    moved2 = {q for _, _, qs, _ in migs2 for q in qs}
    assert len(moved2) == 1, migs2
    # LOCAL pairs (dropped from matches, unit in planned_away) consume
    # their requester too: withholding a second unit for it would starve
    # the migration path end-to-end through round()
    eng3 = PlanEngine(types=(T1,), max_tasks=64, max_requesters=8)
    snaps3 = {
        10: {"tasks": [(1, T1, 5, 8), (2, T1, 4, 8), (3, T1, 3, 8)],
             "reqs": [(9, 7, [T1])], "consumers": 0, "stamp": t0,
             "task_stamp": t0},
        11: {"tasks": [], "reqs": [(5, 1, [T1])], "consumers": 0,
             "stamp": t0, "task_stamp": t0},
        12: {"tasks": [], "reqs": [], "consumers": 1, "stamp": t0,
             "task_stamp": t0},
    }
    matches3, migs3 = eng3.round(snaps3, None)
    # one local pair (dropped) + one cross match leave exactly one unit;
    # it must reach the starved consumer on 12, not be double-withheld
    assert len(matches3) == 1 and matches3[0][2] == 11, matches3
    moved3 = {q for _, _, qs, _ in migs3 for q in qs}
    assert moved3, (matches3, migs3)


def test_pump_precheck_admits_rank_with_only_planned_away_inventory():
    """ADVICE r4: a req-parked destination whose stale snapshot still
    lists units the plan ledger already moved away must ADMIT the
    scarce+concentrated pump pre-check — its raw count is nonzero but it
    is starved NOW. Before the fix the pump stayed gated a whole
    snapshot generation after the opening burst was planned out."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine

    eng = PlanEngine(types=(T1,), max_tasks=64, max_requesters=8)
    t0 = _time.monotonic()
    snaps = {
        # 4 units < 5 consumers (scarce), 3 of 4 on rank 10 (concentrated)
        10: {"tasks": [(j, T1, 1, 8) for j in range(3)],
             "reqs": [], "consumers": 3, "stamp": t0, "task_stamp": t0},
        # rank 11: one consumer parked; its snapshot still lists unit 99
        # but the ledger says 99 was planned away AFTER this task view
        11: {"tasks": [(99, T1, 1, 8)], "reqs": [(5, 1, [T1])],
             "consumers": 2, "stamp": t0, "task_stamp": t0},
    }
    eng._planned_tasks[(11, 99)] = t0 + 1.0  # planned after the view
    assert eng._maybe_imbalanced(snaps), (
        "pre-check must admit: rank 11 is req-parked and every listed "
        "unit is planned away"
    )
    # sanity: with the unit genuinely eligible (ledger older than the
    # view) the same shape is NOT admitted via the planned-away clause
    eng2 = PlanEngine(types=(T1,), max_tasks=64, max_requesters=8)
    eng2._planned_tasks[(11, 99)] = t0 - 1.0
    assert not eng2._maybe_imbalanced(snaps)


def test_fully_stale_migration_batch_still_clears_credit(monkeypatch):
    """Round-4 regression: a planner migration whose every unit is stale
    at enactment must STILL result in the destination acking the batch
    id, clearing the planner's in-flight credit. Before the fix the
    source silently dropped such batches and the phantom credit made the
    destination look fed (solve suppressed + pump skipped) until the
    TTLs expired — whole worker pools parked ~180 ms mid-run.

    The TTL and stamp fallbacks are pinned OFF so only the exact
    ack-clearing path can clear the forged credit."""
    import time as _time

    from adlb_tpu.balancer.engine import PlanEngine

    monkeypatch.setattr(PlanEngine, "INFLOW_TTL", 1e9)
    monkeypatch.setattr(PlanEngine, "INFLOW_MIN_AGE", 1e9)

    holder = {}
    orig = PlanEngine.round

    def forging(self, snapshots, world=None):
        holder["eng"] = self
        matches, migs = orig(self, snapshots, world)
        servers = sorted(snapshots)
        if not holder.get("forged") and len(servers) >= 2:
            src, dest = servers[0], servers[1]
            mid = self._mig_next
            self._mig_next += 1
            # credit exactly as _plan_migrations would record it
            self._planned_in.setdefault(dest, []).append(
                (_time.monotonic(), 5, mid, src, frozenset({T1}))
            )
            migs = list(migs) + [(src, dest, [987654321], mid)]
            holder["forged"] = dest
        return matches, migs

    monkeypatch.setattr(PlanEngine, "round", forging)

    def app(ctx):
        deadline = _time.monotonic() + 8.0
        ok = False
        while _time.monotonic() < deadline:
            eng = holder.get("eng")
            dest = holder.get("forged")
            if dest is not None and eng is not None:
                live = eng._planned_in.get(dest)
                if not live:
                    ok = True  # ack arrived; credit cleared exactly
                    break
            _time.sleep(0.05)
        if ctx.rank == 0:
            ctx.set_problem_done()
        return ok

    res = run_world(
        2, 2, [T1], app,
        cfg=Config(balancer="tpu", balancer_max_tasks=16,
                   balancer_max_requesters=4),
        timeout=60.0,
    )
    assert res.app_results[0] or res.app_results[1], (
        "forged fully-stale migration credit was never cleared by the "
        "destination's ack"
    )


def test_sidecar_survives_dead_destination():
    """End-of-world race: a server closes its listener before the sidecar
    finishes broadcasting/planning to it. The sidecar must mark the
    destination ended and drain out — not die with an unhandled thread
    exception (observed as BrokenPipe->ConnectionRefused tracebacks in
    bench teardown)."""
    from adlb_tpu.balancer.sidecar import run_sidecar
    from adlb_tpu.runtime.messages import Tag, msg

    world = _world(ns=2)
    s0, s1 = world.server_ranks

    class DeadEp:
        """One SS_STATE with a parked requester (forces a HUNGRY
        broadcast), then silence; every send is refused."""

        def __init__(self):
            self.frames = [
                msg(Tag.SS_STATE, s0, tasks_flat=[100, T1, 5, 8],
                    reqs_flat=[0, 1, 1, T1], nbytes=8, consumers=1),
            ]
            self.sends = 0

        def recv(self, timeout=None):
            return self.frames.pop(0) if self.frames else None

        def send(self, dest, m, **kw):
            self.sends += 1
            raise ConnectionRefusedError(111, "refused")

    ep = DeadEp()
    cfg = Config(balancer="tpu", balancer_min_gap=0.0)
    rounds = run_sidecar(world, cfg, ep)["rounds"]  # must return, not raise
    assert ep.sends >= 1  # it really tried the dead destinations
    # the refused broadcast popped the only snapshot, so no solve ran
    assert rounds == 0


def test_sidecar_survives_plan_frame_to_dead_holder():
    """Same teardown race on the PLAN paths: the HUNGRY broadcast goes
    through, the solve plans a match, and THEN the holder's listener is
    gone — the plan-frame send must mark it ended (skipping its other
    plan frames) and drain, not raise."""
    from adlb_tpu.balancer.sidecar import run_sidecar
    from adlb_tpu.runtime.messages import Tag, msg

    world = _world(ns=2)
    s0, s1 = world.server_ranks

    class PlanDeadEp:
        def __init__(self):
            # Batch 1: holder s0 has two units; requester home s1 has two
            # parked requesters -> the solve emits two matches for holder
            # s0 (the None ends the batch so the solve runs). Batch 2:
            # s1 finishes normally via DS_END, letting the loop drain.
            self.script = [
                msg(Tag.SS_STATE, s0,
                    tasks_flat=[100, T1, 5, 8, 101, T1, 4, 8],
                    reqs_flat=[], nbytes=16, consumers=1),
                msg(Tag.SS_STATE, s1, tasks_flat=[],
                    reqs_flat=[0, 1, 1, T1, 1, 2, 1, T1],
                    nbytes=0, consumers=2),
                None,
                msg(Tag.DS_END, s1),
            ]
            self.plan_sends = 0
            self.hungry_sends = 0

        def recv(self, timeout=None):
            return self.script.pop(0) if self.script else None

        def send(self, dest, m, **kw):
            if m.tag is Tag.SS_PLAN_MATCH or m.tag is Tag.SS_PLAN_MIGRATE:
                self.plan_sends += 1
                raise ConnectionRefusedError(111, "refused")
            self.hungry_sends += 1  # HUNGRY broadcasts still deliver

        def close(self):
            pass

    ep = PlanDeadEp()
    cfg = Config(balancer="tpu", balancer_min_gap=0.0)
    rounds = run_sidecar(world, cfg, ep)["rounds"]  # must return, not raise
    assert rounds >= 1  # the solve really ran
    assert ep.hungry_sends >= 1
    # first plan frame to the dead holder ends it; its second match is
    # skipped rather than re-attempted
    assert ep.plan_sends == 1, ep.plan_sends
