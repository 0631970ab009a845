"""reduce/window.py on hand-made logs: the arithmetic of the end-to-end
metrics and of the steadiness flags. No world, no chip."""

import numpy as np
import pytest

from benchmarks.reduce import records
from benchmarks.reduce.window import Window

WORK = 0.1          # seconds a unit takes
T_END = 110.0       # window [100, 110] with seconds=10
NSERVERS = 2        # producer rank 0 -> server 0; rank 1 remote, rank 2 homed


def worker(rank, deliveries, t_put=50.0):
    """Records of one worker that got one unit per fetch: ``deliveries`` is
    a list of (t_call, t_ret); the unit is done WORK later."""
    units = np.zeros(len(deliveries), dtype=records.UNIT)
    fetches = np.zeros(len(deliveries), dtype=records.FETCH)
    for i, (t_call, t_ret) in enumerate(deliveries):
        units[i] = (rank * 1000 + i, t_put, T_END, int(WORK * 1e6), 7,
                    t_call, t_ret, t_ret + WORK)
        fetches[i] = (t_call, t_ret, 1, 1)
    return units, fetches


def logs_of(tmp_path, per_rank, n_extra_backlog=100):
    """Write the workers' logs, plus a parked pair of units far outside the
    window that keeps the backlog deep (put early, delivered late)."""
    for rank, deliveries in per_rank.items():
        units, fetches = worker(rank, deliveries)
        if rank == 1 and n_extra_backlog:
            late = np.zeros(n_extra_backlog, dtype=records.UNIT)
            for i in range(n_extra_backlog):
                late[i] = (9000 + i, 50.0, T_END, 0, 7, 200.0, 200.001,
                           200.001)
            units = np.concatenate([units, late])
        records.write_worker_log(str(tmp_path), rank, units, fetches)
    n = sum(len(d) for d in per_rank.values()) + n_extra_backlog
    records.write_producer_log(str(tmp_path), n, 50.0, 60.0, T_END)
    return records.read_logs(str(tmp_path))


def steady(rank_offset=0.0, gap=0.01):
    """A worker that fetches for ``gap`` s, works WORK s, from 90 to 115."""
    out, t = [], 90.0 + rank_offset
    while t < 115.0:
        out.append((t, t + gap))
        t += gap + WORK
    return out


def test_steady_window_counts_all_work_over_all_time(tmp_path):
    logs = logs_of(tmp_path, {1: steady(), 2: steady(0.05)})
    w = Window(logs, 10.0, workers=2, nservers=NSERVERS, needs_backlog=True)
    # each worker finishes one unit every 0.11 s: 90.9 in 10 s, two workers
    assert w.units_done in (181, 182)
    assert w.units_per_s == pytest.approx(w.units_done / 10.0)
    assert w.worker_blocked_pct == pytest.approx(100 * 0.01 / 0.11, rel=0.02)
    assert w.match_wait_p95_ms == pytest.approx(10.0, rel=1e-6)
    assert w.fetch_calls == w.units_delivered
    assert not w.unsteady


def test_a_stall_lowers_the_rate_and_raises_the_tail(tmp_path):
    (tmp_path / "a").mkdir()
    base = logs_of(tmp_path / "a", {1: steady(), 2: steady(0.05)})
    w0 = Window(base, 10.0, 2, NSERVERS, True)
    # both workers sit in one fetch from 103 to 105
    stalled = {}
    for rank, off in ((1, 0.0), (2, 0.05)):
        out = []
        for t_call, t_ret in steady(off):
            if 103.0 <= t_call < 105.0:
                continue
            out.append((t_call, t_ret))
        out.append((103.0 + off, 105.0 + off))
        stalled[rank] = sorted(out)
    (tmp_path / "b").mkdir()
    w1 = Window(logs_of(tmp_path / "b", stalled), 10.0, 2, NSERVERS, True)
    assert w1.units_per_s < w0.units_per_s * 0.85
    assert w1.worker_blocked_pct > w0.worker_blocked_pct + 15
    # 2 of ~150 units waited 2 s: the p95 does not see them, the max does
    assert w1.match_wait_s.max() == pytest.approx(2.0)
    many = {r: d + [(103.0 + 0.001 * k, 105.0) for k in range(20)]
            for r, d in stalled.items()}
    (tmp_path / "c").mkdir()
    w2 = Window(logs_of(tmp_path / "c", many), 10.0, 2, NSERVERS, True)
    assert w2.match_wait_p95_ms > 1000 > w0.match_wait_p95_ms


def test_units_outside_the_window_are_not_counted(tmp_path):
    inside = [(101.0, 101.01), (105.0, 105.01)]
    outside = [(80.0, 80.01), (99.85, 99.86),     # done at 99.96: before
               (109.95, 109.96),                  # done at 110.06: after
               (120.0, 120.01)]
    logs = logs_of(tmp_path, {1: sorted(inside + outside)})
    w = Window(logs, 10.0, 1, NSERVERS, True)
    assert w.units_done == 2
    assert w.units_per_s == pytest.approx(0.2)
    # delivered in the window: the two inside and the one at 109.96
    assert w.units_delivered == 3
    # blocked time is clipped to the window: 3 fetches of 10 ms
    assert w.worker_blocked_pct == pytest.approx(100 * 0.03 / 10.0)


def test_wait_is_from_put_when_the_worker_was_parked_first(tmp_path):
    units, fetches = worker(1, [(100.0, 104.0)], t_put=103.5)
    records.write_worker_log(str(tmp_path), 1, units, fetches)
    records.write_producer_log(str(tmp_path), 1, 50.0, 60.0, T_END)
    w = Window(records.read_logs(str(tmp_path)), 10.0, 1, NSERVERS, False)
    # parked 3.5 s with nothing to get: not latency; 0.5 s unit-and-worker
    assert w.match_wait_p95_ms == pytest.approx(500.0)
    assert w.worker_blocked_pct == pytest.approx(40.0)


@pytest.mark.parametrize("case,flag", [
    ("late_remote", "planner-not-warm"),
    ("no_remote", "planner-not-warm"),
    ("backlog_empty", "backlog-empty"),
])
def test_an_unsteady_window_is_flagged(tmp_path, case, flag):
    if case == "late_remote":    # first remote (rank 1) delivery at 101
        per_rank = {1: [(100.9, 101.0)], 2: steady()}
        extra = 100
    elif case == "no_remote":    # only the worker homed with the producer
        per_rank = {2: steady()}
        extra = 0
    else:                        # every put delivered before the window ends
        per_rank = {1: steady(), 2: steady(0.05)}
        extra = 0
    if case == "backlog_empty":
        # puts trail deliveries: each unit put just before it is delivered
        for rank, deliveries in per_rank.items():
            units, fetches = worker(rank, deliveries)
            units["t_put"] = units["t_ret"] - 0.001
            records.write_worker_log(str(tmp_path), rank, units, fetches)
        records.write_producer_log(str(tmp_path), 1, 50.0, 60.0, T_END)
        logs = records.read_logs(str(tmp_path))
    else:
        logs = logs_of(tmp_path, per_rank, n_extra_backlog=extra)
        if case == "no_remote":
            # keep the backlog deep so only the planner flag can fire
            logs.units["t_put"][:] = 50.0
    w = Window(logs, 10.0, 2, NSERVERS, needs_backlog=True)
    assert flag in w.flags and w.unsteady
    assert "unsteady" in w.describe()


def test_no_producer_record_is_an_error(tmp_path):
    units, fetches = worker(1, [(100.0, 100.01)])
    records.write_worker_log(str(tmp_path), 1, units, fetches)
    with pytest.raises(ValueError):
        Window(records.read_logs(str(tmp_path)), 10.0, 1, NSERVERS, True)
