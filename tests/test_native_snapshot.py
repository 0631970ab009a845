"""The native daemon's snapshot for the planner (``serverd.cpp``:
``send_snapshot`` over ``snap_index_``) ships what a walk of the whole queue
sorted by ``(prio desc, seqno asc)`` would: the unpinned, untargeted units,
first ``balancer_max_tasks`` of them, in that order.

One world of two tpu-mode daemons among ranks the test plays
(``test_native_flight.Planned``), with ``balancer_max_tasks`` 4. App 0 puts
into server 2 at mixed priorities, some of it targeted; then a plan's match
pins the front, the pin is given back, a migration takes units from the
middle and from beyond the first four, fetches take the front, and a
thousand units come and go behind a front that stays. After each step the
holder's next ``SS_STATE`` is compared with a reference built in Python
from the steps alone.
"""

import os
import subprocess
import sys
import time

import pytest

from adlb_tpu.runtime.messages import Tag, msg
from adlb_tpu.types import ADLB_SUCCESS
from tests.test_native_flight import (
    APP_PARKS, APP_PUTS, HOLDER, HOME, PLANNER, T, Planned, artefacts)
from tests.test_native_flight import pytestmark  # noqa: F401  (needs g++)

U = 2  # a second type: app 0's fetches of T pass its units by
V = 3  # a third, for a front that stays while T comes and goes behind it
CHURN = 1100  # more dead entries than snap_index_compact lets pile up
K = 4
PAYLOAD_LEN = 8  # Planned.put's payload


class Reference:
    """The holder's queue as the steps leave it: seqno -> [type, prio,
    target, pinned]. The daemon numbers its units 1, 2, ... as they
    arrive."""

    def __init__(self):
        self.units = {}
        self.next_seqno = 1

    def put(self, work_type, prio, target=-1):
        self.units[self.next_seqno] = [work_type, prio, target, False]
        self.next_seqno += 1

    def front(self, work_type=None):
        """The seqno a fetch of ``work_type`` (any when None) takes."""
        return self.snapshot(1 << 30, work_type)[0::4][0]

    def snapshot(self, k=K, work_type=None):
        live = sorted(
            (-prio, seqno, wt) for seqno, (wt, prio, target, pinned)
            in self.units.items()
            if not pinned and target < 0 and work_type in (None, wt))
        flat = []
        for neg_prio, seqno, wt in live[:k]:
            flat += [seqno, wt, -neg_prio, PAYLOAD_LEN]
        return flat


def holder_snapshot(w):
    """The holder's ``tasks_flat`` as of now. An ``SS_HUNGRY`` that says
    the wanted set grew asks for a snapshot, and keeps the fast cadence
    on; the second ``SS_STATE`` of the holder after it is taken, so that
    one sent before the step and still in flight is passed by."""
    time.sleep(0.1)
    while w.eps[PLANNER].recv(0.0) is not None:
        pass
    w.eps[PLANNER].send(HOLDER, msg(
        Tag.SS_HUNGRY, PLANNER, hungry=1, req_types=[T, U], grew=1))
    seen = 0
    while True:
        m = w.expect(PLANNER, Tag.SS_STATE)
        if m.src == HOLDER:
            seen += 1
            if seen == 2:
                return [int(v) for v in m.tasks_flat]


def fetch(w, ref, work_type):
    """App 0 reserves ``work_type`` at its home, the holder, and gets it."""
    w.rqseqno += 1
    w.eps[APP_PUTS].send(HOLDER, msg(
        Tag.FA_RESERVE, APP_PUTS, rqseqno=w.rqseqno, req_types=[work_type],
        hang=True))
    resp = w.expect(APP_PUTS, Tag.TA_RESERVE_RESP)
    assert resp.rc == ADLB_SUCCESS
    seqno = int(resp.handle[0])
    assert seqno == ref.front(work_type)
    w.eps[APP_PUTS].send(HOLDER, msg(Tag.FA_GET_RESERVED, APP_PUTS,
                                     seqno=seqno))
    assert w.expect(APP_PUTS, Tag.TA_GET_RESERVED_RESP).rc == ADLB_SUCCESS
    del ref.units[seqno]


def _puts(w, ref):
    for wt, prio, target in [
            (T, 0, -1), (T, 3, -1), (U, -2, -1), (U, 3, -1),
            (U, 7, APP_PUTS), (T, 0, -1), (T, -5, -1), (T, 3, -1),
            (U, 0, -1), (T, 3, -1)]:
        w.put(APP_PUTS, HOLDER, prio=prio, target_rank=target, work_type=wt)
        ref.put(wt, prio, target)


def _plan_match_pins_the_front(w, ref):
    front = ref.front()
    w.park()
    w.eps[PLANNER].send(HOLDER, msg(
        Tag.SS_PLAN_MATCH, PLANNER, seqno=front, for_rank=APP_PARKS,
        req_home=HOME, rqseqno=w.rqseqno))
    assert list(w.served().handle[:2]) == [front, HOLDER]
    ref.units[front][3] = True


def _released_pin(w, ref):
    """The reservation is given back, as a home does with one that came
    too late (``SS_UNRESERVE``); a match for a reserve that is no longer
    parked is given back by the home itself."""
    (pinned,) = [s for s, u in ref.units.items() if u[3]]
    w.eps[APP_PARKS].send(HOLDER, msg(Tag.SS_UNRESERVE, APP_PARKS,
                                      seqno=pinned))
    ref.units[pinned][3] = False
    w.eps[PLANNER].send(HOLDER, msg(
        Tag.SS_PLAN_MATCH, PLANNER, seqno=ref.snapshot()[4],
        for_rank=APP_PARKS, req_home=HOME, rqseqno=w.rqseqno))


def _migration_from_the_middle(w, ref):
    seqnos = ref.snapshot(1 << 30)[0::4]
    taken = [seqnos[2], seqnos[6]]  # one among the first K, one beyond
    w.eps[PLANNER].send(HOLDER, msg(
        Tag.SS_PLAN_MIGRATE, PLANNER, dest=HOME, seqnos=taken, mig_id=1))
    for seqno in taken:
        del ref.units[seqno]


def _fetch_takes_the_front(w, ref):
    fetch(w, ref, T)


def _puts_after_removals(w, ref):
    for wt, prio in [(T, 3), (U, 9), (T, -2)]:
        w.put(APP_PUTS, HOLDER, prio=prio, work_type=wt)
        ref.put(wt, prio)


def _fetches_reach_past_the_migrated(w, ref):
    for _ in range(3):
        fetch(w, ref, T)


def _churn_behind_a_front_that_stays(w, ref):
    for _ in range(K):
        w.put(APP_PUTS, HOLDER, prio=20, work_type=V)
        ref.put(V, 20)
    for _ in range(CHURN):
        w.put(APP_PUTS, HOLDER, prio=1, work_type=T)
        ref.put(T, 1)
        fetch(w, ref, T)


def _the_front_that_stayed_goes(w, ref):
    for _ in range(K):
        fetch(w, ref, V)


STEPS = [_puts, _plan_match_pins_the_front, _released_pin,
         _migration_from_the_middle, _fetch_takes_the_front,
         _puts_after_removals, _fetches_reach_past_the_migrated,
         _churn_behind_a_front_that_stays, _the_front_that_stayed_goes]


@pytest.fixture(scope="module")
def stepped(tmp_path_factory):
    """({step: (shipped, expected)}, artefacts, flight dir) of the world
    above."""
    tmp = tmp_path_factory.mktemp("snapshot")
    w = Planned(tmp, types=(T, U, V), balancer_max_tasks=K)
    ref = Reference()
    seen = {}
    try:
        for step in STEPS:
            step(w, ref)
            seen[step.__name__] = (holder_snapshot(w), ref.snapshot())
        w.finish()
    finally:
        w.close()
    return seen, artefacts(tmp), tmp


@pytest.mark.parametrize("step", [s.__name__ for s in STEPS])
def test_a_snapshot_ships_the_first_k_of_the_sorted_queue(stepped, step):
    seen, _docs, _dir = stepped
    shipped, expected = seen[step]
    assert len(expected) == 4 * K
    assert shipped == expected


def test_the_walk_counts_what_it_visited_and_shipped(stepped):
    _seen, docs, _dir = stepped
    holder = docs[HOLDER]
    assert holder["snap_shipped"] >= K * len(STEPS)
    # what the walks passed by: the pinned front, the migrated and the
    # fetched units as the front reached them; not the churned units,
    # filtered out while no walk passed them
    skipped = holder["snap_walked"] - holder["snap_shipped"]
    assert 0 < skipped < CHURN // 2
    for doc in docs.values():
        assert doc["snap_walked"] >= doc["snap_shipped"] >= 0


def test_obs_report_prints_the_walk(stepped):
    _seen, docs, flight_dir = stepped
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "obs_report.py"),
         str(flight_dir)], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for doc in docs.values():
        assert (f"snapshot index: walked {doc['snap_walked']}, shipped "
                f"{doc['snap_shipped']}") in out.stdout
