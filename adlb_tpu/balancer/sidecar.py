"""Balancer sidecar: the Python/JAX brain driving the native C++ data plane.

SURVEY §7's language split realized end-to-end: native servers
(``adlb_tpu/native/serverd.cpp``) keep the entire data plane — queues,
protocol, payloads — and stream fixed-shape queue-state snapshots
(``SS_STATE``: flattened task/requester metadata, a few KB) to this
process, which runs the batched assignment solve (:mod:`.engine` /
:mod:`.solve`, Pallas on TPU) and answers with ``SS_PLAN_MATCH`` /
``SS_PLAN_MIGRATE``. Payload bytes never cross into Python — exactly the
"balancer brain in a sidecar exchanging fixed-shape arrays" design.

The sidecar occupies a pseudo-rank one past the world (it is not an app or
a server; no role math changes), speaks the binary TLV codec toward
servers, and exits when every server has sent DS_END (or on abort).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from adlb_tpu.balancer.ledger import SnapshotStore, TaskTable
from adlb_tpu.runtime.messages import Tag, msg


class SidecarThread(threading.Thread):
    """The sidecar's serve loop on a thread of the launching process —
    which is therefore the process that owns the chip. ``facts`` holds
    the engine's solver facts once the loop ends; ``error`` holds what
    ended it early. :func:`stop_sidecar` hands both to the launcher."""

    def __init__(self, world, cfg, ep, abort_event) -> None:
        super().__init__(daemon=True, name="adlb-balancer-sidecar")
        self._args = (world, cfg, ep, abort_event)
        self.facts: dict = {}
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            self.facts = run_sidecar(*self._args)
        except BaseException as e:  # noqa: BLE001 — raised by stop_sidecar
            self.error = e
            # tpu mode has no other cross-server matching: end the world
            # now, not at the launcher's timeout with every worker parked
            abort_event = self._args[3]
            if abort_event is not None:
                abort_event.set()


def start_sidecar(world, cfg, abort_event=None, host: str = "127.0.0.1"):
    """Bind the sidecar's endpoint at pseudo-rank ``world.nranks`` and build
    its (not-yet-started) thread. Returns (endpoint, thread): add the
    endpoint's port to the world's address map, update ``ep.addr_map``,
    then ``thread.start()``. Use :func:`stop_sidecar` to tear down — also
    on bootstrap failure, or the thread/endpoint leak. Pass the host other
    machines reach this one at for multi-host worlds (servers on other
    hosts must stream snapshots here)."""
    from adlb_tpu.runtime.transport_tcp import TcpEndpoint

    ep = TcpEndpoint(
        world.nranks, {world.nranks: (host, 0)},
        binary_peers=set(world.server_ranks),
    )
    return ep, SidecarThread(world, cfg, ep, abort_event)


def stop_sidecar(ep, thread, abort_event=None, timeout: float = 10.0) -> dict:
    """Join (the loop exits on the servers' DS_ENDs, or on abort_event),
    close the endpoint, and return the solver facts — or raise the error
    that ended the serve loop, as a server rank's would be."""
    if thread.is_alive():
        thread.join(timeout=timeout)
        if thread.is_alive() and abort_event is not None:
            abort_event.set()
            thread.join(timeout=2.0)
    ep.close()
    if thread.error is not None:
        raise RuntimeError(
            f"balancer sidecar failed: {thread.error!r}"
        ) from thread.error
    return thread.facts


def decode_snapshot(m) -> dict:
    """Unflatten a native SS_STATE frame into the engine's snapshot
    shape. The task table stays the int64 array the codec read off the
    frame (``tasks_flat``, one of its ARRAY_FIELDS), four columns a unit:
    the ledger fills its columns from it, and whoever indexes or
    iterates it gets the ``(seqno, type, prio, len)`` tuples."""
    tf = m.data.get("tasks_flat")
    tasks = TaskTable(() if tf is None else tf)
    rf = m.data.get("reqs_flat") or []
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
    return {
        "tasks": tasks,
        "reqs": reqs,
        "nbytes": m.data.get("nbytes", 0),
        "consumers": m.data.get("consumers", 0),
        "stamp": time.monotonic(),  # receiver clock: never mix hosts' clocks
        # flattened (src, highest id) pairs; absent on pre-ack daemons ->
        # engine falls back to stamp clearing
        "mig_acks": (
            {ma[i]: ma[i + 1] for i in range(0, len(ma), 2)}
            if (ma := m.data.get("mig_acks")) is not None else None
        ),
    }


def merge_delta(snap: dict, m, max_tasks: int, max_jobs: int) -> None:
    """Append the units of an SS_STATE_DELTA frame to the sender's last
    full snapshot, as rows of its task table, up to ``max_tasks`` rows
    (``balancer_max_tasks``). Batched shape (parallel lists) since round
    4; the single-unit shape is kept for older daemons. "jobs" (field
    106) rides only when some unit is non-default: the rows are then five
    wide, and a unit of an overflow namespace (beyond the planner's job
    axis) stays off the table. The stamp is left as it is — requester
    re-eligibility only comes from full snapshots; see the server's
    merge — so ``delta_seq`` is the change signal the resident ledger
    and the solvers' fast paths key on."""
    d = m.data
    if d.get("seqnos") is None:
        cols = [[m.seqno], [m.work_type], [m.prio], [m.work_len]]
    else:
        cols = [m.seqnos, m.work_types, m.prios, m.work_lens]
        jobs = d.get("jobs")
        if jobs is not None and any(jobs):
            cols.append(jobs)
    rows = np.array(cols, np.int64).T
    if rows.shape[1] > 4:
        rows = rows[(rows[:, 4] >= 0) & (rows[:, 4] < max_jobs)]
    tasks = snap["tasks"]
    tasks.extend(rows[:max(max_tasks - len(tasks), 0)])
    snap["nbytes"] = d.get("nbytes", snap["nbytes"])
    snap["delta_seq"] = snap.get("delta_seq", 0) + 1


def run_sidecar(world, cfg, ep, abort_event=None) -> dict:
    """Serve balancer rounds until every server says DS_END; returns the
    engine's solver facts plus ``rounds``, the number of planning rounds
    executed (the flight artifact carries both, error or not)."""
    from adlb_tpu.balancer.engine import PlanEngine, round_gap
    from adlb_tpu.obs.metrics import Registry, attach
    from adlb_tpu.runtime.trace import clock_mark, span

    # the sidecar is its own process/thread: it owns its registry (round
    # duration, plan ages, pairs) and instruments its endpoint's per-tag
    # traffic like any server
    metrics = Registry(rank=world.nranks)
    attach(ep, metrics)
    engine = PlanEngine.from_config(world, cfg, metrics=metrics)
    # versioned snapshot table (balancer/ledger.py): the ledger's sync
    # touches only ranks whose snapshots changed since the last round.
    # The sidecar loop is single-threaded, so the engine reads the live
    # store (no fork needed); in-place merges below bump() it.
    snapshots: SnapshotStore = SnapshotStore()
    ended: set[int] = set()
    servers = set(world.server_ranks)
    rounds = 0
    dirty = False
    # one state machine shared with the in-server master: growth
    # broadcasts immediately, shrinks held for grace (see hungry.py)
    from adlb_tpu.balancer.hungry import HungryTracker

    tracker = HungryTracker()
    me = world.nranks  # pseudo-rank

    def safe_send(dest: int, m) -> None:
        """Send, treating an unreachable server as ended.

        At end-of-world a server can close its listener between sending
        DS_END and the sidecar draining its inbox (or while a broadcast
        is mid-flight); connection refusal there is the normal teardown
        race, not an error — marking the rank ended lets the loop drain
        out instead of dying with an unhandled thread exception.
        connect_grace is short because every peer here snapshots only
        AFTER binding its listener, so a refusal never means "still
        coming up" — without it each dead destination would stall the
        loop for the transport's 15 s startup grace. A rank wrongly
        ended by a transient error is resurrected by its next
        SS_STATE."""
        try:
            ep.send(dest, m, connect_grace=0.25)
        except OSError:
            ended.add(dest)
            snapshots.pop(dest, None)
            tracker.drop(dest)

    def broadcast(payload) -> None:
        if payload is None:
            return
        is_hungry, req_types, grew = payload
        for s in sorted(servers - ended):
            safe_send(
                s,
                msg(Tag.SS_HUNGRY, me, hungry=int(is_hungry),
                    req_types=req_types, grew=int(grew)),
            )

    def handle(m) -> bool:
        """Merge one inbox message into the held state; True when a
        planning round has something new to look at."""
        dirty = False
        if m.tag is Tag.SS_STATE:
            # a fresh snapshot proves the server is alive: resurrect
            # it if a transient send error wrongly marked it ended
            # (DS_END is final — an ended-by-DS_END server never
            # snapshots again, so this cannot resurrect those)
            ended.discard(m.src)
            snapshots[m.src] = decode_snapshot(m)
            broadcast(tracker.update(m.src, snapshots[m.src]["reqs"]))
            dirty = True
        elif m.tag is Tag.SS_STATE_DELTA:
            # put-event: append task(s) to the sender's last full
            # snapshot, in place
            snap = snapshots.get(m.src)
            if snap is not None:
                merge_delta(snap, m, cfg.balancer_max_tasks,
                            cfg.balancer_max_jobs)
                snapshots.bump(m.src)
                dirty = True
        elif m.tag is Tag.DS_END:
            ended.add(m.src)
            snapshots.pop(m.src, None)
            tracker.drop(m.src)
        elif m.tag is Tag.SS_SERVER_DEAD:
            # defensive only: TODAY this never fires — the sidecar
            # plane drives NATIVE daemons, which Config rejects for
            # on_server_failure="failover", and the Python-plane
            # fan-out targets only world server ranks. Kept so a
            # future native failover protocol that does relay the
            # fan-out retires the dead server's snapshot/tracker
            # state (like a DS_END) instead of planning onto it.
            dead_srv = m.rank
            snapshots.pop(dead_srv, None)
            tracker.drop(dead_srv)
            ended.add(dead_srv)
            dirty = True
        elif m.tag is Tag.SS_RANK_DEAD:
            # a worker died under on_worker_failure="reclaim":
            # retire its parked requests from every held snapshot
            # so the next plan stops matching/migrating toward it
            # (stale entries would only cost an UNRESERVE bounce,
            # but the dead rank must not keep attracting work).
            # Forward-compat: today reclaim requires python
            # servers (whose master patches its own snapshots),
            # so this only fires if a future native plane or an
            # operator tool relays the death here.
            dead = m.rank
            for src, snap in snapshots.items():
                kept = [r for r in snap["reqs"] if r[0] != dead]
                if len(kept) != len(snap["reqs"]):
                    snap["reqs"] = kept
                    snapshots.bump(src)  # in-place patch
                    dirty = True
                    broadcast(tracker.update(src, kept))
        return dirty

    def ship(matches, migrations) -> None:
        for holder, seqno, req_home, for_rank, rqseqno in matches:
            if holder in ended:  # died earlier in this very plan loop
                continue
            safe_send(
                holder,
                msg(Tag.SS_PLAN_MATCH, me, seqno=seqno, for_rank=for_rank,
                    req_home=req_home, rqseqno=rqseqno),
            )
        for src_rank, dest, seqnos, mig_id in migrations:
            if src_rank in ended or dest in ended:
                continue
            safe_send(
                src_rank,
                msg(Tag.SS_PLAN_MIGRATE, me, dest=dest, seqnos=seqnos,
                    mig_id=mig_id),
            )

    # the loop's spans (runtime/trace.py; the names are fixed, USERGUIDE
    # §5): wait, ingest, engine.round's own adlb.round, ship, pace
    try:
        while ended < servers:
            if abort_event is not None and abort_event.is_set():
                break
            clock_mark()
            with span("adlb.sidecar.wait", metrics):
                m = ep.recv(timeout=0.25)
            with span("adlb.sidecar.ingest", metrics):
                while m is not None:
                    dirty |= handle(m)
                    m = ep.recv(timeout=0.0)
                broadcast(tracker.flush(time.monotonic()))
            if not dirty or not snapshots:
                continue
            dirty = False
            matches, migrations = engine.round(snapshots, world)
            rounds += 1
            if matches or migrations:
                with span("adlb.sidecar.ship", metrics):
                    ship(matches, migrations)
            if cfg.balancer_min_gap > 0:
                # shared cadence with the in-proc _BalancerWorker
                with span("adlb.sidecar.pace", metrics):
                    time.sleep(
                        round_gap(cfg.balancer_min_gap, matches, migrations))
    finally:
        # the registry's round/plan-age/traffic numbers become reachable
        # as a flight artifact when the world opted in — written in a
        # finally so a serve-loop crash (the one case a post-mortem is
        # FOR) still leaves one; the sidecar is the one balancer brain a
        # server post-mortem cannot see into otherwise
        from adlb_tpu.obs.flight import write_artifact

        solver = engine.solver_facts()
        write_artifact(
            cfg.flight_dir,
            "sidecar",
            {
                "role": "sidecar",
                "rank": me,
                "reason": "aborted" if (abort_event is not None
                                        and abort_event.is_set()) else "exit",
                "rounds": rounds,
                "solver": solver,
                "metrics": metrics.snapshot(),
            },
        )
    return {**solver, "rounds": rounds}
