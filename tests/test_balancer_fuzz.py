"""Randomized adversarial fuzz of the migration credit/ack state machine
(round-4 review item 6).

The phantom-credit bug class (fixed in commit 3236cc1, regression-tested
point-wise in test_balancer.py) lives in the snapshot/credit/ack lattice
spread across ``PlanEngine.round``/``_prune_credits``/``_plan_migrations``
and the master's ``Server._accept_snapshot`` merge.  This harness drives
those REAL code paths — the engine is a live ``PlanEngine`` and snapshot
intake goes through the real unbound ``Server._accept_snapshot`` on a
stub — through randomized adversarial schedules:

* delayed / reordered plan enactments and unit-transfer batches
  (per-(src,dest) FIFO, as TCP guarantees, but arbitrary cross-channel
  interleavings);
* migration batches that go fully or partially stale at the source
  before enactment (the phantom-credit trigger);
* reqs-only-first and reqs-only-interleaved snapshots (the ack-inherit
  merge path);
* snapshots delivered late, skipped, or carrying duplicated acks (the
  running-max ack dict is resent in every snapshot by design);
* optionally, batches lost in transit (the TTL-backstop path).

Oracles checked continuously:

1. **unit conservation / at-most-once delivery** — every unit is in
   exactly one of {queued@rank, in-transit, consumed, lost}; arrival
   asserts the unit was in transit (a double-feed would trip this);
2. **plan-ledger freshness** — the engine never re-plans a (rank, seqno)
   unless a snapshot with a newer task view was accepted after the prior
   plan (guards ledger-eviction regressions);
3. **ack monotonicity** — per (src, dest) channel FIFO implies strictly
   increasing mig_ids at the destination (the sim models BOTH FIFOs
   reality provides: the src->dest unit channel AND the balancer->src
   plan-command stream — without the latter, two batches the engine
   legitimately has outstanding on one channel could enact in inverted
   order under an adversarial due draw and fail this assertion
   spuriously);
4. **credit quiescence** — with the TTL and stamp/min-age fallbacks
   pinned OFF, once all transit drains and every server ships a full
   snapshot, a planning round must leave ``_planned_in`` EMPTY: exact
   ack clearing alone must clear every credit, including fully-stale
   batches.  Reintroducing the round-3 bug (sources dropping fully-stale
   batches instead of shipping the empty batch id) leaks credits here —
   the companion test flips the harness's ``buggy_drop_empty`` knob and
   asserts the oracle catches it.

Reference behavior being protected: the reference balances via per-unit
steal round trips and has no plan credits at all (``src/adlb.c``
PUSH_QUERY path); the credit lattice is this framework's own riskiest
invention, hence the adversarial coverage.
"""

from __future__ import annotations

import random
import time

from adlb_tpu.balancer.engine import PlanEngine
from adlb_tpu.runtime.server import Server

T1, T2 = 1, 2


class _Master:
    """Just enough master-server surface for the real _accept_snapshot."""

    def __init__(self):
        self._snapshots = {}

    def _update_parked(self, src, reqs):
        pass

    def _maybe_wake_balancer(self, src, snap):
        pass

    def accept(self, src, snap):
        Server._accept_snapshot(self, src, snap)


class CreditFuzzSim:
    def __init__(
        self,
        seed: int,
        *,
        nservers: int = 4,
        consumers: int = 2,
        buggy_drop_empty: bool = False,
        drop_prob: float = 0.0,
        stale_all_prob: float = 0.25,
        engine_kw: dict | None = None,
    ):
        self.rng = random.Random(seed)
        self.eng = PlanEngine(
            types=(T1, T2), max_tasks=256, max_requesters=64,
            host_threshold_reqs=10 ** 9, **(engine_kw or {}),
        )
        self.master = _Master()
        self.buggy = buggy_drop_empty
        self.drop_prob = drop_prob
        self.stale_all_prob = stale_all_prob
        self.nservers = nservers
        self.servers = {}
        for s in range(nservers):
            self.servers[s] = {
                "inv": {},  # uid -> (wtype, prio, len)
                "acks": {},  # src -> highest mig_id landed from src
                "workers": [
                    {"busy": 0, "parked": None, "wrank": 100 + s * 10 + i}
                    for i in range(consumers)
                ],
                "rqseq": 0,
                # adversarial: force the first snapshots reqs-only
                "reqs_only_until": self.rng.randrange(0, 6),
            }
        self.meta = {}  # uid -> (wtype, prio, len)
        self.unit_state = {}  # uid -> ("q", rank)|("transit", mid)|state str
        self.next_uid = 0
        self.msgs = []  # balancer->server plan commands
        self.cmd_due = {}  # src -> last mig command due (stream FIFO)
        self.chan = {}  # (src, dest) -> FIFO of unit batches
        self.snap_q = {s: [] for s in range(nservers)}
        self.it = 0
        self.produced = self.consumed = self.lost = 0
        self.stats = {
            "stale_batches": 0, "enacted_batches": 0, "migs_planned": 0,
            "matches_planned": 0, "delivered_units": 0,
        }
        self.last_plan = {}  # (rank, uid) -> monotonic lower bound

    # ------------------------------------------------------------ helpers
    def _consume(self, s: int, uid: int) -> None:
        del self.servers[s]["inv"][uid]
        self.unit_state[uid] = "consumed"
        self.consumed += 1

    def _local_fetch(self, s: int, w: dict) -> bool:
        types = w["parked"][2] if w["parked"] else None
        inv = self.servers[s]["inv"]
        for uid, (wt, _p, _l) in inv.items():
            if types is None or wt in types:
                self._consume(s, uid)
                w["busy"] = self.rng.randrange(2, 10)
                w["parked"] = None
                return True
        return False

    # -------------------------------------------------------- enactments
    def _enact_migration(self, m: dict) -> None:
        rng, src, dest = self.rng, m["src"], m["dest"]
        live = [u for u in m["uids"] if self.unit_state[u] == ("q", src)]
        # adversarial staleness: the source's own workers drain planned
        # units between plan and enactment
        if live and rng.random() < self.stale_all_prob:
            for u in live:
                self._consume(src, u)
            live = []
        elif live:
            for u in list(live):
                if rng.random() < 0.2:
                    self._consume(src, u)
                    live.remove(u)
        self.stats["enacted_batches"] += 1
        if not live:
            self.stats["stale_batches"] += 1
            if self.buggy:
                return  # THE round-3 BUG: fully-stale batch dropped
        if live and self.drop_prob and rng.random() < self.drop_prob:
            for u in live:
                del self.servers[src]["inv"][u]
                self.unit_state[u] = "lost"
                self.lost += 1
            return  # batch lost in transit: only the TTL can clear it
        for u in live:
            del self.servers[src]["inv"][u]
            self.unit_state[u] = ("transit", m["mid"])
        q = self.chan.setdefault((src, dest), [])
        due = self.it + rng.randrange(1, 5)
        if q:
            due = max(due, q[-1]["due"])  # FIFO per channel
        q.append({"due": due, "mid": m["mid"], "uids": live})

    def _arrive(self, src: int, dest: int, batch: dict) -> None:
        sv = self.servers[dest]
        for u in batch["uids"]:
            assert self.unit_state[u] == ("transit", batch["mid"]), (
                "unit delivered twice or from a non-transit state",
                u, self.unit_state[u], batch,
            )
            self.unit_state[u] = ("q", dest)
            sv["inv"][u] = self.meta[u]
        prev = sv["acks"].get(src, 0)
        assert batch["mid"] > prev, (
            "mig_id not strictly increasing per (src,dest) channel",
            src, dest, batch["mid"], prev,
        )
        sv["acks"][src] = batch["mid"]
        self.stats["delivered_units"] += len(batch["uids"])

    def _enact_match(self, m: dict) -> None:
        holder, uid = m["holder"], m["uid"]
        if self.unit_state[uid] != ("q", holder):
            return  # stale plan entry: validated away, as at enactment
        for w in self.servers[m["req_home"]]["workers"]:
            p = w["parked"]
            if p and p[0] == m["for_rank"] and p[1] == m["rqseqno"]:
                self._consume(holder, uid)
                w["busy"] = self.rng.randrange(2, 10)
                w["parked"] = None
                return
        # requester gone (satisfied locally): unit stays where it is

    # --------------------------------------------------------- snapshots
    def _send_snap(self, s: int, reqs_only: bool, immediate: bool = False):
        sv = self.servers[s]
        if self.it < sv["reqs_only_until"]:
            reqs_only = True
        if reqs_only:
            tasks = None
        else:
            tasks = [
                (uid, v[0], v[1], v[2]) for uid, v in sv["inv"].items()
            ][:256]
        reqs = [w["parked"] for w in sv["workers"] if w["parked"]]
        snap = {
            "tasks": tasks,
            "reqs": [(wr, rq, list(ty) if ty else None)
                     for wr, rq, ty in reqs],
            "nbytes": sum(v[2] for v in sv["inv"].values()),
            "consumers": len(sv["workers"]),
            "stamp": time.monotonic(),
            "mig_acks": dict(sv["acks"]),
        }
        if immediate:
            self.master.accept(s, snap)
            return
        due = self.it if self.rng.random() < 0.7 else (
            self.it + self.rng.randrange(1, 4)
        )
        q = self.snap_q[s]
        if q:
            due = max(due, q[-1][0])  # per-server FIFO (TCP ordering)
        q.append((due, snap))

    def _deliver_snaps(self) -> None:
        for s, q in self.snap_q.items():
            while q and q[0][0] <= self.it:
                _, snap = q.pop(0)
                self.master.accept(s, snap)

    # ------------------------------------------------------------- round
    def _check_replan(self, key: tuple, t_before: float) -> None:
        prev = self.last_plan.get(key)
        if prev is None:
            return
        snap = self.master._snapshots.get(key[0])
        assert snap is not None, ("re-plan with no snapshot", key)
        tstamp = snap.get("task_stamp", snap.get("stamp"))
        assert tstamp > prev, (
            "unit re-planned without a fresher accepted task view",
            key, tstamp, prev,
        )

    def _round(self) -> int:
        if not self.master._snapshots:
            return 0
        rng = self.rng
        t_before = time.monotonic()
        matches, migs = self.eng.round(dict(self.master._snapshots))
        seen: set = set()
        for holder, uid, req_home, for_rank, rqseqno in matches:
            key = (holder, uid)
            assert key not in seen, ("unit planned twice in one round", key)
            seen.add(key)
            self._check_replan(key, t_before)
            self.last_plan[key] = t_before
            self.msgs.append({
                "due": self.it + rng.randrange(0, 5), "kind": "match",
                "holder": holder, "uid": uid, "req_home": req_home,
                "for_rank": for_rank, "rqseqno": rqseqno,
            })
            self.stats["matches_planned"] += 1
        for src, dest, uids, mid in migs:
            for uid in uids:
                key = (src, uid)
                assert key not in seen, (
                    "unit planned twice in one round", key,
                )
                seen.add(key)
                self._check_replan(key, t_before)
                self.last_plan[key] = t_before
            # balancer->src is ONE connection: mig commands toward a src
            # enact in plan order (so per-channel mids stay monotonic
            # even with two batches outstanding on one channel — the
            # engine plans that legitimately when a dest's demand grows)
            due = max(self.it + rng.randrange(0, 6),
                      self.cmd_due.get(src, -1))
            self.cmd_due[src] = due
            self.msgs.append({
                "due": due, "kind": "mig",
                "src": src, "dest": dest, "uids": list(uids), "mid": mid,
            })
            self.stats["migs_planned"] += 1
        return len(matches) + len(migs)

    def _check_conservation(self) -> None:
        q = t = 0
        for st in self.unit_state.values():
            if isinstance(st, tuple):
                if st[0] == "q":
                    q += 1
                else:
                    t += 1
        assert self.produced == self.consumed + self.lost + q + t, (
            "unit conservation violated",
            self.produced, self.consumed, self.lost, q, t,
        )
        qd = sum(len(sv["inv"]) for sv in self.servers.values())
        assert qd == q, ("inventory/state divergence", qd, q)

    # -------------------------------------------------------------- step
    def kill(self, rank: int) -> None:
        """Server death mid-run: its snapshots stop (the master pops the
        entry on DS_END / connection loss), its queued inventory and
        everything in transit TO it die with the process.  step() then
        guards every pump on membership in ``servers`` — plans and
        channels referencing the dead rank are dropped, and credits to
        it can only retire via _prune_credits' snapshot-is-None TTL
        branch."""
        self.master._snapshots.pop(rank, None)
        victim = self.servers.pop(rank)
        for uid in victim["inv"]:
            self.unit_state[uid] = "lost"
            self.lost += 1
        for (_src, dst), q in self.chan.items():
            if dst == rank:
                for batch in q:
                    for uid in batch["uids"]:
                        self.unit_state[uid] = "lost"
                        self.lost += 1
                q.clear()
        self.snap_q.pop(rank, None)

    def step(self, produce: bool = True) -> int:
        self.it += 1
        rng = self.rng
        if produce and 0 in self.servers and rng.random() < 0.5:
            for _ in range(rng.randrange(1, 9)):
                uid = self.next_uid
                self.next_uid += 1
                wt = T1 if rng.random() < 0.8 else T2
                self.meta[uid] = (wt, rng.randrange(1, 10), 8)
                self.servers[0]["inv"][uid] = self.meta[uid]
                self.unit_state[uid] = ("q", 0)
                self.produced += 1
        remaining = []
        for m in self.msgs:
            if m["due"] > self.it:
                remaining.append(m)
            elif m["kind"] == "mig":
                # a plan touching a dead rank is dropped: a live source
                # simply keeps its units queued, a dead source's units
                # are already lost
                if m["src"] in self.servers and m["dest"] in self.servers:
                    self._enact_migration(m)
            else:
                if (
                    m["holder"] in self.servers
                    and m["req_home"] in self.servers
                ):
                    self._enact_match(m)
        self.msgs = remaining
        for (src, dest), q in self.chan.items():
            if dest not in self.servers:
                continue  # cleared by kill(); nothing can arrive
            while q and q[0]["due"] <= self.it:
                self._arrive(src, dest, q.pop(0))
        for s, sv in self.servers.items():
            for w in sv["workers"]:
                if w["busy"] > 0:
                    w["busy"] -= 1
                elif w["parked"] is None:
                    if not self._local_fetch(s, w):
                        sv["rqseq"] += 1
                        types = None if rng.random() < 0.7 else (
                            [T1] if rng.random() < 0.8 else [T1, T2]
                        )
                        w["parked"] = (w["wrank"], sv["rqseq"], types)
                else:
                    self._local_fetch(s, w)
        for s in list(self.servers):
            r = rng.random()
            if r < 0.55:
                self._send_snap(s, reqs_only=False)
            elif r < 0.75:
                self._send_snap(s, reqs_only=True)
        self._deliver_snaps()
        planned = self._round()
        self._check_conservation()
        return planned

    def in_flight_empty(self) -> bool:
        return not self.msgs and all(not q for q in self.chan.values()) \
            and all(not q for q in self.snap_q.values())

    def drain(self, max_passes: int = 600) -> bool:
        """Run to quiescence: no production, all transit delivered, full
        snapshots accepted from everyone, and a final round that plans
        nothing. Returns True when quiescent."""
        settled = 0
        for _ in range(max_passes):
            planned = self.step(produce=False)
            if not self.in_flight_empty() or planned:
                settled = 0
                continue
            for s in list(self.servers):
                self._send_snap(s, reqs_only=False, immediate=True)
            if self._round():
                settled = 0
                continue
            settled += 1
            if settled >= 3:
                return True
        return False


def _outstanding_credits(eng: PlanEngine) -> list:
    return [
        (dest, e) for dest, entries in eng._planned_in.items()
        for e in entries
    ]


def test_fuzz_credit_ack_exact_clearing():
    """With the TTL and stamp/min-age fallbacks pinned OFF, exact ack
    clearing alone must clear EVERY migration credit — across random
    adversarial schedules including fully-stale batches, reqs-only-first
    snapshots, and reordered enactments."""
    stale_total = 0
    for seed in (1, 2, 3):
        sim = CreditFuzzSim(
            seed, engine_kw={"inflow_ttl": 1e9, "inflow_min_age": 1e9},
        )
        for _ in range(250):
            sim.step()
        assert sim.drain(), (
            "world failed to quiesce", sim.stats, sim.msgs, sim.chan,
        )
        left = _outstanding_credits(sim.eng)
        assert not left, (
            "phantom credits survived exact ack clearing", left, sim.stats,
        )
        assert sim.stats["migs_planned"] > 0, (
            "schedule never exercised migrations", sim.stats,
        )
        stale_total += sim.stats["stale_batches"]
    # the dangerous path must actually have been exercised
    assert stale_total > 0, "no fully-stale batches across all seeds"


def test_fuzz_detects_reintroduced_phantom_credit_bug():
    """Reintroducing the round-3 bug (source silently drops a fully-stale
    batch instead of shipping its empty id) must leak credits that the
    quiescence oracle catches — i.e. the fuzz genuinely guards the fix."""
    leaked = False
    stale = 0
    for seed in (1, 2, 3, 4):
        sim = CreditFuzzSim(
            seed, buggy_drop_empty=True, stale_all_prob=0.5,
            engine_kw={"inflow_ttl": 1e9, "inflow_min_age": 1e9},
        )
        for _ in range(250):
            sim.step()
        sim.drain()
        stale += sim.stats["stale_batches"]
        if _outstanding_credits(sim.eng):
            leaked = True
            break
    assert stale > 0, "bug path never exercised (no fully-stale batches)"
    assert leaked, (
        "fuzz failed to detect the reintroduced phantom-credit bug"
    )


def test_fuzz_ttl_backstop_clears_lost_batches():
    """Batches lost in transit (crashed peer, dropped connection) leave
    credits only the TTL backstop can clear; after the TTL every credit
    must be gone at the next round."""
    for seed in (7, 8):
        sim = CreditFuzzSim(
            seed, drop_prob=0.3,
            engine_kw={"inflow_ttl": 0.2, "inflow_min_age": 0.01},
        )
        for _ in range(200):
            sim.step()
        sim.drain()
        time.sleep(0.25)  # > inflow_ttl: the backstop horizon passes
        for s in range(sim.nservers):
            sim._send_snap(s, reqs_only=False, immediate=True)
        # age against a PRE-round timestamp: the engine prunes with its
        # own (slightly later) clock, so any credit it keeps is strictly
        # younger than TTL relative to t_round — judging with a fresh
        # post-round clock would flag credits that merely aged a few ms
        # between the prune and the assertion (observed flake)
        t_round = time.monotonic()
        sim._round()
        # the final round prunes everything past the TTL but may itself
        # plan fresh migrations (leftover inventory, parked reqs) — the
        # invariant is that no credit OLDER than the TTL survives a round
        old = [
            (d, e) for d, e in _outstanding_credits(sim.eng)
            if t_round - e[0] > sim.eng.INFLOW_TTL
        ]
        assert not old, ("credits outlived the TTL backstop", old)
        assert sim.lost > 0, "drop schedule never lost a batch"


def test_fuzz_dead_destination_credits_ttl_pruned():
    """A destination that STOPS appearing in snapshots (server ended /
    died — the master pops its snapshot on DS_END) can never ack its
    in-flight credits; _prune_credits' snapshot-is-None branch must
    still retire them by TTL, and the planner must keep functioning for
    the survivors (the conservation oracle stays armed throughout)."""
    exercised = 0
    for seed in (11, 12, 13):
        sim = CreditFuzzSim(
            seed, engine_kw={"inflow_ttl": 0.2, "inflow_min_age": 0.01},
        )
        # run until some non-master rank holds live credits (cap the
        # search so a pathological seed fails loudly, not forever)
        dead = None
        for _ in range(400):
            sim.step()
            cand = [r for r in sim.eng._planned_in if r != 0]
            if cand:
                dead = max(cand, key=lambda r: len(sim.eng._planned_in[r]))
                break
        if dead is None:
            continue  # this seed never migrated off-master; try the next
        assert sim.eng._planned_in.get(dead), "vacuous kill target"
        exercised += 1
        sim.kill(dead)
        # survivors keep running; the dead rank's credits age out via
        # the TTL-only branch (no snapshot can ever ack them again)
        deadline = time.monotonic() + 0.35  # > inflow_ttl
        while time.monotonic() < deadline:
            sim.step(produce=False)
        t_round = time.monotonic()  # pre-round clock (see TTL test note)
        sim.step(produce=False)
        leftover = [
            (d, e) for d, e in _outstanding_credits(sim.eng) if d == dead
        ]
        old = [e for _, e in leftover if t_round - e[0] > sim.eng.INFLOW_TTL]
        assert not old, (
            "dead destination's credits outlived the TTL-only pruning",
            leftover,
        )
    assert exercised > 0, "no seed ever produced off-master credits"
