"""Distributed (multi-chip) assignment solve — the production path.

SPMD decomposition of :mod:`adlb_tpu.balancer.solve` over a
``jax.sharding.Mesh``: the task table — the big axis, scaling with servers x
queue depth — lives device-resident, sharded by server over mesh axis
``"s"`` (``NamedSharding``), and is updated *incrementally* from per-server
snapshot deltas (only changed rows ship; unchanged servers are skipped by
a stamp fast path). Each planning round is three fixed-shape steps:

1. **sharded candidate generation** (on the mesh) — every device presorts
   its task shard by (type, priority desc, gid) — three composed stable
   single-key sorts; the multi-key comparator sort is ~10x slower on CPU
   backends — and slices each type's top-D candidates, D = C + m + 1.
   This is the only work that scales with table size, which is exactly
   what the mesh parallelizes; it never retraces (fixed [S, K] shapes).
2. **cross-shard merge** of the [ndev, T, 2D] per-device winner tuples
   into global per-type candidate lists ordered by (prio desc, gid asc)
   — two composed stable single-key sorts (gid, then prio): the elastic
   slot map decouples device row order from rank order, so gid-ascending
   is restored explicitly before the priority sort.
3. **auction rounds** — pure head-pointer logic over the merged per-type
   candidate lists and the [T, C] requester-slot tables (O(plan size)):
   rank-k candidate pairs with the k-th open accepting requester,
   cross-type conflicts resolve by (prio, -gid), a global threshold
   defers any winner that a displaced higher-priority task could cascade
   into, and prefix commits keep every shard's consumed tasks a prefix
   of its sorted type segment (which is what makes step 1's head slices
   exact).

The solver runs one of two tiers over those steps:

- ``auction="device"`` (default): all three steps fuse into ONE jitted
  ``shard_map`` program (:func:`_build_plan_fn`) — candidate generation
  per shard, a ``lax.all_gather`` over the ``"s"`` axis, the replicated
  merge, and the auction as a fixed-shape ``lax.while_loop`` over
  host-compacted requester ids (U = T*C distinct ids at most, so the
  per-round scatters never touch O(requesters) state). A planning round
  is one device dispatch plus one [T, C+1] commit-table readback — no
  per-round host merge of the [ndev, T, 2D] gather, no O(S) host work.
- ``auction="host"``: the PR 7 twin, retained verbatim — steps 2-3 on
  the planner host (numpy), with the merged candidate lists cached and
  patched in place between device sweeps. The twin is what the device
  tier is fuzz-checked against (exact same commits from the same state).

Task ids are **rank-keyed**: gid = rank * K + ki (``row_rank`` maps the
resident row to its server rank; int32 on device, so rank * K must stay
under 2**31 — enforced at registration). Because the greedy tie-break is
the gid order itself, slot assignment is free-listed: an elastic join or
leave (PR 15 epoch bump) patches exactly one row and never remaps the
world — no full mesh re-sweep on churn.

The auction reproduces the exact sequential greedy matching of
:func:`adlb_tpu.balancer.solve._host_greedy` — same matched requester
set, same committed task multiset, same total score (fuzz-verified at
mesh sizes 1/2/8 by ``tests/test_sharded_parity.py``) — truncation
aside: at most ``C`` requesters per type are visible per round and
``m`` commits per type can land per auction round, and leftovers are
re-planned by the next balancer tick (the protocol's standing staleness
contract: plan entries are hints validated at enactment).

This replaces the reference's qmstat ring gossip (reference
``src/adlb.c:806-822,1705-1757``): instead of an O(0.1 s) staleness
window on an approximate load vector, the whole queue state is solved
every round, and scale comes from adding devices along ``"s"``.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adlb_tpu.balancer.jobdim import bias_vector, expand_types
from adlb_tpu.balancer.solve import (
    _I32MAX, _NEG, _PRIO_CLIP, _stable_argsort3)


def _shard_candidates(tp, tt, rk, T: int, D: int):
    """Per-shard candidate generation (traced inside shard_map): presort
    the local [Sl, K] task block by (type, prio desc, gid) and slice each
    type's top-D window. gid = rank * K + ki — rank-keyed, NOT row-keyed,
    so the candidate identity (and hence the greedy tie-break) survives
    elastic slot reuse. Returns (cand_prio, cand_gid) [T, D]."""
    Sl, K = tp.shape
    Kl = Sl * K
    tp, tt = tp.reshape(-1), tt.reshape(-1)
    gids = (rk[:, None].astype(jnp.int32) * K
            + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(-1)
    live = (tp > _NEG) & (tt >= 0)
    prio = jnp.clip(tp, -_PRIO_CLIP, _PRIO_CLIP)
    sort_t = jnp.where(live, tt, T).astype(jnp.int32)
    order = _stable_argsort3(sort_t, -prio, gids)
    s_prio = prio[order]
    s_gid = gids[order]
    scount = jnp.zeros((T + 1,), jnp.int32).at[sort_t].add(
        1, mode="drop")
    seg_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(scount[:T])])
    idx = seg_off[:T, None] + jnp.arange(D, dtype=jnp.int32)[None, :]
    ok = idx < seg_off[1:, None]
    idc = jnp.clip(idx, 0, Kl - 1)
    cp = jnp.where(ok, s_prio[idc], _NEG)
    cg = jnp.where(ok, s_gid[idc], _I32MAX)
    return cp, cg


def _build_gather_fn(mesh: Mesh, T: int, D: int, axis: str = "s"):
    """Sharded candidate generation: fn(task_prio [S,K], task_type [S,K],
    row_rank [S]) -> (cand_prio, cand_gid) [ndev, T, D] — each device's
    per-type top-D (prio desc, gid asc) candidates, gid = rank * K + ki.
    This is the device leg of the ``auction="host"`` twin tier."""

    def shard_fn(tp, tt, rk):
        cp, cg = _shard_candidates(tp, tt, rk, T, D)
        return cp[None], cg[None]

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis)),
        out_specs=(P(axis, None, None), P(axis, None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def _build_plan_fn(mesh: Mesh, T: int, D: int, C: int, rounds: int,
                   m: int, axis: str = "s"):
    """The fully on-device planning round: ONE jitted shard_map program
    fusing candidate generation, the cross-shard merge, and the auction.

    fn(task_prio [S,K], task_type [S,K], row_rank [S],  -- mesh-sharded
       reqwin_c [T,C], lens [T], open0 [U+1])           -- replicated
    -> assigned [ndev, T, C+1] of committed gids (-1 = none; column C is
    the scatter dump for non-commits). Every shard computes the same
    replicated answer after the all_gather; the caller reads shard 0.

    ``reqwin_c`` is the requester slot table over HOST-COMPACTED ids
    (np.unique of the reqwin row ids; U = T*C is the static id-space
    bound and doubles as the dump id), so the per-round winner/open
    scatters touch [U+1] arrays — a few KB — never O(requesters) state.
    ``open0[u]`` is True for every real compacted id, False at the dump.

    The auction body is the exact device transcription of
    :func:`_host_auction` — same head slices, same (prio, -gid) conflict
    winner (two int32 scatter passes: max prio per requester, then min
    gid among prio-ties), same global commit threshold including each
    type's truncation sentinel, same prefix commits (a loss blocks every
    later rank via an exclusive cumsum — keys descend in rank, so the
    host's sequential break is exactly this mask), same zero-commit
    early exit (the while_loop condition). Fuzz-pinned against the host
    twin by tests/test_device_auction.py and tests/test_sharded_parity.py."""
    ndev = mesh.devices.size
    L = ndev * D
    U = T * C

    def shard_fn(tp, tt, rk, rwc, lens, open0):
        cp, cg = _shard_candidates(tp, tt, rk, T, D)
        # cross-shard merge, replicated on every device: restore gid
        # order, then stable-sort by prio desc (ties keep gid asc)
        ap = jax.lax.all_gather(cp, axis)  # [ndev, T, D]
        ag = jax.lax.all_gather(cg, axis)
        ap = ap.transpose(1, 0, 2).reshape(T, L)
        ag = ag.transpose(1, 0, 2).reshape(T, L)
        o = jnp.argsort(ag, axis=1, stable=True)
        ap = jnp.take_along_axis(ap, o, axis=1)
        ag = jnp.take_along_axis(ag, o, axis=1)
        o = jnp.argsort(-ap, axis=1, stable=True)
        gp = jnp.take_along_axis(ap, o, axis=1)
        gg = jnp.take_along_axis(ag, o, axis=1)
        # ---- auction rounds (fixed shapes; replicated) ----
        nlive = (gp > _NEG).sum(axis=1).astype(jnp.int32)
        slot_valid = jnp.arange(C, dtype=jnp.int32)[None, :] < lens[:, None]
        trange = jnp.arange(T, dtype=jnp.int32)
        rows_c = jnp.broadcast_to(trange[:, None], (T, C))
        cols_c = jnp.broadcast_to(
            jnp.arange(C, dtype=jnp.int32)[None, :], (T, C))
        rows_m = jnp.broadcast_to(trange[:, None], (T, m))
        arange_m1 = jnp.arange(m + 1, dtype=jnp.int32)

        def body(state):
            head, open_, assigned, rnd, _last = state
            # next m+1 untaken candidates per type (head slice)
            cidx = head[:, None] + arange_m1[None, :]
            okc = cidx < nlive[:, None]
            cl = jnp.minimum(cidx, L - 1)
            mp_full = jnp.where(okc, gp[trange[:, None], cl], _NEG)
            mg_full = jnp.where(okc, gg[trange[:, None], cl], _I32MAX)
            mp, mg = mp_full[:, :m], mg_full[:, :m]
            trunc_p, trunc_g = mp_full[:, m], mg_full[:, m]
            # first m open slots per type: scatter-min each open slot's
            # column at its open-rank (ranks >= m and closed slots fall
            # off the [T, m] table via mode="drop")
            slot_open = slot_valid & open_[rwc]
            sr = jnp.cumsum(slot_open, axis=1)
            nopen = sr[:, -1]
            jrank = jnp.where(slot_open, sr - 1, m).astype(jnp.int32)
            pair_slot = jnp.full((T, m), C, jnp.int32).at[
                rows_c, jrank].min(cols_c, mode="drop")
            valid = (mp > _NEG) & (pair_slot < C)
            psc = jnp.clip(pair_slot, 0, C - 1)
            rid = jnp.where(valid, rwc[trange[:, None], psc], U)
            # cross-type conflicts: winner per requester by (prio, -gid)
            bp = jnp.full((U + 1,), _NEG, jnp.int32).at[rid].max(
                jnp.where(valid, mp, _NEG))
            is_pmax = valid & (mp == bp[rid])
            bg = jnp.full((U + 1,), _I32MAX, jnp.int32).at[rid].min(
                jnp.where(is_pmax, mg, _I32MAX))
            win = is_pmax & (mg == bg[rid])
            lose = valid & ~win
            # global commit threshold: best key among losers and each
            # type's truncation sentinel (only while it has an open
            # slot); lexicographic max as (max prio, min gid among ties)
            sent = (nopen > 0) & (trunc_p > _NEG)
            lp = jnp.maximum(
                jnp.max(jnp.where(lose, mp, _NEG)),
                jnp.max(jnp.where(sent, trunc_p, _NEG)))
            lg = jnp.minimum(
                jnp.min(jnp.where(lose & (mp == lp), mg, _I32MAX)),
                jnp.min(jnp.where(sent & (trunc_p == lp), trunc_g,
                                  _I32MAX)))
            keygt = (mp > lp) | ((mp == lp) & (mg < lg))
            lose_before = (jnp.cumsum(lose, axis=1) - lose) > 0
            commit = win & keygt & ~lose_before
            assigned = assigned.at[
                rows_m, jnp.where(commit, psc, C)].max(
                jnp.where(commit, mg, -1))
            open_ = open_.at[jnp.where(commit, rid, U)].set(False)
            head = head + commit.sum(axis=1).astype(jnp.int32)
            return (head, open_, assigned, rnd + 1,
                    commit.sum().astype(jnp.int32))

        def cond(state):
            return (state[3] < rounds) & (state[4] > 0)

        init = (
            jnp.zeros((T,), jnp.int32),
            open0,
            jnp.full((T, C + 1), -1, jnp.int32),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(1, jnp.int32),
        )
        assigned = jax.lax.while_loop(cond, body, init)[2]
        return assigned[None]

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis),
                  P(None, None), P(None), P(None)),
        out_specs=P(axis, None, None),
        check_vma=False,
    )
    return jax.jit(fn)


def _reqwin(req_mask, req_valid, T: int, C: int, perm=None):
    """Requester slot tables: ``reqwin [T, C]`` — the first C valid
    requester row ids accepting each type, in scan order (the greedy
    "first open compatible requester" order) — plus per-type lengths.

    ``perm`` (a full row permutation) sets the scan order; the stateful
    solver passes its rank-sorted row order so the windows match the
    single-device packer's sorted-rank rows exactly even though the
    elastic slot map free-lists physical rows. The WINDOW ENTRIES stay
    physical row ids (extraction indexes the resident refs).

    Chunked early-exit scan: with deep requester tables (1M parked)
    the window is filled from the first few thousand rows, so the
    common-case cost is O(chunk * T), not O(NR * T)."""
    NR = req_valid.shape[0]
    reqwin = np.full((T, C), -1, dtype=np.int32)
    lens = np.zeros((T,), dtype=np.int32)
    CHUNK = 16384
    for a in range(0, NR, CHUNK):
        b = min(a + CHUNK, NR)
        if perm is None:
            vm = req_mask[a:b] & req_valid[a:b, None]  # [chunk, T]
        else:
            rows = perm[a:b]
            vm = req_mask[rows] & req_valid[rows][:, None]
        done = True
        for t in range(T):
            n = int(lens[t])
            if n >= C:
                continue
            idx = np.flatnonzero(vm[:, t])[: C - n]
            if idx.size:
                reqwin[t, n: n + idx.size] = (
                    idx + a if perm is None else rows[idx])
                lens[t] = n + idx.size
            if lens[t] < C:
                done = False
        if done:
            break
    return reqwin, lens


def _host_auction(gp, gg, reqwin, lens, req_open, rounds: int, m: int):
    """The auction rounds (numpy, O(plan size) per round).

    gp/gg: [T, L] merged candidate (prio, gid) lists, prio desc / gid
    asc, _NEG-padded. reqwin/lens: slot tables from :func:`_reqwin`.
    req_open: bool over requester rows, mutated in place. Returns
    ``assigned [T, C]`` of committed gids (-1 = none).

    Exits early the first round that commits nothing: the globally best
    candidate with an open accepting slot always commits (it wins any
    conflict and tops any threshold), so a zero-commit round proves the
    matching is maximal."""
    T, L = gp.shape
    C = reqwin.shape[1]
    head = np.zeros((T,), dtype=np.int64)
    nlive = (gp > _NEG).sum(axis=1)
    slot_valid = np.arange(C)[None, :] < lens[:, None]
    assigned = np.full((T, C), -1, dtype=np.int64)
    arange_m1 = np.arange(m + 1)
    trange = np.arange(T)
    for _ in range(rounds):
        # next m+1 untaken candidates per type (head slice)
        cidx = head[:, None] + arange_m1[None, :]
        okc = cidx < nlive[:, None]
        cl = np.minimum(cidx, L - 1)
        mp_full = np.where(okc, gp[trange[:, None], cl], int(_NEG))
        mg_full = np.where(okc, gg[trange[:, None], cl], _I32MAX)
        mp, mg = mp_full[:, :m], mg_full[:, :m]
        trunc_p, trunc_g = mp_full[:, m], mg_full[:, m]
        # first m open slots per type
        open_ = slot_valid & req_open[np.clip(reqwin, 0, None)]
        sr = np.cumsum(open_, axis=1)
        nopen = sr[:, -1] if C else np.zeros((T,), np.int64)
        # pair_slot[t, j] = index of the (j+1)-th open slot (C = none)
        pair_slot = np.full((T, m), C, dtype=np.int64)
        for t in range(T):
            if nopen[t]:
                k = int(min(nopen[t], m))
                pair_slot[t, :k] = np.flatnonzero(open_[t])[:k]
        valid = (mp > int(_NEG)) & (pair_slot < C)
        rid = np.where(
            valid, reqwin[trange[:, None], np.clip(pair_slot, 0, C - 1)],
            -1)
        # cross-type conflicts: winner per requester by (prio, -gid)
        win = np.zeros((T, m), dtype=bool)
        best: dict = {}
        vt, vj = np.nonzero(valid)
        for t, j in zip(vt.tolist(), vj.tolist()):
            key = (int(mp[t, j]), -int(mg[t, j]))
            r = int(rid[t, j])
            if r not in best or key > best[r][0]:
                best[r] = (key, t, j)
        for r, (_k, t, j) in best.items():
            win[t, j] = True
        win &= valid
        lose = valid & ~win
        # global commit threshold: the best key among losers and each
        # type's truncation sentinel (only while it has an open slot)
        L_key = (int(_NEG), -_I32MAX)
        lt, lj = np.nonzero(lose)
        for t, j in zip(lt.tolist(), lj.tolist()):
            k = (int(mp[t, j]), -int(mg[t, j]))
            if k > L_key:
                L_key = k
        for t in range(T):
            if nopen[t] and trunc_p[t] > int(_NEG):
                k = (int(trunc_p[t]), -int(trunc_g[t]))
                if k > L_key:
                    L_key = k
        # prefix commit above the threshold
        ncommit = 0
        for t in range(T):
            for j in range(m):
                if lose[t, j]:
                    break  # a loss blocks every later rank this round
                if not win[t, j]:
                    continue
                if (int(mp[t, j]), -int(mg[t, j])) <= L_key:
                    continue
                c = int(pair_slot[t, j])
                assigned[t, c] = mg[t, j]
                req_open[rid[t, j]] = False
                head[t] += 1
                ncommit += 1
        if ncommit == 0:
            break
    return assigned


def _sharded_to_host(x) -> np.ndarray:
    """Device->host of a [ndev, ...] mesh-sharded array, read
    shard-by-shard in device order (the sharded array's own __array__
    assembly is an order of magnitude slower on host-platform meshes)."""
    shards = sorted(
        x.addressable_shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards])


def _slot_sizes(slots_per_type: Optional[int], cand_width: int,
                rounds: int, NR: int) -> tuple[int, int]:
    """(C, D): requester slots per type and the candidate depth the
    sweep must gather. D = C + m + 1 is load-bearing for exactness —
    heads advance at most C and the threshold sentinel reads m past the
    head — so both solvers size through this one helper."""
    C = min(slots_per_type or max(64, cand_width * max(rounds, 1)), NR)
    C = C or 1
    return C, C + cand_width + 1


def _merge_shard_major(cp, cg):
    """Merge [ndev, T, D] per-shard candidate tables into exact global
    (prio desc, gid asc) lists [T, ndev*D]: two composed stable
    single-key sorts — gid first, then prio desc. (Rank-keyed gids are
    NOT monotone across the shard-major concatenation once the elastic
    slot map reuses rows, so gid order must be restored explicitly
    before the priority sort; padding gids are _I32MAX and sort last
    within the _NEG-priority run, as before.)"""
    T = cp.shape[1]
    ap = cp.transpose(1, 0, 2).reshape(T, -1)
    ag = cg.transpose(1, 0, 2).reshape(T, -1)
    o = np.argsort(ag, axis=1, kind="stable")
    ap = np.take_along_axis(ap, o, axis=1)
    ag = np.take_along_axis(ag, o, axis=1)
    mi = np.argsort(-ap, axis=1, kind="stable")
    return (
        np.take_along_axis(ap, mi, axis=1),
        np.take_along_axis(ag, mi, axis=1),
    )


def build_distributed_solver(mesh: Mesh, rounds: int = 16, axis: str = "s",
                             cand_width: int = 32,
                             slots_per_type: Optional[int] = None):
    """Returns fn(task_prio [S,K], task_type [S,K], req_mask [NR,T],
    req_valid [NR]) -> assign [NR] of global task ids (-1 = none), with
    the task tables sharded over `axis` of `mesh`.

    Server rows that are not a multiple of the mesh size are padded with
    empty rows automatically (padding is appended, so real task ids are
    unchanged, and padded rows — priority floor, no type — can never win
    an assignment: nothing to strip from the returned plan)."""
    ndev = mesh.devices.size
    built = {}

    def solve(task_prio, task_type, req_mask, req_valid):
        task_prio = np.asarray(task_prio)
        task_type = np.asarray(task_type)
        req_mask = np.asarray(req_mask)
        req_valid = np.asarray(req_valid)
        S, K = task_prio.shape
        NR, T = req_mask.shape
        pad = (-S) % ndev
        if pad:
            task_prio = np.concatenate(
                [task_prio,
                 np.full((pad, K), int(_NEG), task_prio.dtype)])
            task_type = np.concatenate(
                [task_type, np.full((pad, K), -1, task_type.dtype)])
        m = cand_width
        C, D = _slot_sizes(slots_per_type, m, rounds, NR)
        key = (task_prio.shape[0], K, T, C)
        if key not in built:
            built[key] = _build_gather_fn(mesh, T, D, axis=axis)
        gather_fn = built[key]
        shard = NamedSharding(mesh, P(axis, None))
        tp = jax.device_put(jnp.asarray(task_prio), shard)
        tt = jax.device_put(jnp.asarray(task_type), shard)
        # row index as rank: the functional path has no slot reuse, so
        # gid = si * K + ki exactly as before
        rk = jax.device_put(
            jnp.arange(task_prio.shape[0], dtype=jnp.int32),
            NamedSharding(mesh, P(axis)))
        cp, cg = gather_fn(tp, tt, rk)
        gp, gg = _merge_shard_major(_sharded_to_host(cp),
                                    _sharded_to_host(cg))
        rw, lens = _reqwin(req_mask, req_valid, T, C)
        req_open = req_valid.copy()
        assigned = _host_auction(gp, gg, rw, lens, req_open, rounds, m)
        assign = np.full((NR,), -1, dtype=np.int32)
        t_idx, c_idx = np.nonzero(assigned >= 0)
        assign[rw[t_idx, c_idx]] = assigned[t_idx, c_idx]
        return assign

    return solve


class DistributedAssignmentSolver:
    """Host wrapper mirroring AssignmentSolver.solve() but with the task
    table device-resident and sharded over the mesh, updated
    incrementally from per-server snapshot deltas.

    ``solve(snapshots, world)`` is the engine-compatible entry: it diffs
    the snapshots against the resident state (``ingest``) — a stamp fast
    path skips unchanged servers outright when snapshots carry
    ``task_stamp``/``stamp`` (the engine forwards them), falling back to
    a tuple compare otherwise — ships only changed rows to the mesh,
    runs the fixed-shape planning round (``plan``), and unpacks plan
    entries. Phase timings land in ``last_ingest_ms`` /
    ``last_solve_ms`` / ``last_extract_ms`` for the obs gauges.

    Stamp fast-path caveat (documented contract): a server whose
    filtered task list changes with no stamp bump and no plan of ours
    touching it (engine plan-ledger TTL expiry) is picked up at its next
    snapshot — at most one idle-heartbeat interval late, well inside the
    protocol's plans-are-hints staleness tolerance."""

    #: the engine may hand solve() a LedgerView instead of a snapshot
    #: dict (array-resident host tier, balancer/ledger.py): ingest then
    #: copies packed rows for servers whose ledger generation moved —
    #: no tuple re-derivation, no stamp-key diffing
    SUPPORTS_VIEW = True

    #: changed-row count above which a plan re-sweeps the table on the
    #: mesh instead of patching the merged candidate lists in place
    DELTA_RESYNC_ROWS = 16
    #: force a full device sweep at least every this many plans, so the
    #: incremental candidate view can never drift unbounded (it is exact
    #: by construction; the resync is belt-and-braces + keeps the mesh
    #: path continuously exercised)
    RESYNC_INTERVAL = 64

    def __init__(
        self,
        types: Sequence[int],
        max_tasks_per_server: int,
        max_requesters: int,
        mesh: Mesh,
        rounds: int = 16,
        servers_per_device: int = 1,
        cand_width: int = 32,
        slots_per_type: Optional[int] = None,
        auction: str = "device",
        max_jobs: int = 1,
        job_weights: Optional[dict] = None,
    ) -> None:
        if auction not in ("device", "host"):
            raise ValueError(
                f"auction must be 'device' or 'host', got {auction!r}")
        self.auction = auction
        self.base_types = tuple(types)
        self.base_T = max(len(self.base_types), 1)
        self.max_jobs = max(int(max_jobs), 1)
        # composite (job, type) axis under multi-job planning — the
        # base types verbatim when single-job (balancer/jobdim.py);
        # the mesh kernels see T' generic types and stay untouched
        self.types = expand_types(self.base_types, self.max_jobs)
        self.job_bias = bias_vector(job_weights, self.max_jobs)
        self.type_index = {t: i for i, t in enumerate(self.types)}
        self.K = max_tasks_per_server
        self.R = max_requesters
        self.mesh = mesh
        self.ndev = mesh.devices.size
        self.rounds = rounds
        self.S = self.ndev * servers_per_device
        T = max(len(self.types), 1)
        self.T = T
        self.m = cand_width
        NR = self.S * self.R
        self.C, self.D = _slot_sizes(
            slots_per_type, cand_width, rounds, NR)

        # ---- host mirrors of the resident device state ----
        self._tp = np.full((self.S, self.K), int(_NEG), dtype=np.int32)
        self._tt = np.full((self.S, self.K), -1, dtype=np.int32)
        self._req_valid = np.zeros((NR,), dtype=bool)
        self._req_mask = np.zeros((NR, T), dtype=bool)
        self._task_cache: dict[int, tuple] = {}
        self._req_cache: dict[int, tuple] = {}
        self._task_stamp: dict[int, float] = {}
        self._req_stamp: dict[int, float] = {}
        self._servers: list = []  # registered ranks (slot order free)
        self._si: dict[int, int] = {}
        # rank behind each resident row (-1 = free): the gid key space.
        # Slots are free-listed, never remapped — the auction tie-break
        # is the rank-keyed gid, not the row index
        self._row_rank = np.full((self.S,), -1, dtype=np.int64)
        self._free_si: list[int] = []
        self._next_si = 0
        # ranks whose candidate entries the next host-tier patch must
        # drop (a freed slot's row_rank is already recycled by then)
        self._dropped_ranks: set = set()
        # rank-sorted requester row order (see _reqwin): rebuilt only
        # when membership changes — the requester tie-break, like the
        # task gid, must follow rank order, not physical slot order
        self._row_perm: Optional[np.ndarray] = None
        # sequence numbers of the resident task rows, and how many of a
        # row's K are filled: the (rank, seqno) pair is made only for
        # the rows a plan returns
        self._task_seq = np.zeros((self.S, self.K), dtype=np.int64)
        self._task_n = np.zeros((self.S,), dtype=np.int64)
        self._req_ref: list = [None] * NR
        self._reqs_dirty = True
        self._full_reload = False
        # servers whose tasks/reqs our own last plan consumed: their
        # ledger-filtered snapshot content changes without a stamp bump
        self._planned_servers: set = set()
        # view-ingest bookkeeping: the ledger membership generation and
        # per-slot task/req generations last consumed (slot-indexed
        # arrays, diffed vectorized; generations are globally monotonic
        # so a slot reused for a new rank can never alias)
        self._seen_member_gen = None
        self._seen_tgen: Optional[np.ndarray] = None
        self._seen_rgen: Optional[np.ndarray] = None

        # device state & jitted fns, built lazily (constructing a solver
        # must not force accelerator init before first use)
        self._dev_tp = None
        self._dev_tt = None
        self._dev_rk = None
        self._gather_fn = None
        self._plan_fn = None
        # device-tier requester tables (rebuilt when reqs change):
        # compacted reqwin + initial open vector (see _build_plan_fn)
        self._rwc: Optional[np.ndarray] = None
        self._open0: Optional[np.ndarray] = None
        # merged per-type candidate lists [T, ndev*D] (prio desc, gid
        # asc, _NEG-padded): materialized by the device sweep, patched
        # in place for small deltas (exactly what a sweep would produce
        # — asserted by tests), re-swept when a delta is large or every
        # RESYNC_INTERVAL plans
        self._gp: Optional[np.ndarray] = None
        self._gg: Optional[np.ndarray] = None
        self._cand_dirty = True
        self._plans_since_sweep = 0
        self.sweep_count = 0
        # why each host-tier re-sweep ran (obs: solver_resweeps counter;
        # the device tier regenerates candidates every plan on-device,
        # so it never re-sweeps and these stay zero)
        self.sweep_reasons: dict = {"cold": 0, "delta": 0, "cadence": 0}
        self.last_sweep_ms = 0.0

        self.last_ingest_ms = 0.0
        self.last_solve_ms = 0.0
        self.last_extract_ms = 0.0
        self.solve_count = 0
        self.first_device_solve_s = 0.0
        self.device_failures = 0

    def facts(self) -> dict:
        """Which path answered, and how often (PlanEngine.solver_facts).
        Both tiers run the candidate sweep on the mesh; the name says
        where the auction runs."""
        return {
            "path": "mesh-device" if self.auction == "device"
            else "mesh-host",
            "device_solves": self.solve_count,
            "host_solves": 0,
            "device_failures": self.device_failures,
            "first_device_solve_s": round(self.first_device_solve_s, 3),
            # distinct devices holding a shard of the resident task
            # table (0 until the first ingest builds it)
            "table_devices": 0 if self._dev_tp is None else len(
                {sh.device for sh in self._dev_tp.addressable_shards}),
        }

    # ------------------------------------------------------------------
    def set_job_bias(self, job_weights: Optional[dict]) -> bool:
        """Install new fair-share biases and invalidate every cached
        task row (packed prios embed the bias; the stamp/tuple caches
        compare RAW snapshot tuples, which a weight change does not
        touch — so they must be dropped, not diffed). The view path
        needs no flush here: a weight change forces the ledger's own
        full rebuild, which bumps every slot generation."""
        bias = bias_vector(job_weights, self.max_jobs)
        if bias == self.job_bias:
            return False
        self.job_bias = bias
        self._task_cache.clear()
        self._task_stamp.clear()
        self._cand_dirty = True
        return True

    def _ensure_built(self) -> None:
        if self._gather_fn is not None:
            return
        from adlb_tpu.utils.jaxenv import ensure_compile_cache

        ensure_compile_cache()
        self._gather_fn = _build_gather_fn(self.mesh, self.T, self.D)
        self._shard = NamedSharding(self.mesh, P("s", None))
        self._shard1 = NamedSharding(self.mesh, P("s"))
        self._devices = list(self.mesh.devices.reshape(-1))
        self._Sl = self.S // self.ndev
        # the resident table is kept as per-device shard pieces: a delta
        # re-uploads only the touched devices' [Sl, K] blocks (a few KB)
        # and the sharded array reassembles around the untouched ones
        # zero-copy — no mesh-wide scatter dispatch, no replication of
        # update args to every device
        self._piece_p = [None] * self.ndev
        self._piece_t = [None] * self.ndev
        self._piece_r = [None] * self.ndev
        self._reload_devices(range(self.ndev))

    def _reload_devices(self, devs) -> None:
        Sl = self._Sl
        for d in devs:
            blk = slice(d * Sl, (d + 1) * Sl)
            self._piece_p[d] = jax.device_put(
                self._tp[blk], self._devices[d])
            self._piece_t[d] = jax.device_put(
                self._tt[blk], self._devices[d])
            # free rows upload rank 0: they are dead (priority floor),
            # so their gids can never surface as candidates
            self._piece_r[d] = jax.device_put(
                np.maximum(self._row_rank[blk], 0).astype(np.int32),
                self._devices[d])
        shape = (self.S, self.K)
        self._dev_tp = jax.make_array_from_single_device_arrays(
            shape, self._shard, self._piece_p)
        self._dev_tt = jax.make_array_from_single_device_arrays(
            shape, self._shard, self._piece_t)
        self._dev_rk = jax.make_array_from_single_device_arrays(
            (self.S,), self._shard1, self._piece_r)

    def _map_server(self, s) -> Optional[int]:
        si = self._si.get(s)
        if si is not None:
            return si
        if self._free_si:
            si = self._free_si.pop()
        elif self._next_si < self.S:
            si = self._next_si
            self._next_si += 1
        else:
            # beyond capacity: unmapped until a registered server dies
            # (ingest still re-diffs every REGISTERED server each
            # round, so capacity overflow never leaves stale resident
            # rows — only unplanned extras)
            return None
        if s * self.K + self.K - 1 > _I32MAX:
            raise ValueError(
                f"server rank {s} overflows the int32 gid space "
                f"(rank * max_tasks_per_server must stay under 2**31)")
        # slots are free-listed and NEVER remapped: the auction
        # tie-break is the rank-keyed gid (rank * K + ki), not the row
        # index, so an elastic join patches one row instead of
        # re-packing the world
        self._servers.append(s)
        self._si[s] = si
        self._row_rank[si] = s
        self._row_perm = None  # rank order changed: rebuild lazily
        return si

    def _unregister(self, s, changed: list) -> None:
        """A vanished server (drain/failover): clear its resident rows
        and recycle the slot. Rank-keyed gids make this purely local —
        no other row moves, and the slot's next tenant brings its own
        gid range."""
        si = self._si.pop(s)
        self._servers.remove(s)
        if (self._tp[si] > int(_NEG)).any():
            changed.append(si)
            # the host-tier candidate patch must drop this rank's
            # entries even after row_rank forgets it
            self._dropped_ranks.add(int(s))
        self._tp[si, :] = int(_NEG)
        self._tt[si, :] = -1
        self._task_n[si] = 0
        base = si * self.R
        if self._req_valid[base:base + self.R].any():
            self._req_valid[base:base + self.R] = False
            self._req_mask[base:base + self.R, :] = False
            for i in range(self.R):
                self._req_ref[base + i] = None
            self._reqs_dirty = True
        self._task_cache.pop(s, None)
        self._req_cache.pop(s, None)
        self._task_stamp.pop(s, None)
        self._req_stamp.pop(s, None)
        self._row_rank[si] = -1
        self._free_si.append(si)
        self._row_perm = None  # rank order changed: rebuild lazily

    def _pack_tasks(self, s: int, tasks: tuple) -> None:
        si = self._si[s]
        row_p = self._tp[si]
        row_t = self._tt[si]
        row_p.fill(int(_NEG))
        row_t.fill(-1)
        row_s = self._task_seq[si]
        # task tuples are (seqno, type, prio, len) — a 5th (job)
        # element rides along under multi-job planning; index, don't
        # unpack. The composite index / weight bias handling is the
        # exact twin of solve.py's dict packer and ledger._rebuild_tasks
        J, bias, nb = self.max_jobs, self.job_bias, len(self.job_bias)
        for ki, tk in enumerate(tasks[: self.K]):
            seqno, wtype, prio = tk[0], tk[1], tk[2]
            jb = (tk[4] if len(tk) > 4 else 0) if J > 1 else 0
            b = bias[jb] if 0 <= jb < nb else 0
            row_p[ki] = max(-_PRIO_CLIP, min(_PRIO_CLIP, prio)) + b
            row_t[ki] = self.type_index.get(
                wtype if J <= 1 else (jb, wtype), -1)
            row_s[ki] = seqno
        self._task_n[si] = min(len(tasks), self.K)
        self._task_cache[s] = tasks

    def _pack_reqs(self, s: int, reqs: tuple) -> None:
        si = self._si[s]
        R = self.R
        base = si * R
        self._req_valid[base: base + R] = False
        self._req_mask[base: base + R, :] = False
        for ri in range(R):
            self._req_ref[base + ri] = None
        J, T0 = self.max_jobs, self.base_T
        for ri, req in enumerate(reqs[:R]):
            # req tuples are (rank, rqseqno, types|None) — a 4th
            # (fused-reserve) element may ride along since the
            # remote-fused-fetch change, and a 5th (job) since
            # multi-job planning; index, don't unpack. Job handling
            # twins ledger._rebuild_reqs exactly: any-type = job-block
            # mask, overflow job = empty mask
            rank, rqseqno, req_types = req[0], req[1], req[2]
            jb = (req[4] if len(req) > 4 else 0) if J > 1 else 0
            i = base + ri
            self._req_valid[i] = True
            if J > 1 and not 0 <= jb < J:
                pass  # overflow job: planner-invisible
            elif req_types is None:
                if J <= 1:
                    self._req_mask[i, :] = True
                else:
                    self._req_mask[i, jb * T0:(jb + 1) * T0] = True
            else:
                for t in req_types:
                    ti = self.type_index.get(t if J <= 1 else (jb, t))
                    if ti is not None:
                        self._req_mask[i, ti] = True
            self._req_ref[i] = (s, rank, rqseqno)
        self._req_cache[s] = reqs
        self._reqs_dirty = True

    # ------------------------------------------------------------------
    def ingest(self, snapshots: dict) -> int:
        """Diff snapshots against the resident state; ship only changed
        server rows to the device mesh. Returns changed-row count."""
        t0 = time.perf_counter()
        self._ensure_built()
        changed: list[int] = []
        planned = self._planned_servers
        # every snapshot is OFFERED a row (registered servers always
        # keep theirs; new ones register while capacity lasts, extras
        # map to None). Slicing to the lowest-S ranks here instead
        # would strand a registered server outside the slice: still in
        # `snapshots`, so the vanished-server sweep below never clears
        # it, and its frozen rows would keep winning auctions.
        for s in sorted(snapshots):
            si = self._map_server(s)
            if si is None:
                continue
            snap = snapshots[s]
            # the key tuples pair the snapshot stamps with the
            # event-delta sequences (in-place snapshot mutations carry
            # no stamp bump — see server._merge_task_delta) and the
            # engine's ledger stamp (our plans change the filtered view
            # with no snapshot at all). Compared for (in)equality ONLY:
            # the components come from different hosts' monotonic
            # clocks, so ordering across them is meaningless.
            led = snap.get("ledger_stamp")
            tstamp = snap.get("task_stamp", snap.get("stamp"))
            tkey = (tstamp, snap.get("delta_seq", 0), led)
            if (
                tstamp is None
                or s in planned
                or self._task_stamp.get(s) != tkey
            ):
                tasks = tuple(map(tuple, snap["tasks"][: self.K]))
                if self._task_cache.get(s) != tasks:
                    self._pack_tasks(s, tasks)
                    changed.append(self._si[s])
                if tstamp is not None:
                    self._task_stamp[s] = tkey
            rstamp = snap.get("stamp")
            rkey = (rstamp, snap.get("req_seq", 0), led)
            if (
                rstamp is None
                or s in planned
                or self._req_stamp.get(s) != rkey
            ):
                reqs = tuple(map(tuple, snap["reqs"][: self.R]))
                if self._req_cache.get(s) != reqs:
                    self._pack_reqs(s, reqs)
                if rstamp is not None:
                    self._req_stamp[s] = rkey
        planned.clear()
        # servers that vanished (failover): unregister — clear their
        # rows AND free the slot for the next join. Checked every
        # ingest (O(S) dict lookups) — gating on a shrinking snapshot
        # COUNT missed a death that coincides with another server
        # joining, or a world larger than capacity S, leaving a dead
        # server's resident rows winning auctions forever
        for s in [r for r in self._servers if r not in snapshots]:
            self._unregister(s, changed)
        self._finish_ingest(changed)
        self.last_ingest_ms = (time.perf_counter() - t0) * 1e3
        return len(changed)

    def _finish_ingest(self, changed: list) -> None:
        """Shared ingest tail (tuple and view paths): ship changed
        device blocks, patch or dirty the host tier's merged candidate
        lists, rebuild the requester slot windows."""
        if self._full_reload:
            self._reload_devices(range(self.ndev))
            self._full_reload = False
            self._cand_dirty = True
        elif changed:
            self._reload_devices(sorted({si // self._Sl for si in changed}))
            if self.auction == "device":
                # the device tier regenerates candidates from the
                # resident table every plan — nothing to patch
                self._dropped_ranks.clear()
            elif (
                self._gp is None
                or len(changed) > max(self.DELTA_RESYNC_ROWS, self.ndev)
            ):
                self._cand_dirty = True
            else:
                self._patch_candidates(changed)
        if self._reqs_dirty:
            if self._row_perm is None:
                # rank-sorted slots first, then the unused slots (all
                # their rows invalid — order among them is irrelevant)
                used = sorted(self._si.items())  # (rank, si) rank-asc
                rest = sorted(
                    set(range(self.S)) - {si for _, si in used})
                slot_seq = np.asarray(
                    [si for _, si in used] + rest, dtype=np.int64)
                self._row_perm = (
                    slot_seq[:, None] * self.R
                    + np.arange(self.R, dtype=np.int64)[None, :]
                ).reshape(-1)
            self._rw, self._lens = _reqwin(
                self._req_mask, self._req_valid, self.T, self.C,
                self._row_perm)
            if self.auction == "device":
                self._build_req_tables()
            self._reqs_dirty = False

    def _build_req_tables(self) -> None:
        """Device-tier requester tables: compact the reqwin row ids to
        a dense [0, U) id space (U = T*C static; U itself is the dump
        id) so the on-device auction's winner/open scatters are a few
        KB, independent of the requester-table depth."""
        U = self.T * self.C
        flat = self._rw.reshape(-1)
        pos = np.flatnonzero(flat >= 0)
        uniq, inv = np.unique(flat[pos], return_inverse=True)
        rwc = np.full((self.T * self.C,), U, dtype=np.int32)
        rwc[pos] = inv.astype(np.int32)
        self._rwc = rwc.reshape(self.T, self.C)
        open0 = np.zeros((U + 1,), dtype=bool)
        open0[: uniq.size] = True
        self._open0 = open0

    def _ingest_view(self, view) -> int:
        """Delta ingest from the engine's array-resident host ledger:
        copy the packed rows of every slot whose ledger generation
        moved since we last consumed it. The ledger already applied the
        plan-mark/suppression filtering, so there is no stamp-key
        bookkeeping and no tuple compare here — the generation counters
        ARE the change signal (they cover in-place deltas, dead-rank
        patches, and the engine's own plan touches alike).

        Fully vectorized: the changed-slot set is two numpy compares
        against the seen-generation mirrors, and the O(S) membership
        walk runs only when the ledger's ``member_gen`` moved (churn) —
        a steady-state round does O(changed) python work, which is what
        holds the idle planning round flat at 10k servers."""
        t0 = time.perf_counter()
        self._ensure_built()
        # layout agreement is load-bearing: refs index [K]/[R] rows
        assert (view.K, view.R, tuple(view.types)) == (
            self.K, self.R, self.types)
        changed: list[int] = []
        ncap = view.t_gen.shape[0]
        if (
            view.member_gen != self._seen_member_gen
            or self._seen_tgen is None
            or self._seen_tgen.shape[0] != ncap
        ):
            # membership walk (cold start / churn / ledger realloc):
            # register joins, unregister vanished ranks (a death may
            # coincide with a join or a beyond-capacity world, so the
            # check is membership-exact, not count-based), grow the
            # seen-generation mirrors
            fresh: list = []
            for s in view.servers:
                if s not in self._si and self._map_server(s) is not None:
                    fresh.append(s)
            sset = set(view.servers)
            for s in [r for r in self._servers if r not in sset]:
                self._unregister(s, changed)
            old_t, old_r = self._seen_tgen, self._seen_rgen
            self._seen_tgen = np.zeros(ncap, np.int64)
            self._seen_rgen = np.zeros(ncap, np.int64)
            if old_t is not None:
                n = min(old_t.shape[0], ncap)
                self._seen_tgen[:n] = old_t[:n]
                self._seen_rgen[:n] = old_r[:n]
            for s in fresh:
                # a rank we just registered (join, or an extra that
                # finally got capacity): its slot gens may predate our
                # mirror — force the copy (gen 0 precedes every bump)
                slot = view.slot_of(s)
                self._seen_tgen[slot] = 0
                self._seen_rgen[slot] = 0
            self._seen_member_gen = view.member_gen
        R = self.R
        slot_rank = view.slot_rank
        for slot in np.flatnonzero(
                view.t_gen != self._seen_tgen).tolist():
            self._seen_tgen[slot] = view.t_gen[slot]
            si = self._si.get(int(slot_rank[slot]))
            if si is None:
                continue  # freed slot, or beyond-capacity extra
            self._tp[si, :] = view.pk_tp[slot]
            self._tt[si, :] = view.pk_tt[slot]
            self._task_seq[si, :] = view.pk_ts[slot]
            self._task_n[si] = view.pk_tn[slot]
            changed.append(si)
        for slot in np.flatnonzero(
                view.r_gen != self._seen_rgen).tolist():
            self._seen_rgen[slot] = view.r_gen[slot]
            si = self._si.get(int(slot_rank[slot]))
            if si is None:
                continue
            base = si * R
            self._req_valid[base:base + R] = view.pk_rv[slot]
            self._req_mask[base:base + R, :] = view.pk_rm[slot]
            rrefs = view.pk_rrefs[slot]
            for i in range(R):
                self._req_ref[base + i] = rrefs[i]
            self._reqs_dirty = True
        # plan() keeps recording its touches for the tuple path; the
        # view path's generations already carry them — drop so the set
        # cannot grow unboundedly
        self._planned_servers.clear()
        self._finish_ingest(changed)
        self.last_ingest_ms = (time.perf_counter() - t0) * 1e3
        return len(changed)

    def _patch_candidates(self, changed: list) -> None:
        """Patch the merged candidate lists for a small delta by
        re-merging every AFFECTED SHARD whole from the host mirror —
        not just the changed servers' rows: a sweep's per-shard top-D
        window can have excluded a shard-mate's lower-priority tasks,
        and when a delta drains the shard's top entries those must
        resurface immediately, not at the next resync. The result
        equals (is a superset of, truncated at the same capacity) what
        a fresh sweep would produce down to every auction-reachable
        rank (D), as long as a type's list stays under its capacity L.
        A type that saturates L gets truncated at the TAIL (still exact
        to depth D this round) and flags a full mesh re-sweep for the
        next plan, so deep-tail entries can never silently go missing
        across rounds."""
        K = self.K
        Sl = self._Sl
        gp, gg = self._gp, self._gg
        L = gp.shape[1]
        # shards whose sweep window truncated nothing hold ALL their
        # live entries in the merged lists, so patching just the
        # changed servers' rows is exact and O(delta). A truncated
        # shard must re-merge WHOLE from the host mirror (its
        # shard-mates' beyond-window tasks may need to resurface) —
        # after which it is complete and drops out of the set.
        heavy = sorted({
            d for d in {si // Sl for si in changed}
            if self._shard_trunc[d]
        })
        row_set = sorted(
            set(changed)
            | {r for d in heavy for r in range(d * Sl, (d + 1) * Sl)}
        )
        rows = np.asarray(row_set, dtype=np.int64)
        # entries are dropped by the RANK their gid carries — the
        # affected rows' current tenants plus any rank whose slot was
        # freed since the last patch (its row_rank is already recycled)
        ranks = {int(r) for r in self._row_rank[rows] if r >= 0}
        ranks |= self._dropped_ranks
        self._dropped_ranks = set()
        drop = np.isin(
            gg // K, np.asarray(sorted(ranks), dtype=np.int64)
        ) & (gp > int(_NEG))
        for d in heavy:
            self._shard_trunc[d] = False
        # fresh entries: the affected rows' blocks from the host mirror
        # (freed rows carry rank -1 — negative gids, excluded by `live`)
        new_gid = (self._row_rank[rows][:, None] * K
                   + np.arange(K, dtype=np.int64)[None, :]).reshape(-1)
        new_p = self._tp[rows].reshape(-1)
        new_t = self._tt[rows].reshape(-1)
        live = (new_p > int(_NEG)) & (new_t >= 0)
        for t in range(self.T):
            sel = live & (new_t == t)
            keep = ~drop[t] & (gp[t] > int(_NEG))
            merged_p = np.concatenate([gp[t][keep], new_p[sel]])
            merged_g = np.concatenate([gg[t][keep], new_gid[sel]])
            # stable prio sort alone is not gid-exact across the two
            # concatenated pieces; sort one composite (prio, -gid) key,
            # then truncate the sorted result to capacity (never the
            # kept list before merging — that dropped live candidates)
            ck = merged_p.astype(np.int64) * (1 << 32) + (
                (1 << 32) - 1 - merged_g)
            order = np.argsort(-ck)[:L]
            n = order.shape[0]
            if merged_p.shape[0] > L:
                self._cand_dirty = True  # saturated: re-sweep next plan
            gp[t, :n] = merged_p[order]
            gg[t, :n] = merged_g[order]
            gp[t, n:] = int(_NEG)
            gg[t, n:] = _I32MAX

    def _sweep(self) -> None:
        """Full device sweep: the sharded candidate generation on the
        mesh plus the ONE device->host transfer of the planning round,
        re-materializing the merged candidate lists."""
        t0 = time.perf_counter()
        cp, cg = self._gather_fn(self._dev_tp, self._dev_tt,
                                 self._dev_rk)
        # read shard-by-shard: the sharded array's own __array__
        # assembly is an order of magnitude slower on host-platform
        # meshes
        self._gp, self._gg = _merge_shard_major(
            _sharded_to_host(cp), _sharded_to_host(cg))
        self._dropped_ranks.clear()  # re-materialized from live rows
        self._gg = self._gg.astype(np.int64)
        self._gp = self._gp.astype(np.int64)
        # which shards' top-D windows truncated anything: per-(shard,
        # type) live counts over the host mirror (one bincount)
        live = (self._tp > int(_NEG)) & (self._tt >= 0)
        shard_ids = np.repeat(
            np.arange(self.ndev, dtype=np.int64), self._Sl * self.K)
        keys = shard_ids[live.reshape(-1)] * self.T + np.clip(
            self._tt.reshape(-1)[live.reshape(-1)], 0, self.T - 1)
        counts = np.bincount(keys, minlength=self.ndev * self.T)
        self._shard_trunc = (
            counts.reshape(self.ndev, self.T) > self.D).any(axis=1)
        self._cand_dirty = False
        self._plans_since_sweep = 0
        self.sweep_count += 1
        self.last_sweep_ms = (time.perf_counter() - t0) * 1e3

    def _device_plan(self) -> np.ndarray:
        """The device-tier planning round: one jitted dispatch of the
        fused candidate-gen/merge/auction program, one [T, C+1]
        readback (shard 0 — every shard holds the replicated answer)."""
        if self._plan_fn is None:
            self._plan_fn = _build_plan_fn(
                self.mesh, self.T, self.D, self.C, self.rounds, self.m)
        out = self._plan_fn(
            self._dev_tp, self._dev_tt, self._dev_rk,
            self._rwc, self._lens.astype(np.int32), self._open0)
        shard = min(out.addressable_shards,
                    key=lambda sh: sh.index[0].start or 0)
        return np.asarray(shard.data)[0, :, : self.C]

    def plan(self) -> list:
        """One fixed-shape planning round over the resident state."""
        if not self._req_valid.any():
            return []
        t0 = time.perf_counter()
        self._ensure_built()
        if self.auction == "device":
            assigned = self._device_plan()
        else:
            if (
                self._cand_dirty
                or self._plans_since_sweep >= self.RESYNC_INTERVAL
            ):
                self.sweep_reasons[
                    "cold" if self._gp is None
                    else "delta" if self._cand_dirty
                    else "cadence"] += 1
                self._sweep()
            self._plans_since_sweep += 1
            req_open = self._req_valid.copy()
            assigned = _host_auction(
                self._gp, self._gg, self._rw, self._lens, req_open,
                self.rounds, self.m)
        t1 = time.perf_counter()
        self.last_solve_ms = (t1 - t0) * 1e3
        pairs = []
        t_idx, c_idx = np.nonzero(assigned >= 0)
        gids = assigned[t_idx, c_idx].tolist()
        rids = self._rw[t_idx, c_idx].tolist()
        K = self.K
        for g, rid in zip(gids, rids):
            rank, ki = divmod(int(g), K)
            si = self._si.get(rank)
            rref = self._req_ref[rid]
            if si is None or ki >= self._task_n[si] or rref is None:
                continue
            # a gid carries its holder's rank (rank * K + row)
            holder, seqno = rank, int(self._task_seq[si, ki])
            req_home, for_rank, rqseqno = rref
            pairs.append((holder, seqno, req_home, for_rank, rqseqno))
            self._planned_servers.add(holder)
            self._planned_servers.add(req_home)
        self.last_extract_ms = (time.perf_counter() - t1) * 1e3
        if self.solve_count == 0:
            # set-up, not speed: build + compile (or cache load) + one plan
            self.first_device_solve_s = time.perf_counter() - t0
        self.solve_count += 1
        return pairs

    def solve(self, snapshots, world) -> list:
        """Engine-compatible one-call path: ingest deltas, then plan.
        Accepts either the filtered-snapshot dict or the engine's
        array-resident ledger view."""
        try:
            if getattr(snapshots, "is_array", False):
                self._ingest_view(snapshots)
            else:
                self.ingest(snapshots)
            return self.plan()
        except Exception:
            # counted and re-raised: the resident table, the sweep and
            # the auction are all the mesh path, and nothing below the
            # caller replaces it with a host solve
            self.device_failures += 1
            raise
