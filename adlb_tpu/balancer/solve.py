"""The batched global assignment solve.

Inputs are fixed-shape tensors (S servers x K tasks, S x R requesters, T
types) so the jitted computation never recompiles; variable-size queue state
is truncated on the host side (highest priorities first) and anything that
does not fit is simply handled next round — staleness is already part of the
protocol contract (plan entries are validated against live state at
enactment, like the reference's push/RFR races, ``src/adlb.c:2182-2192``).

Algorithm (single device): exact sequential greedy under ``lax.scan`` — tasks
in descending priority order (stable, so FIFO on ties, matching the
reference's algebraically-largest-``work_prio`` + seqno contract), each
taking the first open compatible requester. One scan step is O(NR) vector
work; the whole solve is one fused loop on device. This is exactly the
matching the reference's per-server ``wq_find_hi_prio`` loop would produce if
it could see every server's queue at once (reference ``src/xq.c:190-247``) —
which is the point: same semantics, global scope, O(1) staleness.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from adlb_tpu.balancer.jobdim import bias_vector, expand_types
from adlb_tpu.runtime.trace import span

# Sentinel far below any real priority (int32-safe; real priorities are
# clipped to +/-1e9, reference priorities are C ints). A plain int, NOT a
# jnp scalar: materializing a device array at import would initialize the
# accelerator backend for every importer — and a chip belongs to the one
# process that initialized it, so an importer that only ever uses the
# numpy host path would take it from the process that plans on it.
_NEG = -(2**31) + 1
_PRIO_CLIP = 10**9
_I32MAX = 2**31 - 1


def _stable_argsort2(primary, secondary):
    """argsort by (primary asc, secondary asc, index asc) — the
    lexsort((secondary, primary)) order — composed from two single-key
    stable sorts (XLA's variadic comparator sort is ~10x slower on CPU
    hosts than its single-key fast path).  Shared by the sharded
    candidate generation and the on-device auction
    (balancer/distributed.py)."""
    o1 = jnp.argsort(secondary, stable=True)
    o2 = jnp.argsort(primary[o1], stable=True)
    return o1[o2]


def _stable_argsort3(primary, secondary, tertiary):
    """argsort by (primary asc, secondary asc, tertiary asc) from three
    composed single-key stable sorts — innermost key first."""
    o = jnp.argsort(tertiary, stable=True)
    o = o[jnp.argsort(secondary[o], stable=True)]
    return o[jnp.argsort(primary[o], stable=True)]


@jax.jit
def _greedy_assign(
    task_prio: jax.Array,  # [NT] int32, _NEG for padding
    task_type: jax.Array,  # [NT] int32 type *index*, -1 for padding
    req_mask: jax.Array,  # [NR, T] bool: requester accepts type index
    req_valid: jax.Array,  # [NR] bool
) -> jax.Array:
    """Returns assign[NR] int32: task index assigned to each requester, -1 if none."""
    NT = task_prio.shape[0]
    NR = req_mask.shape[0]
    ridx = jnp.arange(NR, dtype=jnp.int32)

    # descending priority, stable (ties resolve to lower task index = seqno)
    order = jnp.argsort(-task_prio, stable=True)

    def step(open_req, t_idx):
        prio = task_prio[t_idx]
        ttype = task_type[t_idx]
        compat = (
            open_req
            & req_valid
            & (prio > _NEG)
            & (ttype >= 0)
            & req_mask[:, jnp.clip(ttype, 0)]
        )
        r = jnp.argmax(compat)  # first open compatible requester
        found = compat[r]
        open_req = open_req & ~(found & (ridx == r))
        return open_req, jnp.where(found, r.astype(jnp.int32), jnp.int32(-1))

    open0 = jnp.ones((NR,), dtype=bool)
    _, winner_per_task = jax.lax.scan(step, open0, order)
    # invert: winner_per_task[k] is the requester chosen for task order[k]
    # (-1 = none). Requesters win at most once, so the scatter is 1-1.
    valid = winner_per_task >= 0
    assign = jnp.full((NR,), -1, dtype=jnp.int32)
    assign = assign.at[jnp.where(valid, winner_per_task, NR)].set(
        jnp.where(valid, order.astype(jnp.int32), -1), mode="drop"
    )
    return assign


def _host_greedy(task_prio, task_type, req_mask, req_valid):
    """Numpy twin of :func:`_greedy_assign` — bit-identical semantics, used
    below a size threshold where an accelerator dispatch round-trip costs
    more than the whole solve.

    Considers only tasks whose type some open requester accepts (tasks of
    other types can never match, so skipping them cannot change the greedy
    outcome) and early-exits once every requester is matched — so a round
    where the only parked requester wants a type with no queued inventory
    (gfmc's answer collector) costs one vectorized mask, not a scan."""
    NR = req_mask.shape[0]
    assign = np.full((NR,), -1, dtype=np.int32)
    open_req = req_valid.copy()
    n_open = int(open_req.sum())
    if n_open == 0:
        return assign
    wanted = req_mask[open_req].any(axis=0)  # [T]
    live = (task_prio > int(_NEG)) & (task_type >= 0)
    live &= wanted[np.clip(task_type, 0, None)]
    cand = np.nonzero(live)[0]
    if cand.size == 0:
        return assign
    order = cand[np.argsort(-task_prio[cand], kind="stable")]
    for t in order:
        tt = task_type[t]
        compat = open_req & req_mask[:, tt]
        r = int(np.argmax(compat))
        if not compat[r]:
            continue
        assign[r] = t
        open_req[r] = False
        n_open -= 1
        if n_open == 0:
            break
    return assign


class AssignmentSolver:
    """Host-side wrapper: packs per-server snapshots into fixed-shape arrays,
    runs the greedy solve, unpacks plan entries.

    Adaptive placement: instances with few live requesters run the numpy twin
    on the host (an accelerator dispatch round-trip would dominate); larger
    instances run the jitted scan on device. Both produce the identical
    matching (same greedy order), so the threshold is purely a latency
    knob.

    ``solve`` also accepts the engine's array-resident host ledger (a
    :class:`adlb_tpu.balancer.ledger.ArrayLedger` view) in place of the
    snapshot dict: the packed kept-requester / eligible-task rows are
    consumed directly — no per-row tuple walk — and the matching is
    identical to the dict path (fuzz-proven by tests/test_ledger_parity)."""

    #: the engine may hand solve() a LedgerView instead of a snapshot dict
    SUPPORTS_VIEW = True

    #: parked requesters at or below which a round runs the numpy twin,
    #: unless the caller sets ``host_threshold_reqs``
    DEFAULT_HOST_THRESHOLD = 64

    def __init__(
        self, types: Sequence[int], max_tasks: int, max_requesters: int,
        rounds: int = 6,
        host_threshold_reqs: Optional[int] = DEFAULT_HOST_THRESHOLD,
        backend: str = "xla", max_jobs: int = 1,
        job_weights: Optional[dict] = None, metrics=None,
        nservers: int = 0,
    ) -> None:
        """backend: "xla" = the jitted lax.scan greedy; "pallas" = the
        VMEM-resident Pallas sweep kernel (adlb_tpu.balancer.pallas_solve),
        interpreted off-TPU; "auto" = pallas on a real TPU backend (where it
        measures ~4x faster than the scan at S*K=1024), xla elsewhere (the
        interpreted kernel is too slow to be the default on CPU). All
        backends produce the identical matching. "auto" is resolved lazily
        at the first device solve — probing jax.default_backend() here would
        initialize the accelerator for hosts whose every solve stays on the
        numpy path.

        metrics: the engine's obs registry, or None; the ``adlb.solve.*``
        spans (pack, put, call, wait, get | host, extract) observe into
        it.

        nservers: the world's server count. A device solve is padded to
        at least that many servers' rows: a program is built per shape,
        and a world's first rounds see only the servers that have
        reported so far. Unpadded, each such count is a program of its
        own (a Pallas compile is 12 s on a v5e, and the workers wait for
        it); padded, they run what every later round runs."""
        if backend not in ("auto", "xla", "pallas"):
            raise ValueError(f"unknown solver backend {backend!r}")
        self.base_types = tuple(types)
        self.base_T = max(len(self.base_types), 1)
        self.max_jobs = max(int(max_jobs), 1)
        # composite (job, type) axis under multi-job planning — the
        # base types verbatim when single-job (balancer/jobdim.py)
        self.types = expand_types(self.base_types, self.max_jobs)
        self.job_bias = bias_vector(job_weights, self.max_jobs)
        self.type_index = {t: i for i, t in enumerate(self.types)}
        self.K = max_tasks
        self.R = max_requesters
        self.rounds = rounds
        self.host_threshold_reqs = host_threshold_reqs
        self.nservers = nservers
        self.backend = backend
        self.metrics = metrics
        self._device_fn = None  # lazily resolved (pallas import is deferred)
        # which program answers device solves, once one has been built:
        # "numpy" until then (every solve so far ran the host twin), then
        # "xla" | "pallas" | "pallas-interpret"
        self.path = "numpy"
        self.solve_count = 0
        self.host_solve_count = 0
        self.device_solve_count = 0
        self.first_device_solve_s = 0.0
        self.device_failures = 0
        # rounds the DEFAULT threshold would have sent to the device,
        # whatever threshold is in force: what a forced-device or
        # forced-host run says about the default placement rule
        self.over_default_count = 0

    def set_job_bias(self, job_weights: Optional[dict]) -> bool:
        """Install new fair-share biases for the dict-path packers (the
        view path inherits the ledger's — the engine keeps both in
        step). Returns True when the bias changed."""
        bias = bias_vector(job_weights, self.max_jobs)
        if bias == self.job_bias:
            return False
        self.job_bias = bias
        return True

    def _place_on_host(self, n_reqs: int) -> bool:
        if n_reqs > self.DEFAULT_HOST_THRESHOLD:
            self.over_default_count += 1
        return (
            self.host_threshold_reqs is not None
            and n_reqs <= self.host_threshold_reqs
        )

    def _device_assign(self):
        if self._device_fn is None:
            from adlb_tpu.utils.jaxenv import ensure_compile_cache

            ensure_compile_cache()
            on_tpu = jax.default_backend() == "tpu"
            backend = self.backend
            if backend == "auto":
                backend = "pallas" if on_tpu else "xla"
            if backend == "pallas":
                from adlb_tpu.balancer.pallas_solve import make_pallas_assign

                # off-TPU the kernel can only be interpreted; the path
                # name says so, so no caller mistakes it for Mosaic
                self._device_fn = make_pallas_assign(interpret=not on_tpu)
                self.path = "pallas" if on_tpu else "pallas-interpret"
            else:
                self._device_fn = _greedy_assign
                self.path = "xla"
        return self._device_fn

    def _device_solve(self, task_prio, task_type, req_mask, req_valid):
        """One device solve, read back to numpy. A failure is counted and
        re-raised: nothing below the caller turns it into a host solve."""
        t0 = time.perf_counter()
        reg = self.metrics
        n_reqs = req_valid.shape[0]
        short = self.nservers - n_reqs // self.R
        if short > 0:
            # servers yet to report: rows no task or requester fills,
            # behind the real ones, so every index stays what it was
            task_prio = np.concatenate(
                [task_prio, np.full(short * self.K, _NEG, task_prio.dtype)])
            task_type = np.concatenate(
                [task_type, np.full(short * self.K, -1, task_type.dtype)])
            req_mask = np.concatenate(
                [req_mask, np.zeros((short * self.R,) + req_mask.shape[1:],
                                    req_mask.dtype)])
            req_valid = np.concatenate(
                [req_valid, np.zeros(short * self.R, req_valid.dtype)])
        try:
            fn = self._device_assign()
            with span("adlb.solve.put", reg):
                args = (
                    jnp.asarray(task_prio),
                    jnp.asarray(task_type),
                    jnp.asarray(req_mask),
                    jnp.asarray(req_valid),
                )
            with span("adlb.solve.call", reg):
                out = fn(*args)
            # the read-back below would block here anyway: the wait only
            # parts the device's time from the copy's
            with span("adlb.solve.wait", reg):
                out.block_until_ready()
            with span("adlb.solve.get", reg):
                assign = np.asarray(out)[:n_reqs]
        except Exception:
            self.device_failures += 1
            raise
        if self.device_solve_count == 0:
            # set-up, not speed: backend start-up + compile (or cache
            # load) + one solve, paid inside the first device round
            self.first_device_solve_s = time.perf_counter() - t0
        self.device_solve_count += 1
        return assign

    def facts(self) -> dict:
        """Which path answered, and how often (PlanEngine.solver_facts)."""
        return {
            "path": self.path,
            "device_solves": self.device_solve_count,
            "host_solves": self.host_solve_count,
            "device_failures": self.device_failures,
            "first_device_solve_s": round(self.first_device_solve_s, 3),
            "rounds_over_default_threshold": self.over_default_count,
        }

    def solve(self, snapshots, world) -> list:
        """snapshots: server_rank -> {"tasks": [(seqno, type, prio, len)...],
        "reqs": [(rank, rqseqno, req_types|None)...]} — or an
        ArrayLedger view (see class docstring).

        Returns [(holder_server, seqno, req_home_server, for_rank, rqseqno)].
        """
        if getattr(snapshots, "is_array", False):
            return self._solve_view(snapshots)
        reg = self.metrics
        with span("adlb.solve.pack", reg):
            packed = self._pack(snapshots)
        if packed is None:
            return []
        host, task_prio, task_type, req_mask, req_valid, task_ref, req_ref \
            = packed
        if host:
            with span("adlb.solve.host", reg):
                assign = _host_greedy(
                    task_prio, task_type, req_mask, req_valid)
            self.host_solve_count += 1
        else:
            assign = self._device_solve(
                task_prio, task_type, req_mask, req_valid)
        self.solve_count += 1

        pairs = []
        with span("adlb.solve.extract", reg):
            for i, t in enumerate(assign):
                if t < 0 or req_ref[i] is None or task_ref[t] is None:
                    continue
                holder, seqno = task_ref[t]
                req_home, for_rank, rqseqno = req_ref[i]
                pairs.append((holder, seqno, req_home, for_rank, rqseqno))
        return pairs

    def _pack(self, snapshots):
        """The dict packer: ``(host, task_prio, task_type, req_mask,
        req_valid, task_ref, req_ref)``, or None when nothing can match."""
        servers = sorted(snapshots)
        S, K, R, T = len(servers), self.K, self.R, len(self.types)
        if S == 0:
            return None
        req_mask = np.zeros((S * R, T), dtype=bool)
        req_valid = np.zeros((S * R,), dtype=bool)
        req_ref: list = [None] * (S * R)
        J, T0 = self.max_jobs, self.base_T
        for si, s in enumerate(servers):
            # req tuples are (rank, rqseqno, types) — a 4th element
            # (fused-reserve flag, consumed by the plan-match sender)
            # may ride along since the remote-fused-fetch change, and a
            # 5th (job) since multi-job planning. Job handling is the
            # exact twin of ledger._rebuild_reqs: any-type becomes a
            # job-block mask, overflow jobs pack an empty mask.
            for ri, req in enumerate(snapshots[s]["reqs"][:R]):
                rank, rqseqno, req_types = req[0], req[1], req[2]
                jb = (req[4] if len(req) > 4 else 0) if J > 1 else 0
                i = si * R + ri
                req_valid[i] = True
                if J > 1 and not 0 <= jb < J:
                    pass  # overflow job: planner-invisible
                elif req_types is None:
                    if J <= 1:
                        req_mask[i, :] = True
                    else:
                        req_mask[i, jb * T0:(jb + 1) * T0] = True
                else:
                    for t in req_types:
                        ti = self.type_index.get(t if J <= 1 else (jb, t))
                        if ti is not None:
                            req_mask[i, ti] = True
                req_ref[i] = (s, rank, rqseqno)
        n_reqs = int(req_valid.sum())
        if n_reqs == 0:
            return None

        host = self._place_on_host(n_reqs)
        if host:
            # pack only tasks of a type some requester wants: others can
            # never match, and skipping them up front keeps the per-round
            # host cost proportional to useful work, not queue depth
            wanted = req_mask[req_valid].any(axis=0)  # [T]
            prios: list = []
            ttypes: list = []
            task_ref = []
            bias, nb = self.job_bias, len(self.job_bias)
            for si, s in enumerate(servers):
                for tk in snapshots[s]["tasks"][:K]:
                    seqno, wtype, prio = tk[0], tk[1], tk[2]
                    jb = (tk[4] if len(tk) > 4 else 0) if J > 1 else 0
                    ti = self.type_index.get(
                        wtype if J <= 1 else (jb, wtype), -1)
                    if ti < 0 or not wanted[ti]:
                        continue
                    b = bias[jb] if 0 <= jb < nb else 0
                    prios.append(
                        max(-_PRIO_CLIP, min(_PRIO_CLIP, prio)) + b)
                    ttypes.append(ti)
                    task_ref.append((s, seqno))
            if not task_ref:
                return None
            task_prio = np.asarray(prios, dtype=np.int32)
            task_type = np.asarray(ttypes, dtype=np.int32)
        else:
            task_prio = np.full((S * K,), int(_NEG), dtype=np.int32)
            task_type = np.full((S * K,), -1, dtype=np.int32)
            task_ref = [None] * (S * K)
            bias, nb = self.job_bias, len(self.job_bias)
            for si, s in enumerate(servers):
                for ki, tk in enumerate(snapshots[s]["tasks"][:K]):
                    seqno, wtype, prio = tk[0], tk[1], tk[2]
                    jb = (tk[4] if len(tk) > 4 else 0) if J > 1 else 0
                    i = si * K + ki
                    b = bias[jb] if 0 <= jb < nb else 0
                    task_prio[i] = \
                        max(-_PRIO_CLIP, min(_PRIO_CLIP, prio)) + b
                    task_type[i] = self.type_index.get(
                        wtype if J <= 1 else (jb, wtype), -1)
                    task_ref[i] = (s, seqno)
            if (task_type < 0).all():
                return None
        return host, task_prio, task_type, req_mask, req_valid, task_ref, \
            req_ref

    def _solve_view(self, view) -> list:
        """The array-ledger fast path: identical greedy matching over the
        ledger's packed per-server rows (kept requesters truncated [:R],
        eligible tasks [:K], sorted-server row order — exactly the dict
        packer's layout), with no per-row Python walk."""
        K, R, T = self.K, self.R, len(self.types)
        # the ledger is built from the same engine Config; the row
        # layouts must agree or refs would misindex
        assert (view.K, view.R, tuple(view.types)) == (K, R, self.types)
        slots = view.slot_order
        S = slots.size
        if S == 0:
            return []
        reg = self.metrics
        with span("adlb.solve.pack", reg):
            req_valid = view.pk_rv[slots].reshape(-1)
            n_reqs = int(req_valid.sum())
            if n_reqs == 0:
                return []
            req_mask = view.pk_rm[slots].reshape(S * R, T)
            task_prio = view.pk_tp[slots].reshape(-1)
            task_type = view.pk_tt[slots].reshape(-1)
        host = self._place_on_host(n_reqs)
        if host:
            # _host_greedy's internal wanted/live filter makes the
            # compacted pre-pack of the dict path unnecessary: same
            # candidates, same stable order, same matching
            with span("adlb.solve.host", reg):
                assign = _host_greedy(
                    task_prio, task_type, req_mask, req_valid)
            self.host_solve_count += 1
            if not (assign >= 0).any():
                return []
        else:
            if (task_type < 0).all():
                return []
            assign = self._device_solve(
                task_prio, task_type, req_mask, req_valid)
        self.solve_count += 1
        pairs = []
        with span("adlb.solve.extract", reg):
            slot_list = slots.tolist()
            rrefs = view.pk_rrefs
            for i in np.flatnonzero(assign >= 0).tolist():
                t = int(assign[i])
                tref = view.task_ref(slot_list[t // K], t % K)
                rref = rrefs[slot_list[i // R]][i % R]
                if tref is None or rref is None:
                    continue
                holder, seqno = tref
                req_home, for_rank, rqseqno = rref
                pairs.append((holder, seqno, req_home, for_rank, rqseqno))
        return pairs
