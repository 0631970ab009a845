"""Benchmark: TPU global balancer vs reference-style stealing heuristics.

Runs the nq and coinop workloads (the BASELINE.json configs) under both
cross-server balancing strategies implemented by this framework:

* steal — the rebuilt reference heuristics (qmstat state broadcast + RFR
  pull stealing), the stand-in for upstream ADLB's behavior;
* tpu — the periodic batched global assignment solve in JAX (the north-star
  architecture from BASELINE.json).

Output contract (round 4): the FULL detail record is printed first for
human auditing, then a COMPACT headline record is printed as the FINAL
stdout line. The driver keeps only the last ~2000 chars of output, so
the final line is guaranteed to fit and parse (round 3's grown detail
line truncated to garbage). The compact line carries every headline
field plus per-rep spreads so the claims are auditable from the driver's
record alone.

Estimator contract (round 6): the BAR metrics —
``vs_baseline`` and the per-workload keys (``nq``/``tsp``/``sudoku``/
``gfmc``/``classic_ratio``) — are the PAIRED per-rep-pair ratio medians
(phase-robust: adjacent interleaved reps share the host's hour-scale
phase, so the per-pair ratio cancels it); the pooled medians remain as
``*_pooled``. The HEADLINE scale rows are the both-modes batch:8
consumer rows ``n64b``/``n128b``; single-fetch scale rows are secondary.
"""

import json
import os
import subprocess
import sys
import time


def _require_backend(probe_timeout: float = 60.0) -> str:
    """Probe backend initialization in a subprocess, so a chip that hangs
    at start-up fails the benchmark here instead of deadlocking it later.
    Returns the platform the environment asked for; raises when the probe
    fails or hangs — the benchmark never re-pins itself to the CPU."""
    try:
        subprocess.run(
            [sys.executable, "-c", "import jax; jax.devices()"],
            timeout=probe_timeout,
            check=True,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE,
            text=True,
        )
    except subprocess.TimeoutExpired:
        raise SystemExit(
            f"bench: JAX backend did not initialize within "
            f"{probe_timeout:.0f}s") from None
    except subprocess.CalledProcessError as e:
        raise SystemExit(
            f"bench: JAX backend failed to initialize:\n"
            f"{e.stderr[-800:]}") from None
    return os.environ.get("JAX_PLATFORMS", "default")


def main() -> None:
    platform = _require_backend()

    from adlb_tpu.runtime.world import Config
    from adlb_tpu.workloads import coinop, hotspot, nq, trickle

    N = 9
    APPS, SERVERS = 6, 3
    CUTOFF = 3

    def cfg(mode: str) -> Config:
        if mode == "steal":
            # upstream-faithful baseline: the reference's qmstat is a
            # store-and-forward ring token at a fixed 0.1 s interval
            # (reference src/adlb.c:165,806-822,1705-1757); this framework's
            # improved direct-broadcast stealing is reported separately.
            return Config(
                balancer="steal",
                qmstat_mode="ring",
                qmstat_interval=0.1,
                exhaust_check_interval=0.2,
            )
        if mode == "steal_fast":
            return Config(balancer="steal", exhaust_check_interval=0.2)
        return Config(
            balancer="tpu",
            exhaust_check_interval=0.2,
            balancer_max_tasks=256,
            balancer_max_requesters=64,
        )

    # warm the solver (host path) so setup cost stays out of the timing
    from adlb_tpu.balancer.solve import AssignmentSolver

    warm = AssignmentSolver(types=(1,), max_tasks=128, max_requesters=32)
    warm.solve({0: {"tasks": [(1, 1, 1, 1)], "reqs": [(0, 1, None)]}}, None)

    def interleaved(run_one, modes=("steal", "tpu"), reps=3):
        """Alternate modes rep by rep so slow phases of the shared host
        (cron, compiles, co-tenants) hit every mode instead of skewing
        whichever mode ran last; returns {mode: [result, ...]}."""
        out = {m: [] for m in modes}
        for _ in range(reps):
            for m in modes:
                out[m].append(run_one(m))
        return out

    def median_by(rows, key=None):
        """Median-of-reps: robust to one lucky/unlucky draw per mode,
        which best-of is not (a single fast outlier in either mode skews
        the ratio on a noisy shared host)."""
        v = sorted(rows, key=key)
        return v[len(v) // 2]

    # hotspot on the ALL-NATIVE plane: C clients + C++ server daemons, every
    # rank an OS process (no GIL coupling); the Python runtime appears only
    # as the balancer sidecar. 64 app ranks / 16 servers is the scale the
    # one-interpreter harness cannot reach. Work grain 8 ms keeps the
    # single-core host scheduling-bound, not message-bound. Measured FIRST,
    # before half an hour of in-proc worlds accumulates memory pressure
    # that starves 80-process native worlds.
    from adlb_tpu.workloads import hotspot_native

    def native_cfg(mode: str) -> Config:
        if mode == "steal":
            return Config(balancer="steal", qmstat_mode="ring",
                          qmstat_interval=0.1)
        # solver_host_threshold stays until ROADMAP A2 decides the rule
        return Config(balancer="tpu", balancer_max_tasks=2048,
                      balancer_max_requesters=256,
                      solver_host_threshold=10**6)

    # AssertionError included everywhere native worlds are contained: the
    # workload wrappers fail via known-answer asserts, and a single bad
    # rep (lost unit, wrong B&B answer) must burn its own row, not the
    # whole bench record
    _NATIVE_ERRS = (RuntimeError, OSError, TimeoutError, AssertionError)

    def hot_native(mode: str, apps: int, servers: int, n: int,
                   fetch: str = "single", work_us: int = 8000):
        def one():
            r = hotspot_native.run(
                n_tasks=n, work_us=work_us, num_app_ranks=apps,
                nservers=servers, cfg=native_cfg(mode), timeout=300.0,
                fetch=fetch,
            )
            assert r.tasks == n, (
                f"native hotspot {mode}: lost work ({r.tasks})"
            )
            return r

        return one()

    try:
        # task counts follow scripts/scaling_curve.py's sizing formula
        # ((apps-1) consumer-seconds of 8 ms grain ~= 1 s ideal makespan)
        # so these rows and the curve's are the same measurement
        nat16 = interleaved(lambda m: hot_native(m, 16, 4, 1875))
        nat16_steal = median_by(nat16["steal"],
                                key=lambda r: r.tasks_per_sec)
        nat16_tpu = median_by(nat16["tpu"], key=lambda r: r.tasks_per_sec)
        # 5 interleaved reps + medians (round 4, up from 3): an
        # 81-process world on this one-core host has multi-second
        # scheduler slow phases that swing single draws ±30% in BOTH
        # modes, and the wait%% medians this row's scale story rests on
        # need more than a best-of-3 draw
        nat64 = interleaved(lambda m: hot_native(m, 64, 16, 7875),
                            reps=5)
        nat64_steal = median_by(nat64["steal"],
                                key=lambda r: r.tasks_per_sec)
        nat64_tpu = median_by(nat64["tpu"], key=lambda r: r.tasks_per_sec)
        native_rows = {
            "native_16r_steal_tasks_per_sec": round(
                nat16_steal.tasks_per_sec, 1),
            "native_16r_tpu_tasks_per_sec": round(nat16_tpu.tasks_per_sec, 1),
            "native_16r_ratio": round(
                nat16_tpu.tasks_per_sec / nat16_steal.tasks_per_sec, 3),
            "native_16r_steal_idle_pct": round(nat16_steal.idle_pct, 1),
            "native_16r_tpu_idle_pct": round(nat16_tpu.idle_pct, 1),
            "native_64r_steal_tasks_per_sec": round(
                nat64_steal.tasks_per_sec, 1),
            "native_64r_tpu_tasks_per_sec": round(nat64_tpu.tasks_per_sec, 1),
            "native_64r_ratio": round(
                nat64_tpu.tasks_per_sec / nat64_steal.tasks_per_sec, 3),
            "native_64r_steal_idle_pct": round(nat64_steal.idle_pct, 1),
            "native_64r_tpu_idle_pct": round(nat64_tpu.idle_pct, 1),
            # direct measure of time blocked acquiring work (Reserve+Get),
            # reported alongside the utilization-based idle% (nominal
            # compute over makespan, see workloads/hotspot_native.py)
            "native_16r_steal_wait_pct": round(nat16_steal.wait_pct, 1),
            "native_16r_tpu_wait_pct": round(nat16_tpu.wait_pct, 1),
            "native_64r_steal_wait_pct": round(nat64_steal.wait_pct, 1),
            "native_64r_tpu_wait_pct": round(nat64_tpu.wait_pct, 1),
            # headline consumers use the single-unit fused fetch; the
            # batched fused fetch is measured right below so the choice
            # stays a recorded measurement, not folklore
            "native_64r_tpu_fetch_mode": "single",
        }
    except _NATIVE_ERRS as e:
        # no C toolchain (or daemon spawn failure): report, don't die
        native_rows = {"native_error": repr(e)}

    # batched fused fetch delta at 64 ranks, interleaved against fresh
    # single-unit reps (not the headline pool above) so the pair shares
    # slow phases. Own try: a failure here must not discard the headline
    # rows already measured above.
    try:
        natb = interleaved(
            lambda m: hot_native("tpu", 64, 16, 7875,
                                 fetch="single" if m == "one" else "batch:8"),
            modes=("one", "batch"),
        )
        nb_one = median_by(natb["one"], key=lambda r: r.tasks_per_sec)
        nb_batch = median_by(natb["batch"], key=lambda r: r.tasks_per_sec)
        native_rows.update({
            "native_64r_tpu_batch8_tasks_per_sec": round(
                nb_batch.tasks_per_sec, 1),
            "native_64r_tpu_single_paired_tasks_per_sec": round(
                nb_one.tasks_per_sec, 1),
            "native_batch_fetch_delta_pct": round(
                100.0 * (nb_batch.tasks_per_sec / nb_one.tasks_per_sec - 1.0),
                1) if nb_one.tasks_per_sec else 0.0,
        })
    except _NATIVE_ERRS as e:
        native_rows.setdefault("native_batch_error", repr(e))

    # 64 ranks, BOTH modes on the batched fused fetch — the HEADLINE
    # 64-rank scale row since round 6 (the batched
    # consumer is the framework's own best path and the measured scale
    # story; the single-fetch rows above stay as secondary continuity
    # metrics). Identical call in both modes; batching only pays for
    # units the balancer pre-positioned locally — that asymmetry IS the
    # balancing advantage being measured.
    try:
        nb64 = interleaved(
            lambda m: hot_native(m, 64, 16, 7875, fetch="batch:8"),
        )
        nb64_steal = median_by(nb64["steal"],
                               key=lambda r: r.tasks_per_sec)
        nb64_tpu = median_by(nb64["tpu"], key=lambda r: r.tasks_per_sec)
        native_rows.update({
            "native_64r_batch8_steal_tasks_per_sec": round(
                nb64_steal.tasks_per_sec, 1),
            "native_64r_batch8_tpu_tasks_per_sec": round(
                nb64_tpu.tasks_per_sec, 1),
            "native_64r_batch8_ratio": round(
                nb64_tpu.tasks_per_sec / nb64_steal.tasks_per_sec, 3)
            if nb64_steal.tasks_per_sec else 0.0,
            "native_64r_batch8_steal_wait_pct": round(
                nb64_steal.wait_pct, 1),
            "native_64r_batch8_tpu_wait_pct": round(
                nb64_tpu.wait_pct, 1),
            "native_64r_batch8_steal_reps": [
                round(r.tasks_per_sec) for r in nb64["steal"]],
            "native_64r_batch8_tpu_reps": [
                round(r.tasks_per_sec) for r in nb64["tpu"]],
        })
    except _NATIVE_ERRS as e:
        native_rows.setdefault("native_64r_batch_error", repr(e))

    # 128 ranks on the framework's own best consumer path: BOTH modes on
    # the batched fused fetch (identical call; batching only pays for
    # units the balancer pre-positioned locally — that asymmetry IS the
    # balancing advantage). 24 ms grain as in scripts/scaling_curve.py's
    # 128-rank row (8 ms at 161 processes is kernel-scheduling-bound on
    # this one-core host). Measured 2026-07-31 development run: steal
    # 2486 vs tpu 3732 → 1.501, tpu wait 1.7-12.5%.
    try:
        nb128 = interleaved(
            lambda m: hot_native(m, 128, 32, 5291, fetch="batch:8",
                                 work_us=24000),
        )
        nb128_steal = median_by(nb128["steal"],
                                key=lambda r: r.tasks_per_sec)
        nb128_tpu = median_by(nb128["tpu"], key=lambda r: r.tasks_per_sec)
        native_rows.update({
            "native_128r_batch8_steal_tasks_per_sec": round(
                nb128_steal.tasks_per_sec, 1),
            "native_128r_batch8_tpu_tasks_per_sec": round(
                nb128_tpu.tasks_per_sec, 1),
            "native_128r_batch8_ratio": round(
                nb128_tpu.tasks_per_sec / nb128_steal.tasks_per_sec, 3)
            if nb128_steal.tasks_per_sec else 0.0,
            "native_128r_batch8_steal_wait_pct": round(
                nb128_steal.wait_pct, 1),
            "native_128r_batch8_tpu_wait_pct": round(
                nb128_tpu.wait_pct, 1),
            "native_128r_batch8_steal_reps": [
                round(r.tasks_per_sec) for r in nb128["steal"]],
            "native_128r_batch8_tpu_reps": [
                round(r.tasks_per_sec) for r in nb128["tpu"]],
        })
    except _NATIVE_ERRS as e:
        native_rows.setdefault("native_128r_batch_error", repr(e))

    # THE north-star workloads at native scale (BASELINE.json names
    # nq and tsp at 256 MPI ranks; 128 ranks is this
    # one-core host's measurable ceiling, scripts/sim_scale.py carries the
    # extrapolation) — real B&B/DFS compute, known-answer validated every
    # rep, 3 interleaved reps with medians.
    try:
        from adlb_tpu.workloads import nq_native, tsp_native

        def nq_scale_one(mode, apps, servers):
            def one():
                r = nq_native.run(
                    n=13, cutoff=3, num_app_ranks=apps, nservers=servers,
                    cfg=native_cfg(mode), timeout=420.0,
                )
                assert r.solutions == r.expected, (
                    f"nq {mode}@{apps}: {r.solutions} != {r.expected}"
                )
                return r

            return one()

        def tsp_scale_one(mode, apps, servers):
            def one():
                r = tsp_native.run(
                    n_cities=9, num_app_ranks=apps, nservers=servers,
                    cfg=native_cfg(mode), timeout=420.0,
                )
                assert r.best == r.optimum, (
                    f"tsp {mode}@{apps}: {r.best} != {r.optimum}"
                )
                return r

            return one()

        for apps, servers, tag in ((64, 16, "64r"), (128, 32, "128r")):
            for name, one in (("nq", nq_scale_one), ("tsp", tsp_scale_one)):
                # tsp@64r gets 5 reps: it is the one row whose ratio has
                # sat below 1.0, and B&B draws swing ±30% — the interval
                # needs more than a best-of-3 median
                nreps = 5 if (name == "tsp" and tag == "64r") else 3
                try:
                    runs = interleaved(lambda m: one(m, apps, servers),
                                       reps=nreps)
                except _NATIVE_ERRS as e:
                    # per-row containment: one bad scale row must not
                    # discard the remaining rows
                    native_rows[f"native_{name}_{tag}_error"] = repr(e)
                    continue
                st = median_by(runs["steal"], key=lambda r: r.tasks_per_sec)
                tp = median_by(runs["tpu"], key=lambda r: r.tasks_per_sec)
                native_rows.update({
                    f"native_{name}_{tag}_steal_tasks_per_sec": round(
                        st.tasks_per_sec, 1),
                    f"native_{name}_{tag}_tpu_tasks_per_sec": round(
                        tp.tasks_per_sec, 1),
                    f"native_{name}_{tag}_ratio": round(
                        tp.tasks_per_sec / st.tasks_per_sec, 3)
                    if st.tasks_per_sec else 0.0,
                    f"native_{name}_{tag}_steal_wait_pct": round(
                        st.wait_pct, 1),
                    f"native_{name}_{tag}_tpu_wait_pct": round(
                        tp.wait_pct, 1),
                    # per-rep spreads (full record only): every scale
                    # claim auditable from the BENCH file alone
                    f"native_{name}_{tag}_steal_reps": [
                        round(r.tasks_per_sec) for r in runs["steal"]],
                    f"native_{name}_{tag}_tpu_reps": [
                        round(r.tasks_per_sec) for r in runs["tpu"]],
                })
    except _NATIVE_ERRS as e:
        native_rows.setdefault("native_scale_error", repr(e))

    # trickle on the all-native plane: the dispatch-latency story without
    # any GIL coupling (C clients + C++ daemons; the in-proc probe's twin)
    from adlb_tpu.workloads import trickle_native

    def nat_tric_one(mode):
        if mode == "steal":
            c = Config(balancer="steal", qmstat_mode="ring",
                       qmstat_interval=0.1)
        else:
            c = Config(balancer="tpu", balancer_max_tasks=512,
                       balancer_max_requesters=64)
        return trickle_native.run(
            n_tasks=240, num_app_ranks=8, nservers=4, cfg=c, timeout=120.0,
        )

    try:
        nt_runs = interleaved(nat_tric_one)
        nt_steal = median_by(nt_runs["steal"],
                             key=lambda r: r.dispatch_p50_ms)
        nt_tpu = median_by(nt_runs["tpu"], key=lambda r: r.dispatch_p50_ms)
        native_rows.update({
            "native_trickle_p50_ms_steal": round(nt_steal.dispatch_p50_ms, 2),
            "native_trickle_p50_ms_tpu": round(nt_tpu.dispatch_p50_ms, 2),
            "native_trickle_p90_ms_steal": round(nt_steal.dispatch_p90_ms, 2),
            "native_trickle_p90_ms_tpu": round(nt_tpu.dispatch_p90_ms, 2),
            "native_dispatch_speedup": round(
                nt_steal.dispatch_p50_ms / nt_tpu.dispatch_p50_ms, 2)
            if nt_tpu.dispatch_p50_ms else 0.0,
        })
    except _NATIVE_ERRS as e:
        native_rows.setdefault("native_error", repr(e))

    # coinop on the all-native plane: the fork's own pop-latency probe
    # (reference examples/coinop.cpp) — flooded pool, so p50/p95 measure
    # pure pop service latency through the C client + C++ daemon path
    from adlb_tpu.workloads import coinop_native

    def nat_coin_one(mode):
        return coinop_native.run(
            n_tokens=400, num_app_ranks=8, nservers=4,
            cfg=native_cfg(mode), timeout=120.0,
        )

    try:
        nc_runs = interleaved(nat_coin_one)
        nc_steal = median_by(nc_runs["steal"],
                             key=lambda r: r.latency_p50_ms)
        nc_tpu = median_by(nc_runs["tpu"], key=lambda r: r.latency_p50_ms)
        native_rows.update({
            "native_coinop_p50_ms_steal": round(nc_steal.latency_p50_ms, 3),
            "native_coinop_p50_ms_tpu": round(nc_tpu.latency_p50_ms, 3),
            "native_coinop_p95_ms_steal": round(nc_steal.latency_p95_ms, 3),
            "native_coinop_p95_ms_tpu": round(nc_tpu.latency_p95_ms, 3),
        })
    except _NATIVE_ERRS as e:
        native_rows.setdefault("native_coinop_error", repr(e))

    def nq_one(mode):
        r = nq.run(
            n=N, num_app_ranks=APPS, nservers=SERVERS,
            max_depth_for_puts=CUTOFF, cfg=cfg(mode), timeout=600.0,
        )
        assert r.solutions == nq.KNOWN_SOLUTIONS[N], (
            f"{mode}: wrong answer {r.solutions}"
        )
        return r

    nq_runs = interleaved(nq_one, reps=5)
    steal = median_by(nq_runs["steal"], key=lambda r: r.tasks_per_sec)
    tpu = median_by(nq_runs["tpu"], key=lambda r: r.tasks_per_sec)

    # tsp: the other BASELINE.json-named workload (branch-and-bound with
    # broadcast bound updates; compute-bound like nq at this scale).
    # n_cities=10 so the run is long enough (~3.5 s) that the 0.2 s
    # exhaustion-termination quantum stays noise (<5%); pooled per-rep
    # medians like sudoku/gfmc — B&B node counts are nondeterministic
    # run to run in both modes.
    from adlb_tpu.workloads import tsp

    TSP_N = 10
    tsp_want = tsp.brute_force_optimum(
        tsp.dist_matrix(tsp.make_cities(TSP_N, seed=3))
    )

    def tsp_one(mode):
        r = tsp.run(n_cities=TSP_N, num_app_ranks=APPS, nservers=SERVERS,
                    seed=3, cfg=cfg(mode), timeout=600.0)
        assert r.best == tsp_want, f"tsp {mode}: {r.best} != {tsp_want}"
        return (r.tasks_processed, r.elapsed)

    def pooled(rows):
        """Median of per-rep RATES. Each rep's tasks/elapsed already
        normalizes B&B search-luck node-count swings (both modes); the
        median then drops the one-stuck-rep failure mode that a
        total-tasks/total-time pool has, where a single run caught in a
        host slow phase dominates the denominator (observed: a 5-rep
        sudoku pool swinging 0.83-0.97 on the same code)."""
        return median_by([t / s for t, s in rows])

    # 7 reps (round 4, up from 5): B&B search-luck rates swing ±30% per
    # rep in both modes and recorded draws put the 5-rep pooled median
    # anywhere in 0.86-1.07
    tsp_runs = interleaved(tsp_one, reps=7)
    tsp_steal = pooled(tsp_runs["steal"])
    tsp_tpu = pooled(tsp_runs["tpu"])

    # sudoku + gfmc (the self-checking GFMC mini-app economy, reference
    # examples/c4.c): the remaining reference-named workloads, mode vs mode
    from adlb_tpu.workloads import gfmc, sudoku

    # 17-clue grid: enough search that the run is not over in one burst.
    # First-solution search luck swings node counts per run, so the rate
    # is the median of per-rep rates (see pooled()), not best-of.
    SUDOKU_HARD = (
        "000000010400000000020000000000050407008000300001090000"
        "300400200050100000000806000"
    )

    def sudoku_one(mode):
        r = sudoku.run(puzzle=SUDOKU_HARD, num_app_ranks=APPS,
                       nservers=SERVERS, cfg=cfg(mode), timeout=600.0,
                       n_puzzles=8)
        assert r.valid, f"sudoku {mode}: invalid solution"
        return (r.tasks_processed, r.elapsed)

    # first-solution search luck swings node counts per run, so the rate
    # is the median of per-rep rates (see pooled()); 7 reps (round 4,
    # up from 5): recorded draws swing +-40% per rep in BOTH modes
    # (round-4 dress: steal 4860-8323/s within one run's reps), and a
    # 5-rep median leaves the pooled ratio a two-bad-draw lottery
    sudoku_runs = interleaved(sudoku_one, reps=7)
    sudoku_steal = pooled(sudoku_runs["steal"])
    sudoku_tpu = pooled(sudoku_runs["tpu"])

    def gfmc_one(mode):
        r = gfmc.run(num_a=400, bs_per_a=8, cs_per_b=5,
                     num_app_ranks=APPS, nservers=SERVERS,
                     cfg=cfg(mode), timeout=600.0)
        assert r.ok, f"gfmc {mode}: wrong counts {r.counts}"
        return (r.tasks_processed, r.elapsed)

    # 9 reps (round 4, up from 7): gfmc's pooled ratio swung 0.87-1.00
    # across 5-rep draws on this host's hour-scale slow phases, and a
    # round-4 rehearsal drew 0.934 when one slow phase crushed two
    # adjacent reps in both modes; the wider pool tightens the median
    gfmc_runs = interleaved(gfmc_one, reps=9)
    gfmc_steal = pooled(gfmc_runs["steal"])
    gfmc_tpu = pooled(gfmc_runs["tpu"])

    # hotspot: all work enters one server, consumers everywhere — the
    # balancing scenario ADLB exists for; makespan-based, GIL-free work.
    # 16 ranks / 8 servers: enough ring hops that upstream's gossip
    # staleness shows, while staying under the one-interpreter message cap
    HOT_APPS, HOT_SERVERS, HOT_N = 16, 8, 1200

    def hot_one(mode, fused=True):
        r = hotspot.run(
            n_tasks=HOT_N, work_time=0.004, num_app_ranks=HOT_APPS,
            nservers=HOT_SERVERS, cfg=cfg(mode), timeout=300.0, fused=fused,
        )
        assert r.tasks == HOT_N, f"hotspot {mode}: lost work ({r.tasks})"
        return r

    # the headline row: 7 reps — its median sets vs_baseline, and single
    # draws swing ±5% with the host's hour-scale phases. Consumers use
    # the fused get_work call (one round trip when the unit is local):
    # both modes issue the identical call, so the mode that pre-positions
    # work locally is paid for the locality it created.
    hot_runs = interleaved(hot_one, modes=("steal", "steal_fast", "tpu"),
                           reps=7)
    hot_steal = median_by(hot_runs["steal"], key=lambda r: r.tasks_per_sec)
    hot_fast = median_by(hot_runs["steal_fast"],
                         key=lambda r: r.tasks_per_sec)
    hot_tpu = median_by(hot_runs["tpu"], key=lambda r: r.tasks_per_sec)
    steal_idle_med = median_by([r.idle_pct for r in hot_runs["steal"]])
    tpu_idle_med = median_by([r.idle_pct for r in hot_runs["tpu"]])

    # continuity row: the two-call Reserve+Get consumer loop benchmarked in
    # rounds 1-2 (the reference's only consumer shape), so the fused-loop
    # switch above stays auditable against earlier BENCH_r* files.
    # 7 reps (round 4): ~1 draw in 3 hits a host slow phase and collapses
    # the tpu side 20-25% (a round-4 rehearsal drew two adjacent
    # collapsed reps); the median must survive two bad draws
    hcl_runs = interleaved(lambda m: hot_one(m, fused=False), reps=7)
    hcl_steal = median_by(hcl_runs["steal"], key=lambda r: r.tasks_per_sec)
    hcl_tpu = median_by(hcl_runs["tpu"], key=lambda r: r.tasks_per_sec)
    hcl_steal_idle = median_by([r.idle_pct for r in hcl_runs["steal"]])
    hcl_tpu_idle = median_by([r.idle_pct for r in hcl_runs["tpu"]])

    # trickle: steady arrival at one server, consumers elsewhere — isolates
    # dispatch (discovery) latency, the structural gap between gossip-driven
    # stealing and the event-driven global solve
    def tric_one(mode):
        return trickle.run(
            n_tasks=200, interval=0.01, group=2, work_time=0.002,
            num_app_ranks=8, nservers=4, cfg=cfg(mode), timeout=300.0,
        )

    # plan age = staleness of the snapshot state each enacted plan was
    # computed from; collected over the tpu trickle reps (steal worlds run
    # no engine rounds, so interleaving leaves the samples pure)
    from adlb_tpu.balancer.engine import drain_plan_ages

    drain_plan_ages()
    tric_runs = interleaved(tric_one, modes=("steal", "steal_fast", "tpu"))
    ages = sorted(drain_plan_ages())
    tric_steal = median_by(tric_runs["steal"],
                           key=lambda r: r.dispatch_p50_ms)
    tric_fast = median_by(tric_runs["steal_fast"],
                          key=lambda r: r.dispatch_p50_ms)
    tric_tpu = median_by(tric_runs["tpu"], key=lambda r: r.dispatch_p50_ms)

    # pipelined consumer (get_work_stream depth=4) vs the blocking
    # two-call loop above, both balancer modes, paired interleaved reps:
    # the data-plane PR's dispatch-latency claim (remote fused fetch
    # removes the GET_RESERVED leg; the stream removes the re-park gap)
    # measured as a first-class metric rather than folklore. The steal
    # side runs in BROADCAST mode (steal_fast — the framework's own
    # steal path, where the empty->nonempty event qmstat lands): under
    # the upstream-faithful 0.1 s ring, dispatch is gossip-cadence-bound
    # and no consumer shape can move it — that row stays the ring
    # baseline above.
    def tric_pipe_one(mode):
        return trickle.run(
            n_tasks=200, interval=0.01, group=2, work_time=0.002,
            num_app_ranks=8, nservers=4, cfg=cfg(mode), timeout=300.0,
            consumer="stream", stream_depth=4,
        )

    tric_pipe_runs = interleaved(tric_pipe_one, modes=("steal_fast", "tpu"))
    tric_pipe_steal = median_by(tric_pipe_runs["steal_fast"],
                                key=lambda r: r.dispatch_p50_ms)
    tric_pipe_tpu = median_by(tric_pipe_runs["tpu"],
                              key=lambda r: r.dispatch_p50_ms)

    # device solve IN THE LOOP: every balancer round's solve forced
    # through the accelerator (solver_host_threshold=0), so the
    # snapshot->device-solve->plan->enactment pipeline runs end-to-end in
    # the production shape. Reported beside the adaptive host path
    # above: the host/device placement threshold is a latency decision,
    # not a correctness one, and ROADMAP A2 settles it on the chip.
    from adlb_tpu.runtime.world import Config as _Cfg

    dev_err = None
    try:
        from adlb_tpu.balancer.solve import AssignmentSolver as _AS

        warm_dev = _AS(types=(1, 2), max_tasks=256, max_requesters=64,
                       host_threshold_reqs=0)
        warm_dev.solve(
            {0: {"tasks": [(1, 1, 1, 1)], "reqs": [(0, 1, None)]}}, None
        )  # compile at the world's exact shapes
        tric_dev = trickle.run(
            n_tasks=200, interval=0.01, group=2, work_time=0.002,
            num_app_ranks=8, nservers=4,
            cfg=_Cfg(balancer="tpu", exhaust_check_interval=0.2,
                     balancer_max_tasks=256, balancer_max_requesters=64,
                     solver_host_threshold=0),
            timeout=300.0,
        )
        device_rows = {
            "trickle_dispatch_p50_ms_tpu_device_solve": round(
                tric_dev.dispatch_p50_ms, 2),
            "trickle_dispatch_p90_ms_tpu_device_solve": round(
                tric_dev.dispatch_p90_ms, 2),
        }
    except Exception as e:  # noqa: BLE001 — contained as a row (A1)
        dev_err = repr(e)
        device_rows = {"device_solve_error": dev_err}

    def pct(v, p):
        return v[min(int(p * len(v)), len(v) - 1)] if v else 0.0

    plan_age_p50_ms = round(pct(ages, 0.50) * 1e3, 2)
    plan_age_p90_ms = round(pct(ages, 0.90) * 1e3, 2)

    # solve scale: end-to-end snapshot->pairs latency of the batched global
    # solve at pool sizes far beyond the reference's feasible scale (its
    # 0.1s ring gossip + O(n) scans); device path forced
    def solve_scale(S, K, R, reps=3):
        import numpy as np

        from adlb_tpu.balancer.solve import AssignmentSolver

        rng = np.random.default_rng(0)
        solver = AssignmentSolver(
            types=(1, 2, 3, 4), max_tasks=K, max_requesters=R,
            backend="auto", host_threshold_reqs=0,
        )
        snaps = {}
        for s in range(S):
            snaps[100 + s] = {
                "tasks": [
                    (i + 1, int(rng.integers(1, 5)),
                     int(rng.integers(-50, 50)), 64)
                    for i in range(K)
                ],
                "reqs": [
                    (s * R + i, i + 1, [int(rng.integers(1, 5))])
                    for i in range(R)
                ],
            }
        solver.solve(snaps, None)  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            pairs = solver.solve(snaps, None)
            best = min(best, time.perf_counter() - t0)
        assert len(pairs) == S * R
        return round(best * 1e3, 1)

    import jax as _jax

    on_tpu = _jax.default_backend() not in ("cpu",)
    solve_4k_ms = solve_scale(8, 512, 64)
    solve_16k_ms = solve_scale(16, 1024, 128) if on_tpu else None

    # The kernel's ON-CHIP solve time separated from the dispatch
    # round trip. solve_scale above is end-to-end (snapshot packing +
    # dispatch + kernel + result fetch); here the device arrays are
    # pre-staged, the warmed jitted call is timed around
    # block_until_ready, and the measured null-dispatch round trip (a
    # trivial jitted op on the same device) is subtracted — what remains
    # is kernel execution plus result transfer.
    def null_rtt(reps=5):
        """Dispatch round trip of a trivial jitted op: the fixed cost to
        subtract from every on-chip measurement."""
        import jax.numpy as jnp

        nf = _jax.jit(lambda x: x + 1)
        x = _jax.device_put(jnp.zeros((8,), jnp.int32))
        nf(x).block_until_ready()  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            nf(x).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    def solve_onchip(S, K, R, null_s, reps=5):
        import numpy as np

        import jax.numpy as jnp
        from adlb_tpu.balancer.solve import AssignmentSolver

        rng = np.random.default_rng(0)
        T = 4
        solver = AssignmentSolver(
            types=tuple(range(1, T + 1)), max_tasks=K, max_requesters=R,
            backend="auto", host_threshold_reqs=0,
        )
        fn = solver._device_assign()
        task_prio = rng.integers(-50, 50, size=(S * K,)).astype(np.int32)
        task_type = rng.integers(0, T, size=(S * K,)).astype(np.int32)
        req_mask = np.zeros((S * R, T), dtype=bool)
        req_mask[np.arange(S * R), rng.integers(0, T, S * R)] = True
        req_valid = np.ones((S * R,), dtype=bool)
        args = [
            _jax.device_put(jnp.asarray(a))
            for a in (task_prio, task_type, req_mask, req_valid)
        ]
        fn(*args).block_until_ready()  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(*args).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return round(max(best - null_s, 0.0) * 1e3, 1)

    def solve_chained(nt, nr, k1=10, k2=50, reps=3):
        """Per-solve time via the two-K difference: two jitted chains of
        10 and 50 data-DEPENDENT kernel calls, (T50-T10)/40. Any fixed
        dispatch cost cancels exactly, which the single-dispatch
        null-subtraction above cannot guarantee when the round trip
        varies between samples. The dependency (out[0] & 1 perturbs priorities) stops
        XLA hoisting the loop-invariant solve (out[0] * 0 folds away and
        runs ONE kernel for any K). The K spread must put the signal,
        (k2-k1) x per-solve, well above the round trip's jitter
        — the 4k x 512 shape (~0.3 ms/solve) needs a few hundred
        extra solves or the difference drowns (a first draw at 10/50
        measured -0.55 ms)."""
        import numpy as np

        import jax.numpy as jnp
        from adlb_tpu.balancer.pallas_solve import pallas_greedy_assign

        rng = np.random.default_rng(0)
        prio = jnp.asarray(rng.integers(0, 100, nt), jnp.int32)
        ttype = jnp.asarray(rng.integers(0, 8, nt), jnp.int32)
        mask = jnp.asarray(rng.random((nr, 8)) < 0.5)
        valid = jnp.ones((nr,), bool)

        def chain(K):
            @_jax.jit
            def chained(p):
                def step(p, _):
                    out = pallas_greedy_assign(p, ttype, mask, valid)
                    return p + (out[0] & 1).astype(p.dtype), out[0]
                _c, outs = _jax.lax.scan(step, p, None, length=K)
                return outs

            int(chained(prio).sum())  # compile + full sync
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                int(chained(prio).sum())
                best = min(best, time.perf_counter() - t0)
            return best

        return round((chain(k2) - chain(k1)) / (k2 - k1) * 1e3, 2)

    onchip_4k = onchip_65k = null_rtt_ms = None
    chain_4k = chain_65k = None
    if on_tpu:
        try:
            null_s = null_rtt()
            null_rtt_ms = round(null_s * 1e3, 1)
            onchip_4k = solve_onchip(8, 512, 64, null_s)
            onchip_65k = solve_onchip(16, 4096, 512, null_s, reps=3)
        except Exception as e:  # noqa: BLE001 — contained as a row (A1)
            device_rows.setdefault("device_solve_error", repr(e))
        # separate containment: a failure here must not discard the
        # legacy rows measured above
        try:
            chain_4k = solve_chained(4096, 512, k1=10, k2=410)
            chain_65k = solve_chained(65536, 8192)
        except Exception as e:  # noqa: BLE001
            device_rows.setdefault("device_chain_error", repr(e))

    # pop latency (coinop): paired interleaved reps + medians since round
    # 7 — the ~1 ms/pop ceiling this PR attacks needs a draw-robust
    # estimate, not the single run rounds 1-6 recorded
    def coin_one(mode):
        return coinop.run(
            n_tokens=400, num_app_ranks=APPS, nservers=SERVERS,
            cfg=cfg(mode), timeout=300.0,
        )

    coin_runs = interleaved(coin_one)
    lat_steal = median_by(coin_runs["steal"],
                          key=lambda r: r.latency_p50_ms)
    lat_tpu = median_by(coin_runs["tpu"], key=lambda r: r.latency_p50_ms)

    # server-failover recovery cost (on_server_failure="failover"): an
    # 8-rank TCP world (6 apps + 2 servers, real processes) with the
    # NON-master server SIGKILLed mid-workload — records the buddy's
    # detection->promotion MTTR plus the units lost (counted replication
    # lag) / re-executed accounting, so the policy's recovery cost lands
    # in BENCH_*.json instead of folklore. Own containment: a failed row
    # must not discard the rest of the bench.
    def failover_bench():
        import struct

        from adlb_tpu.runtime.transport_tcp import spawn_world as _sw
        from adlb_tpu.types import ADLB_SUCCESS
        from adlb_tpu.types import InfoKey as _IK

        n_units = 160

        def app(ctx):
            if ctx.rank == 0:
                for i in range(n_units):
                    ctx.put(struct.pack("<q", i), 1)
            got = []
            while True:
                rc, w = ctx.get_work([1])
                if rc != ADLB_SUCCESS:
                    return got
                got.append(struct.unpack("<q", w.payload)[0])
                time.sleep(0.002)

        res = _sw(
            6, 2, [1], app,
            cfg=Config(on_server_failure="failover",
                       exhaust_check_interval=0.2,
                       fault_spec={"seed": 9,
                                   "kill_server_at_frame": {1: 80}}),
            timeout=240.0,
        )
        done = [x for v in res.app_results.values() for x in v]
        lost = sum(s.get(int(_IK.FAILOVER_LOST), 0.0)
                   for s in res.server_stats.values())
        mttr = max(
            (s.get(int(_IK.FAILOVER_MTTR_MS), 0.0)
             for s in res.server_stats.values()),
            default=0.0,
        )
        missing = len(set(range(n_units)) - set(done))
        assert missing <= lost, f"{missing} units vanished, {lost} counted"
        return {
            "failover_mttr_ms": round(mttr, 1),
            "failover_units_total": n_units,
            "failover_units_lost": int(lost),
            "failover_units_reexecuted": len(done) - len(set(done)),
            "failover_server_casualties": res.server_casualties,
        }

    try:
        failover_rows = failover_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        failover_rows = {"failover_error": repr(e)[:200]}

    # MASTER-failover recovery cost: the same TCP world but the MASTER
    # is the one SIGKILLed — the ring buddy is the standing deputy and
    # promotes under a bumped fleet epoch (ISSUE 20). Records the median
    # detection->takeover MTTR over 3 worlds (the kill frame halves per
    # retry until the kill lands inside the run, like the chaos draw),
    # plus what the standing deputy costs when nothing dies: wall-clock
    # of an identical in-proc put-storm world with the brain stream on
    # ("failover") vs off ("abort"), as a ratio. Own containment.
    def master_failover_bench():
        import struct

        from adlb_tpu.api import run_world as _rw
        from adlb_tpu.runtime.transport_tcp import spawn_world as _sw
        from adlb_tpu.types import ADLB_SUCCESS
        from adlb_tpu.types import InfoKey as _IK

        n_units = 160

        def app(ctx):
            if ctx.rank == 0:
                for i in range(n_units):
                    ctx.put(struct.pack("<q", i), 1)
            got = []
            while True:
                rc, w = ctx.get_work([1])
                if rc != ADLB_SUCCESS:
                    return got
                got.append(struct.unpack("<q", w.payload)[0])
                time.sleep(0.002)

        mttrs, lost_total = [], 0
        for rep in range(3):
            frame = 80
            for _attempt in range(3):
                res = _sw(
                    6, 2, [1], app,
                    cfg=Config(on_server_failure="failover",
                               exhaust_check_interval=0.2,
                               failover_client_wait=30.0,
                               fault_spec={"seed": 21 + rep,
                                           "kill_server_at_frame":
                                               {0: frame}}),
                    timeout=240.0,
                )
                assert not res.aborted
                done = [x for v in res.app_results.values() for x in v]
                lost = sum(s.get(int(_IK.FAILOVER_LOST), 0.0)
                           for s in res.server_stats.values())
                missing = len(set(range(n_units)) - set(done))
                assert missing <= lost, \
                    f"{missing} units vanished, {lost} counted"
                if res.server_casualties:
                    break
                frame = max(10, frame // 2)
            assert res.server_casualties, "master outlived every retry"
            lost_total += int(lost)
            mttrs.append(max(
                (s.get(int(_IK.FAILOVER_MTTR_MS), 0.0)
                 for s in res.server_stats.values()),
                default=0.0,
            ))

        def storm_s(policy):
            def sapp(ctx):
                if ctx.rank == 0:
                    for i in range(400):
                        ctx.put(struct.pack("<q", i), 1)
                n = 0
                while True:
                    rc, _w = ctx.get_work([1])
                    if rc != ADLB_SUCCESS:
                        return n
                    n += 1

            t0 = time.monotonic()
            _rw(4, 2, [1], sapp,
                cfg=Config(on_server_failure=policy,
                           exhaust_check_interval=0.2),
                timeout=120.0)
            return time.monotonic() - t0

        on = median_by([storm_s("failover") for _ in range(3)])
        off = median_by([storm_s("abort") for _ in range(3)])
        return {
            "master_failover_mttr_ms": round(median_by(mttrs), 1),
            "master_failover_mttr_reps_ms": [round(m, 1) for m in mttrs],
            "master_failover_units_lost": lost_total,
            "brain_repl_on_s": round(on, 3),
            "brain_repl_off_s": round(off, 3),
            "brain_repl_overhead_ratio":
                round(on / off, 3) if off > 0 else 0.0,
        }

    try:
        master_failover_rows = master_failover_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        master_failover_rows = {"master_failover_error": repr(e)[:200]}

    # gray-failure recovery cost (lease_timeout_s armed): a worker
    # SIGSTOPped mid-trickle while holding an unfetched reservation —
    # hang_mttr_ms is stall-to-redelivery (expiry detection + re-enqueue
    # + rematch, measured across processes on the shared CLOCK_MONOTONIC)
    # — and a put storm against a tiny hard-watermarked memory cap,
    # recording that backoff sheds the overload instead of aborting the
    # producer. Own containment, like the failover row.
    def gray_bench():
        import struct

        from adlb_tpu.runtime.faults import sigstop_self
        from adlb_tpu.runtime.transport_tcp import spawn_world as _sw
        from adlb_tpu.types import ADLB_SUCCESS as _OK

        T_W, T_V, T_ANS, T_STALL, T_GO = 1, 2, 3, 4, 5
        lease_s = 0.5

        def hang_app(ctx):
            # rank 1 is the ONLY requester of T_V until it confirms (via
            # the T_GO token) that it HOLDS the marked unit's lease —
            # then it stamps the clock and freezes. Expiry re-enqueues
            # the unit; rank 2 (unblocked by T_GO) stamps its
            # redelivery. Rank 0 waits for BOTH stamps before
            # terminating, so the world can never tear down under the
            # still-stopped victim.
            if ctx.rank == 0:
                assert ctx.put(b"marked", T_V) == _OK
                for i in range(20):  # the trickle around the stall
                    assert ctx.put(struct.pack("<q", i), T_W) == _OK
                stamps = {}
                while len(stamps) < 2:
                    rc, r = ctx.reserve([T_ANS, T_STALL])
                    assert rc == _OK, rc
                    rc, buf = ctx.get_reserved(r.handle)
                    if rc != _OK:
                        continue
                    stamps[r.work_type] = struct.unpack("<d", buf)[0]
                ctx.set_problem_done()
                return (stamps[T_ANS] - stamps[T_STALL]) * 1e3
            if ctx.rank == 1:
                rc, r = ctx.reserve([T_V])
                assert rc == _OK, rc
                assert ctx.put(b"go", T_GO) == _OK
                t_stall = time.monotonic()
                # past worst-case expiry latency (~1.25x lease + scan
                # jitter) but under the 2x hang bar: a declared-dead
                # rank would be excluded from the exhaustion vote and
                # the world could terminate before this stamp lands
                sigstop_self(1.6 * lease_s)
                ctx.get_reserved(r.handle)  # fenced/void: rc != OK
                ctx.put(struct.pack("<d", t_stall), T_STALL,
                        target_rank=0)
                return "stalled"
            rc, r = ctx.reserve([T_GO])  # rank 1 holds the T_V lease now
            assert rc == _OK, rc
            ctx.get_reserved(r.handle)
            got = 0
            while True:
                rc, r = ctx.reserve([T_W, T_V])
                if rc != _OK:
                    return got
                rc, buf = ctx.get_reserved(r.handle)
                if rc != _OK:
                    continue
                if buf == b"marked":  # the redelivered stalled unit
                    ctx.put(struct.pack("<d", time.monotonic()), T_ANS,
                            target_rank=0)
                got += 1
                time.sleep(0.01)

        res = _sw(
            3, 2, [T_W, T_V, T_ANS, T_STALL, T_GO], hang_app,
            cfg=Config(on_worker_failure="reclaim",
                       lease_timeout_s=lease_s,
                       exhaust_check_interval=0.2),
            timeout=120.0,
        )
        mttr_ms = res.app_results[0]
        rows = {"hang_mttr_ms": round(mttr_ms, 1),
                "hang_lease_timeout_ms": lease_s * 1e3}

        def storm_app(ctx):
            n = 80
            if ctx.rank == 0:
                for i in range(n):
                    rc = ctx.put(struct.pack("<q", i) + b"\0" * 56, T_W)
                    assert rc == _OK, rc
                return {"put_backoffs":
                        ctx._c.metrics.value("put_backoffs"),
                        "put_retries": ctx._c.metrics.value("put_retries")}
            got = 0
            while True:
                rc, w = ctx.get_work([T_W])
                if rc != _OK:
                    return got
                got += 1
                time.sleep(0.005)

        res = _sw(
            2, 2, [T_W], storm_app,
            cfg=Config(max_malloc_per_server=512, mem_soft_frac=0.85,
                       mem_hard_frac=0.9, put_max_retries=200,
                       exhaust_check_interval=0.2),
            timeout=120.0,
        )
        rows.update(
            put_storm_units=80,
            put_storm_consumed=res.app_results[1],
            put_storm_backoffs=int(res.app_results[0]["put_backoffs"]),
            put_storm_retries=int(res.app_results[0]["put_retries"]),
        )
        return rows

    try:
        gray_rows = gray_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        gray_rows = {"gray_error": repr(e)[:200]}

    # durable-service recovery cost (Config(wal_dir)): cold restart of a
    # server from its write-ahead log — construction-to-recovered-pool
    # time over a synthetic log of WAL_UNITS 64 B puts, the shard-load +
    # replay path a restarted fleet pays per server. Own containment,
    # like the failover row.
    def service_bench():
        import shutil
        import struct as _struct
        import tempfile

        from adlb_tpu.runtime import wal as _walmod
        from adlb_tpu.runtime.queues import WorkUnit as _WU
        from adlb_tpu.runtime.server import Server as _Server
        from adlb_tpu.runtime.transport import InProcFabric as _Fab
        from adlb_tpu.runtime.world import WorldSpec as _WS

        WAL_UNITS = 2000
        wal_dir = tempfile.mkdtemp(prefix="adlb-bench-wal-")
        try:
            world = _WS(nranks=4, nservers=2, types=(1,))
            w = _walmod.WriteAheadLog(wal_dir, 2, world, fsync_ms=0.0)
            for i in range(WAL_UNITS):
                w.log_put(
                    _WU(seqno=i + 1, work_type=1, prio=0, target_rank=-1,
                        answer_rank=-1,
                        payload=_struct.pack("<q", i) + b"\0" * 56),
                    src=0, put_id=i,
                )
            # a realistic tail: half the pool consumed before the crash
            for i in range(WAL_UNITS // 2):
                w.log_pin(i + 1, 0)
                w.log_consume(i + 1)
            w.tick(time.monotonic(), force=True)
            w.close()
            cfg2 = Config(wal_dir=wal_dir, exhaust_check_interval=0.2)
            # warm the module graph: Server's first construction pulls
            # the balancer (and jax) imports, which would otherwise be
            # billed to the replay measurement
            _Server(_WS(nranks=4, nservers=2, types=(1,)),
                    Config(exhaust_check_interval=0.2), _Fab(4).endpoint(2))
            fabric = _Fab(4)
            t0 = time.monotonic()
            srv = _Server(world, cfg2, fabric.endpoint(2))
            replay_ms = (time.monotonic() - t0) * 1e3
            assert srv.wal_recovered == WAL_UNITS - WAL_UNITS // 2, \
                srv.wal_recovered
            srv.wal.close()
            return {
                "restart_replay_ms": round(replay_ms, 1),
                "restart_replay_units": srv.wal_recovered,
                "restart_replay_log_entries": WAL_UNITS * 2,
            }
        finally:
            shutil.rmtree(wal_dir, ignore_errors=True)

    try:
        service_rows = service_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        service_rows = {"service_error": repr(e)[:200]}

    # shm ring fabric + spill tier (round 7, ROADMAP item 4): pop
    # latency over REAL PROCESSES on the ring fabric vs the identical
    # world on TCP (paired interleaved reps), the >1 MiB payload put
    # row, and the spill tier's fault-in latency + the put-storm
    # acceptance (0 backoffs over a hard-watermarked cap when spill_dir
    # is set, every payload byte-identical). Own containment. NOTE for
    # cross-round reads: on this single-core dev box every cross-process
    # hop pays a scheduler wakeup, so absolute latencies here are
    # scheduling-bound — the fabric's syscall/copy savings show in the
    # batched-consumer row and the large-payload row, and fully on
    # multi-core hosts (the in-proc coinop rows above remain the
    # single-host thread-fabric continuity metric).
    def shm_bench():
        import hashlib
        import shutil
        import struct as _struct
        import tempfile

        from adlb_tpu.runtime.transport_shm import shm_available
        from adlb_tpu.runtime.transport_tcp import spawn_world as _sw
        from adlb_tpu.types import ADLB_SUCCESS as _OK

        if not shm_available():
            return {"shm_note": "no usable /dev/shm; shm rows skipped"}

        def coin_spawn(fabric, consumer="classic"):
            return coinop.run(
                n_tokens=400, num_app_ranks=4, nservers=2,
                cfg=Config(fabric=fabric, exhaust_check_interval=0.25),
                timeout=180.0, spawn=True, consumer=consumer,
            )

        runs = interleaved(lambda f: coin_spawn(f), modes=("shm", "tcp"))
        shm_med = median_by(runs["shm"], key=lambda r: r.latency_p50_ms)
        tcp_med = median_by(runs["tcp"], key=lambda r: r.latency_p50_ms)
        rows = {
            "coinop_shm_p50_ms": round(shm_med.latency_p50_ms, 3),
            "coinop_spawn_tcp_p50_ms": round(tcp_med.latency_p50_ms, 3),
            "coinop_shm_p95_ms": round(shm_med.latency_p95_ms, 3),
            "coinop_spawn_tcp_p95_ms": round(tcp_med.latency_p95_ms, 3),
            "coinop_shm_p50_reps": [
                round(r.latency_p50_ms, 3) for r in runs["shm"]],
            "coinop_spawn_tcp_p50_reps": [
                round(r.latency_p50_ms, 3) for r in runs["tcp"]],
        }
        # the framework's own best consumer path on the ring fabric:
        # batched fused fetch amortizes the scheduler round trip
        bat = [coin_spawn("shm", consumer="batch:8") for _ in range(3)]
        bmed = median_by(bat, key=lambda r: r.latency_p50_ms)
        rows["coinop_shm_batch8_p50_ms"] = round(bmed.latency_p50_ms, 3)

        # >1 MiB payload put latency (acked round trip), shm vs tcp —
        # the scatter-gather encode + ring streaming vs loopback TCP
        PAY = 2 << 20
        N_BIG = 24

        def big_app(ctx):
            if ctx.rank == 0:
                lats = []
                blob = b"P" * PAY
                for _i in range(N_BIG):
                    t0 = time.monotonic()
                    assert ctx.put(blob, 1) == _OK
                    lats.append(time.monotonic() - t0)
                return lats
            n = 0
            while True:
                rc, w = ctx.get_work([1])
                if rc != _OK:
                    return n
                assert len(w.payload) == PAY
                n += 1

        def big_one(fabric):
            res = _sw(2, 1, [1], big_app,
                      cfg=Config(fabric=fabric,
                                 exhaust_check_interval=0.25),
                      timeout=180.0)
            lats = sorted(res.app_results[0])
            assert sum(v for k, v in res.app_results.items()
                       if k != 0) == N_BIG
            return lats[len(lats) // 2] * 1e3

        big = interleaved(lambda f: big_one(f), modes=("shm", "tcp"))
        rows["put_large_p50_ms_shm"] = round(median_by(big["shm"]), 2)
        rows["put_large_p50_ms_tcp"] = round(median_by(big["tcp"]), 2)
        rows["put_large_payload_mib"] = PAY >> 20

        # spill tier: store-level fault-in latency for 1 MiB payloads
        from adlb_tpu.runtime.spill import SpillStore

        sdir = tempfile.mkdtemp(prefix="adlb-bench-spill-")
        try:
            store = SpillStore(sdir, 0)
            blob = os.urandom(1 << 20)
            for i in range(32):
                store.put(i, blob)
            lats = []
            for i in range(32):
                t0 = time.monotonic()
                got = store.take(i)
                lats.append(time.monotonic() - t0)
                assert got == blob
            store.close()
            lats.sort()
            rows["spill_faultin_ms"] = round(lats[len(lats) // 2] * 1e3, 3)

            # acceptance storm: ~240 KiB of puts through a 64 KiB
            # hard-watermarked cap WITH spill_dir — must complete with
            # zero ADLB_BACKOFF and byte-identical fetch-back
            N_STORM, SPAY = 60, 4096

            def storm_app(ctx):
                if ctx.rank == 0:
                    sent = {}
                    for i in range(N_STORM):
                        p = _struct.pack("<q", i) + hashlib.sha256(
                            str(i).encode()).digest() * (SPAY // 32)
                        assert ctx.put(p, 1) == _OK
                        sent[i] = hashlib.sha256(p).hexdigest()
                    return {"sent": sent,
                            "backoffs":
                            ctx._c.metrics.value("put_backoffs"),
                            "retries":
                            ctx._c.metrics.value("put_retries")}
                got = {}
                while True:
                    rc, w = ctx.get_work([1])
                    if rc != _OK:
                        return got
                    i = _struct.unpack("<q", w.payload[:8])[0]
                    got[i] = hashlib.sha256(w.payload).hexdigest()
                    time.sleep(0.002)

            res = _sw(3, 2, [1], storm_app,
                      cfg=Config(max_malloc_per_server=64 << 10,
                                 mem_soft_frac=0.7, mem_hard_frac=0.8,
                                 spill_dir=sdir,
                                 exhaust_check_interval=0.25),
                      timeout=180.0)
            prod = res.app_results[0]
            got = {}
            for r, v in res.app_results.items():
                if r != 0:
                    got.update(v)
            rows.update(
                spill_storm_units=N_STORM,
                spill_storm_consumed=len(got),
                spill_storm_backoffs=int(prod["backoffs"]),
                spill_storm_retries=int(prod["retries"]),
                spill_storm_byte_identical=all(
                    got.get(i) == h for i, h in prod["sent"].items()
                ),
            )
        finally:
            shutil.rmtree(sdir, ignore_errors=True)
        return rows

    try:
        shm_rows = shm_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        shm_rows = {"shm_error": repr(e)[:200]}

    # wire-codec microbench (round 8, ROADMAP item 5c): encode+decode
    # per-frame cost of the compiled C codec vs the pure-Python twin on
    # the wire-native frame mix (put/reserve/fused-response/state-delta
    # — the Put/Reserve/Get_reserved hot path's actual traffic shape).
    # codec_encode_us is the bench_guard-guarded row; the speedup rows
    # carry the >=5x acceptance claim. Own containment.
    def codec_bench():
        from adlb_tpu.runtime import codec as codec_mod
        from adlb_tpu.runtime.messages import Tag, msg

        mix = [
            msg(Tag.FA_PUT, 3, payload=b"\xa5" * 1024, work_type=2,
                prio=-7, target_rank=-1, answer_rank=0, common_len=0,
                common_server=-1, common_seqno=-1, put_id=12),
            msg(Tag.TA_PUT_RESP, 5, rc=1, hint=-1, put_id=12),
            msg(Tag.FA_RESERVE, 0, req_types=[1, 2, 9], hang=True,
                rqseqno=42, prefetch=1),
            msg(Tag.TA_RESERVE_RESP, 6, rc=1, work_type=1, prio=3,
                handle=[7, 5, 0, -1, -1], work_len=4096, answer_rank=-1,
                fetch=1, payloads=[b"u" * 4096] * 8,
                work_types=[1] * 8, prios=[0] * 8,
                answer_ranks=[-1] * 8,
                times_on_q=[0.25] * 8),
            msg(Tag.TA_GET_RESERVED_RESP, 6, rc=1, payload=b"w" * 4096,
                time_on_q=0.125),
            msg(Tag.SS_STATE_DELTA, 4, seqnos=list(range(32)),
                work_types=[1] * 32, prios=[0] * 32,
                work_lens=[64] * 32, nbytes=2048),
            msg(Tag.FA_PUT, 1, payload=b"j" * 64, work_type=1, job_id=7),
            msg(Tag.FA_LOCAL_APP_DONE, 1),
        ]
        bodies = [b"".join(bytes(p) for p in
                           codec_mod.encode_binary_iov_py(m)) for m in mix]
        reps = 4000  # x8 frames = 32k encodes per implementation

        def us_per_frame(fn, args):
            best = float("inf")
            for _rep in range(3):
                t0 = time.perf_counter()
                for a in args:
                    for _ in range(reps // 4):
                        fn(a)
                best = min(
                    best,
                    (time.perf_counter() - t0) / (len(args) * (reps // 4)),
                )
            return best * 1e6

        have_c = codec_mod._load_c_codec()
        rows = {"codec_impl": codec_mod.active_codec(),
                "codec_frames_in_mix": len(mix)}
        enc_py = us_per_frame(codec_mod.encode_binary_iov_py, mix)
        dec_py = us_per_frame(codec_mod.decode_binary_py, bodies)
        rows["codec_encode_us_py"] = round(enc_py, 2)
        rows["codec_decode_us_py"] = round(dec_py, 2)
        if have_c:
            enc_c = us_per_frame(codec_mod._c_encode_iov, mix)
            dec_c = us_per_frame(codec_mod._c_decode, bodies)
            rows["codec_encode_us_c"] = round(enc_c, 2)
            rows["codec_decode_us_c"] = round(dec_c, 2)
            rows["codec_encode_speedup"] = round(enc_py / enc_c, 2)
            rows["codec_decode_speedup"] = round(dec_py / dec_c, 2)
        # the GUARDED row is the ACTIVE implementation's cost — what
        # this record's real frames actually paid — so a record that
        # silently fell back to py regresses against a compiled
        # baseline, which is exactly what the guard exists to catch
        active_c = codec_mod.active_codec() == "c" and have_c
        rows["codec_encode_us"] = rows["codec_encode_us_c"] if active_c \
            else round(enc_py, 2)
        rows["codec_decode_us"] = rows["codec_decode_us_c"] if active_c \
            else round(dec_py, 2)
        if not have_c:
            rows["codec_note"] = "compiled codec unavailable; rows are py"
        return rows

    try:
        codec_rows = codec_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        codec_rows = {"codec_error": repr(e)[:200]}

    # multiplexed channel plane (round 8, ROADMAP item 5b): pop latency
    # over REAL PROCESSES with every python<->python frame riding the
    # host broker (tcp_mux="on") vs the identical per-pair world, paired
    # interleaved reps — on a 1-core box both are scheduler-bound (the
    # provenance stamp records that); plus the 8-burst submission row:
    # wall time for an 8-frame burst delivered through one coalesced
    # gather vs eight sequential sends, endpoint-level (no scheduler in
    # the loop). Own containment.
    def mux_bench():
        from adlb_tpu.runtime.channel import ChannelBroker
        from adlb_tpu.runtime.messages import Tag as _Tag
        from adlb_tpu.runtime.messages import msg as _msg

        def coin_mux(mode):
            return coinop.run(
                n_tokens=400, num_app_ranks=4, nservers=2,
                cfg=Config(fabric="tcp", tcp_mux=mode,
                           exhaust_check_interval=0.25),
                timeout=180.0, spawn=True,
            )

        runs = interleaved(lambda m: coin_mux(m), modes=("on", "off"))
        mux_med = median_by(runs["on"], key=lambda r: r.latency_p50_ms)
        tcp_med = median_by(runs["off"], key=lambda r: r.latency_p50_ms)
        rows = {
            "coinop_mux_p50_ms": round(mux_med.latency_p50_ms, 3),
            "coinop_mux_tcp_p50_ms": round(tcp_med.latency_p50_ms, 3),
            "coinop_mux_p50_reps": [
                round(r.latency_p50_ms, 3) for r in runs["on"]],
            "coinop_mux_tcp_p50_reps": [
                round(r.latency_p50_ms, 3) for r in runs["off"]],
        }

        # 8-burst submission: one coalesced gather vs 8 sequential sends
        from adlb_tpu.runtime.transport_tcp import TcpEndpoint as _EP

        broker = ChannelBroker()
        a = _EP(0, {0: ("127.0.0.1", 0)}, mux=broker.addr)
        b = _EP(1, {1: ("127.0.0.1", 0)}, mux=broker.addr)
        try:
            frame = _msg(_Tag.FA_PUT, 0, payload=b"b" * 256, work_type=1)

            def burst(batched):
                t0 = time.perf_counter()
                if batched:
                    a.submit_begin()
                for _i in range(8):
                    a.send(1, frame)
                if batched:
                    a.submit_flush()
                got = 0
                while got < 8:
                    if b.recv(timeout=5.0) is not None:
                        got += 1
                return (time.perf_counter() - t0) * 1e3

            for _warm in range(20):
                burst(True)
                burst(False)
            bat = sorted(burst(True) for _ in range(60))
            seq = sorted(burst(False) for _ in range(60))
            rows["mux_burst8_batched_ms"] = round(bat[len(bat) // 2], 3)
            rows["mux_burst8_sequential_ms"] = round(seq[len(seq) // 2], 3)
        finally:
            a.close()
            b.close()
            broker.close()
        return rows

    try:
        mux_rows = mux_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        mux_rows = {"mux_error": repr(e)[:200]}

    # multichip planning-round latency at scale: the sharded balancer's
    # full round (snapshot-delta ingest -> sharded solve -> plan
    # extraction) at 1,000 servers / 100k parked and 10,000 servers /
    # 1M parked on an 8-way host-simulated mesh. Measures the HOST
    # auction tier: on a host-SIMULATED mesh the on-device tier's round
    # is dominated by the 8-way virtual-device dispatch/rendezvous cost
    # (~90 ms/call regardless of scale — see MULTICHIP_r08), which
    # would drown any real regression AND break continuity with the
    # r06-r10 plan_round_1k_ms records; the device tier's correctness
    # is pair-list-fuzzed in CI (tests/test_device_auction.py) and its
    # host-sim latency recorded per MULTICHIP round. Runs in a
    # subprocess, on whatever devices JAX shows it there (plan_bench no
    # longer provisions a CPU mesh; A1 owns what this row becomes on a
    # machine where this parent holds the chip). Own containment.
    def plan_round_bench():
        import subprocess as _sp

        proc = _sp.run(
            [sys.executable, "-m", "adlb_tpu.balancer.plan_bench",
             "--quick", "--auction", "host", "--json-only"],
            capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"plan_bench rc={proc.returncode}: {proc.stderr[-200:]}")
        doc = json.loads(proc.stdout.strip().splitlines()[-1])
        by_servers = {r["servers"]: r for r in doc["rows"]}
        big = by_servers.get(1000, doc["rows"][-1])
        out = {
            "plan_round_1k_ms": big["plan_round_p50_ms"],
            "plan_round_1k_p90_ms": big["plan_round_p90_ms"],
            "plan_round_1k_servers": big["servers"],
            "plan_round_1k_parked": big["parked_reqs"],
            "plan_round_sweep_ms": big["device_sweep_ms"],
        }
        huge = by_servers.get(10000)
        if huge is not None:
            out["plan_round_10k_ms"] = huge["plan_round_p50_ms"]
            out["plan_round_10k_p90_ms"] = huge["plan_round_p90_ms"]
            out["plan_round_10k_parked"] = huge["parked_reqs"]
        return out

    try:
        plan_rows = plan_round_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        plan_rows = {"plan_round_error": repr(e)[:200]}

    # host-tier round admission: engine.round() overhead at 1k/10k/100k
    # parked requesters (array-resident ledger vs the pure-Python twin;
    # null solver, so this is purely the admission the host ledger
    # vectorizes). Subprocess-isolated like the plan sweep; needs no
    # devices. Own containment.
    def engine_round_bench():
        import subprocess as _sp

        proc = _sp.run(
            [sys.executable, "-m", "adlb_tpu.balancer.plan_bench",
             "--engine-rounds", "--json-only"],
            capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"engine_rounds rc={proc.returncode}: {proc.stderr[-200:]}")
        doc = json.loads(proc.stdout.strip().splitlines()[-1])
        out = {}
        for row in doc["rows"]:
            parked = row["parked_reqs"]
            label = f"{parked // 1000}k"
            out[f"engine_round_us_{label}"] = row["engine_round_us"]
            out[f"engine_round_py_us_{label}"] = row["engine_round_py_us"]
        big = doc["rows"][-1]
        out["engine_round_us"] = big["engine_round_us"]
        out["engine_round_speedup"] = big["speedup"]
        out["ledger_patches"] = big["ledger_patches"]
        out["ledger_resyncs"] = big["ledger_resyncs"]
        # guarded compact key (ms): the 1k-parked admission p50 whose
        # 2.4x floor the stamp-keyed SnapshotStore sync removed
        if "admission_1k_ms" in doc:
            out["admission_1k_ms"] = doc["admission_1k_ms"]
        return out

    try:
        engine_rows = engine_round_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        engine_rows = {"engine_round_error": repr(e)[:200]}

    # unit-lifecycle tracing overhead (round 9, the SLO sensor layer):
    # coinop pop p50 at trace_sample=1.0 (every put journeyed — the
    # worst case), at the DEFAULT sample rate, and at 0.0 (off), paired
    # interleaved reps. trace_overhead_ratio is the DEFAULT-rate/off
    # per-pair median — the ISSUE 13 acceptance bar bench_guard bounds
    # absolutely at 1.05; the full-sampling rows are baseline-relative
    # regression rows. Own containment.
    def trace_overhead_bench():
        default_rate = Config().trace_sample

        def coin_trace(rate):
            return coinop.run(
                n_tokens=400, num_app_ranks=APPS, nservers=SERVERS,
                cfg=Config(balancer="steal", exhaust_check_interval=0.2,
                           trace_sample=rate),
                timeout=300.0,
            )

        rates = {"full": 1.0, "default": default_rate, "off": 0.0}
        runs = interleaved(
            lambda m: coin_trace(rates[m]), modes=tuple(rates),
        )

        def med(mode):
            return median_by(
                runs[mode], key=lambda r: r.latency_p50_ms
            ).latency_p50_ms

        def pair_med(mode):
            pairs = sorted(
                a.latency_p50_ms / b.latency_p50_ms
                for a, b in zip(runs[mode], runs["off"])
                if b.latency_p50_ms
            )
            return round(pairs[len(pairs) // 2], 3) if pairs else 0.0

        return {
            "coinop_trace_p50_ms": round(med("full"), 3),
            "coinop_trace_default_p50_ms": round(med("default"), 3),
            "coinop_notrace_p50_ms": round(med("off"), 3),
            # per-pair medians (phase-cancelling, like the bar metrics)
            "trace_overhead_ratio": pair_med("default"),
            "trace_overhead_full_ratio": pair_med("full"),
            "trace_sample_default": default_rate,
            "coinop_trace_p50_reps": [
                round(r.latency_p50_ms, 3) for r in runs["full"]],
            "coinop_notrace_p50_reps": [
                round(r.latency_p50_ms, 3) for r in runs["off"]],
        }

    try:
        trace_rows = trace_overhead_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        trace_rows = {"trace_overhead_error": repr(e)[:200]}

    # tail-aware tracing + continuous profiler overhead (round 10):
    # trace_tail forced on (every unit journeys server-side, retention
    # decided at close; trace_sample pinned 0 so the arm is the pure
    # tail cost), the 19 Hz profiler, and both off. The acceptance
    # ratios are RUN-CPU pair ratios (process_time over a 2000-token
    # world, on/off runs ADJACENT with order alternating per rep so
    # linear box drift cancels inside each pair): on the 1-core dev box
    # pop-p50 pair noise is +-15% (scheduler-bound, the r08 caveat made
    # policy in bench_guard's cpu-count skip), while added CPU is the
    # scheduler-immune measure of what the feature actually costs — and
    # is what surfaces as latency on any saturated core. p50 medians
    # ride along for the latency view. Own containment.
    def tail_profile_overhead_bench():
        def coin_mode(mode):
            kw = {"trace_tail": "off", "profile_hz": 0.0}
            if mode == "tail":
                kw["trace_tail"] = "on"
            elif mode == "prof":
                kw["profile_hz"] = 19.0
            c0 = time.process_time()
            r = coinop.run(
                n_tokens=2000, num_app_ranks=APPS, nservers=SERVERS,
                cfg=Config(balancer="steal", exhaust_check_interval=0.2,
                           trace_sample=0.0, **kw),
                timeout=300.0,
            )
            return r, time.process_time() - c0

        coin_mode("off")  # warm (imports, thread pools)
        p50s = {"tail": [], "prof": [], "off": []}
        cpus = {"tail": [], "prof": [], "off": []}
        ratios = {"tail": [], "prof": []}
        # 9 pairs per arm: single-pair noise on this host class is +-8%
        # (hypervisor phases), so the median needs depth — see the
        # bench-box-noise note; ~90 s total, cheap for what it buys
        for rep in range(9):
            for armed in ("tail", "prof"):
                order = (armed, "off") if rep % 2 == 0 else ("off", armed)
                pair = {}
                for m in order:
                    r, c = coin_mode(m)
                    pair[m] = c
                    p50s[m].append(r.latency_p50_ms)
                    cpus[m].append(c)
                ratios[armed].append(pair[armed] / pair["off"])

        def med(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        return {
            "coinop_tail_p50_ms": round(med(p50s["tail"]), 3),
            "coinop_prof_p50_ms": round(med(p50s["prof"]), 3),
            "coinop_tailprof_off_p50_ms": round(med(p50s["off"]), 3),
            "coinop_tail_cpu_s": round(med(cpus["tail"]), 4),
            "coinop_prof_cpu_s": round(med(cpus["prof"]), 4),
            "coinop_tailprof_off_cpu_s": round(med(cpus["off"]), 4),
            # per-adjacent-pair medians: the acceptance bars
            "trace_tail_overhead_ratio": round(med(ratios["tail"]), 3),
            "profile_overhead_ratio": round(med(ratios["prof"]), 3),
            "tailprof_overhead_metric": "run-cpu-adjacent-pair",
            "tail_overhead_ratio_reps": [
                round(x, 3) for x in ratios["tail"]],
            "profile_overhead_ratio_reps": [
                round(x, 3) for x in ratios["prof"]],
        }

    try:
        tail_rows = tail_profile_overhead_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        tail_rows = {"tail_profile_overhead_error": repr(e)[:200]}

    # SLO evaluator overhead (round 12, ISSUE 16): the master-side
    # burn-rate loop armed with 8 objectives (one per work type, tight
    # windows so every obs tick appends to the snapshot ring and walks
    # the full objective list) vs the identical observed world with no
    # objectives. Both arms carry ops_port=0 + obs gossip so the ratio
    # isolates the evaluator itself, not the plumbing it rides on.
    # Same RUN-CPU adjacent-pair method as the tail/profiler rows
    # (process_time around a 2000-token world, order alternating per
    # rep, median of per-pair ratios) — the bench-box-noise policy.
    # Own containment.
    def slo_overhead_bench():
        objectives = tuple(
            {"job": 0, "type": t, "p99_ms": 50.0, "error_frac": 0.01,
             "window_s": 6.0, "severity": "warn"}
            for t in range(8)
        )

        def coin_mode(mode):
            kw = {}
            if mode == "slo":
                kw["slo"] = objectives
                kw["slo_eval_interval"] = 0.1
            c0 = time.process_time()
            r = coinop.run(
                n_tokens=2000, num_app_ranks=APPS, nservers=SERVERS,
                cfg=Config(balancer="steal", exhaust_check_interval=0.2,
                           trace_sample=0.0, ops_port=0,
                           obs_sync_interval=0.2, **kw),
                timeout=300.0,
            )
            return r, time.process_time() - c0

        coin_mode("off")  # warm (imports, thread pools)
        p50s = {"slo": [], "off": []}
        cpus = {"slo": [], "off": []}
        ratios = []
        for rep in range(9):
            order = ("slo", "off") if rep % 2 == 0 else ("off", "slo")
            pair = {}
            for m in order:
                r, c = coin_mode(m)
                pair[m] = c
                p50s[m].append(r.latency_p50_ms)
                cpus[m].append(c)
            ratios.append(pair["slo"] / pair["off"])

        def med(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        return {
            "coinop_slo_p50_ms": round(med(p50s["slo"]), 3),
            "coinop_slo_off_p50_ms": round(med(p50s["off"]), 3),
            "coinop_slo_cpu_s": round(med(cpus["slo"]), 4),
            "coinop_slo_off_cpu_s": round(med(cpus["off"]), 4),
            "slo_overhead_ratio": round(med(ratios), 3),
            "slo_overhead_metric": "run-cpu-adjacent-pair",
            "slo_objectives_armed": len(objectives),
            "slo_overhead_ratio_reps": [round(x, 3) for x in ratios],
        }

    try:
        slo_rows = slo_overhead_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        slo_rows = {"slo_overhead_error": repr(e)[:200]}

    # elastic membership (round 11, ISSUE 15): attach latency — the
    # rank-allocation + fleet-wide fan-out/ack barrier a joining rank
    # pays before its first protocol frame can land anywhere — and
    # scale-out MTTR (scale request -> new shard spawned, bootstrapped
    # by the donor rebalance, and counted ready by the master; the
    # master's own scaleout_mttr_ms gauge, so the row measures the
    # protocol, not the harness). Absolute one-shot latencies, so no
    # on/off CPU pairing applies — per the bench-box noise policy the
    # estimator is the median over reps (3 worlds x 3 attaches, one
    # scale-out each; single draws on the 1-core box are not
    # certifiable) and the rows are guarded baseline-relative
    # (bench_guard "member" row, missing-row = fail). Own containment.
    def membership_bench():
        import struct as _struct
        import threading as _th

        from adlb_tpu.runtime.membership import ElasticWorld
        from adlb_tpu.types import ADLB_SUCCESS as _OK

        def med(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        attach_reps, detach_reps, mttr_reps, wall_reps = [], [], [], []
        for _ in range(3):
            ew = ElasticWorld(
                2, 2, [1],
                cfg=Config(exhaust_check_interval=0.2), timeout=120.0,
            )
            hold = _th.Event()

            def consume(ctx):
                n = 0
                while True:
                    rc, _w = ctx.get_work([1])
                    if rc != _OK:
                        return n
                    n += 1

            def producer(ctx, hold=hold, consume=consume):
                # a standing backlog so the scale-out's donor rebalance
                # ships real units, like a production trigger would
                for i in range(48):
                    assert ctx.put(
                        _struct.pack("<q", i) + b"\0" * 56, 1
                    ) == _OK
                hold.wait(90)
                return consume(ctx)

            def holder(ctx, hold=hold, consume=consume):
                hold.wait(90)
                return consume(ctx)

            ew.run_app(0, producer)
            ew.run_app(1, holder)
            for _ in range(3):
                t0 = time.perf_counter()
                jw = ew.attach_ctx()
                attach_reps.append((time.perf_counter() - t0) * 1e3)
                t0 = time.perf_counter()
                assert jw.ctx.detach_world() == _OK
                detach_reps.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            ew.scale_out()
            wall_reps.append((time.perf_counter() - t0) * 1e3)
            mttr = ew.master.metrics.value("scaleout_mttr_ms")
            mttr_reps.append(mttr if mttr > 0 else wall_reps[-1])
            hold.set()
            res = ew.finish(timeout=120)
            got = sum(v for v in res.values() if isinstance(v, int))
            assert got == 48, f"membership bench lost work ({got}/48)"
        return {
            "attach_ms": round(med(attach_reps), 2),
            "detach_ms": round(med(detach_reps), 2),
            "scaleout_mttr_ms": round(med(mttr_reps), 1),
            "scaleout_wall_ms": round(med(wall_reps), 1),
            "attach_ms_reps": [round(x, 2) for x in attach_reps],
            "scaleout_mttr_ms_reps": [round(x, 1) for x in mttr_reps],
        }

    try:
        member_rows = membership_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        member_rows = {"membership_error": repr(e)[:200]}

    # tail hedging (round 12, ISSUE 17): two rows. hedge_p999 — the
    # straggler-rescue arm: a worker freezes while holding an unfetched
    # reservation strictly UNDER the lease timeout, so only the hedge
    # plane (budgeted speculative sibling, fenced first-wins) can close
    # the unit early; the row is the answer-economy completion time
    # with hedging on vs off over the same stall, medians over
    # interleaved reps. hedge_storm — the budget-subordination arm: a
    # put-storm shape driven handler-by-handler against one hedging
    # server with a forced memory-pressure window mid-storm, recording
    # launches vs the token-bucket bound (frac x deliveries + burst)
    # and the count of sticky-vetoed origins that later launched — both
    # structural zeros by construction, guarded absolutely. Own
    # containment.
    def hedge_bench():
        import struct as _struct

        from adlb_tpu.runtime.membership import ElasticWorld
        from adlb_tpu.types import ADLB_SUCCESS as _OK

        def med(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        T, T_ANS = 1, 3
        n_units = 8
        stall_s = 1.2

        def one_world(hedge_on):
            cfg = Config(
                exhaust_check_interval=0.2, on_worker_failure="reclaim",
                lease_timeout_s=4.0,
                hedge_budget_frac=0.5 if hedge_on else 0.0,
                hedge_min_age_ms=80.0,
            )
            ew = ElasticWorld(3, 1, [T, T_ANS], cfg=cfg)
            if hedge_on:
                for s in ew.servers.values():
                    # what the master's obs gossip would install
                    s.journeys.tail_thr = {(0, T): 0.25}

            def collector(ctx):
                for i in range(n_units):
                    assert ctx.put(_struct.pack("<q", i), T,
                                   answer_rank=0) == _OK
                t0 = time.perf_counter()
                seen = set()
                while len(seen) < n_units:
                    rc, r = ctx.reserve([T_ANS])
                    assert rc == _OK, rc
                    rc, buf = ctx.get_reserved(r.handle)
                    if rc != _OK:
                        continue
                    seen.add(_struct.unpack("<q", buf)[0])
                return (time.perf_counter() - t0) * 1e3

            def worker(sleepy):
                def app(ctx):
                    n, slept = 0, False
                    while True:
                        rc, r = ctx.reserve([T])
                        if rc != _OK:
                            return n
                        if sleepy and not slept:
                            slept = True
                            time.sleep(stall_s)  # reserved, unfetched
                        rc, buf = ctx.get_reserved(r.handle)
                        if rc != _OK:
                            continue  # fenced: the sibling won
                        ctx.put(buf, T_ANS, target_rank=0)
                        n += 1
                return app

            ew.run_app(0, collector)
            ew.run_app(1, worker(True))
            ew.run_app(2, worker(False))
            res = ew.finish(timeout=60)
            done = res[1] + res[2]
            assert done == n_units, f"hedge bench lost work ({done})"
            return res[0]

        on_ms, off_ms = [], []
        for rep in range(3):
            order = (True, False) if rep % 2 == 0 else (False, True)
            for m in order:
                (on_ms if m else off_ms).append(one_world(m))

        # -- hedge_storm: budget subordination under a put storm -------
        from adlb_tpu.runtime.hedge import BURST_TOKENS
        from adlb_tpu.runtime.messages import Tag as _Tag
        from adlb_tpu.runtime.messages import msg as _msg
        from adlb_tpu.runtime.server import Server as _Server
        from adlb_tpu.runtime.transport import InProcFabric as _Fab
        from adlb_tpu.runtime.world import WorldSpec as _WS

        frac, rounds = 0.25, 40
        world = _WS(nranks=4, nservers=2, types=(T,))
        fab = _Fab(4)
        srv = _Server(
            world,
            Config(on_worker_failure="reclaim", lease_timeout_s=0.5,
                   hedge_budget_frac=frac, hedge_min_age_ms=50.0,
                   max_malloc_per_server=1024, mem_soft_frac=0.6),
            fab.endpoint(2),
        )
        srv.journeys.tail_thr[(0, T)] = 0.01

        def drain(rank):
            while fab.endpoints[rank].recv(timeout=0.0) is not None:
                pass

        for i in range(rounds):
            srv._handle(_msg(_Tag.FA_PUT, 0, payload=b"u%d" % i,
                             work_type=T, prio=0, target_rank=-1,
                             answer_rank=-1, common_len=0,
                             common_server=-1, common_seqno=-1))
            srv._handle(_msg(_Tag.FA_RESERVE, 0, req_types=[T],
                             hang=True, rqseqno=2 * i + 1))
            drain(0)
            srv._handle(_msg(_Tag.FA_RESERVE, 1, req_types=[T],
                             hang=True, rqseqno=2 * i + 2))
            pressured = 10 <= i < 20  # mid-storm overload window
            if pressured:
                srv.mem.alloc(800)
            srv._scan_hedges(time.monotonic() + 1.0)
            if pressured:
                srv.mem.free(800)
            for ls in list(srv.leases.leases()):
                u = srv.wq.get(ls.seqno)
                if u is None or not u.pinned:
                    continue
                srv._handle(_msg(_Tag.FA_GET_RESERVED, ls.owner,
                                 seqno=ls.seqno))
            drain(0)
            drain(1)
        assert srv.wq.count == 0, "hedge storm left unsettled inventory"
        launched_seqs, vetoed_seqs = set(), set()
        for _, txt in srv.flight.entries():
            if txt.startswith("hedge_launched"):
                launched_seqs.add(txt.split("origin=")[1].split()[0])
            elif txt.startswith("hedge_vetoed") and "backpressure" in txt:
                vetoed_seqs.add(txt.split("seqno=")[1].split()[0])
        launched = int(srv.metrics.value("hedges_launched"))
        bound = frac * rounds + BURST_TOKENS
        return {
            "hedge_p999_on_ms": round(med(on_ms), 1),
            "hedge_p999_off_ms": round(med(off_ms), 1),
            "hedge_p999_rescue_ratio": round(
                med(off_ms) / med(on_ms), 2) if med(on_ms) else 0.0,
            "hedge_p999_on_ms_reps": [round(x, 1) for x in on_ms],
            "hedge_p999_off_ms_reps": [round(x, 1) for x in off_ms],
            "hedge_storm_deliveries": rounds,
            "hedge_storm_launched": launched,
            "hedge_storm_budget_bound": round(bound, 1),
            "hedge_storm_launch_excess": round(
                max(0.0, launched - bound), 1),
            "hedge_storm_vetoed_backpressure": len(vetoed_seqs),
            "hedge_storm_veto_breaches": len(
                launched_seqs & vetoed_seqs),
        }

    try:
        hedge_rows = hedge_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        hedge_rows = {"hedge_error": repr(e)[:200]}

    # multi-job fairness (round 13, ISSUE 19): a light tenant (8 units,
    # 4:1 fair-share weight) rides the PLANNED path while a heavy
    # tenant floods 40 units against a squeezed snapshot horizon
    # (balancer_max_tasks=16) — the weight bias decides whether the
    # light job's units make the horizon and win solve slots while the
    # flood drains, or wait behind it. The row is the light job's p99
    # put->deliver sojourn with weights on vs off (same worlds,
    # interleaved reps); < 1 means weighting shielded the tenant.
    # Guarded baseline-relative (bench_guard "fairness" row, r08
    # skip-with-note policy until a baseline carries it). Own
    # containment.
    def fairness_bench():
        import struct as _struct

        from adlb_tpu.runtime.membership import ElasticWorld
        from adlb_tpu.types import ADLB_SUCCESS as _OK

        def med(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        T = 1
        n_heavy, n_light = 40, 8

        def one_world(weighted):
            cfg = Config(
                balancer="tpu", balancer_max_jobs=3,
                job_weights={2: 4.0} if weighted else None,
                balancer_max_tasks=16, put_routing="home",
                exhaust_check_interval=0.2,
            )
            ew = ElasticWorld(3, 2, [T], cfg=cfg, timeout=90.0)

            def producer(ctx):
                rc, ja = ctx.submit_job("heavy")
                assert (rc, ja) == (_OK, 1)
                rc, jb = ctx.submit_job("light")
                assert (rc, jb) == (_OK, 2)
                ctx.attach(1)
                for _ in range(n_heavy):
                    assert ctx.put(
                        _struct.pack("<d", time.perf_counter())
                        + b"\0" * 48, T) == _OK
                ctx.attach(2)
                for _ in range(n_light):
                    assert ctx.put(
                        _struct.pack("<d", time.perf_counter())
                        + b"\0" * 48, T) == _OK
                ctx.drain_job(1)
                ctx.drain_job(2)
                return []

            def consumer(jid):
                def app(ctx):
                    time.sleep(0.2)
                    ctx.attach(jid)
                    sojourns = []
                    while True:
                        rc, w = ctx.get_work([T])
                        if rc != _OK:
                            return sojourns
                        sojourns.append(
                            (time.perf_counter()
                             - _struct.unpack("<d", w.payload[:8])[0])
                            * 1e3)
                        time.sleep(0.005)  # per-unit work: a standing
                        # backlog, so horizon ordering matters
                return app

            # home placement (world.home_server: rank % nservers):
            # producer rank 0 and the HEAVY consumer rank 2 share
            # server 0, so the flood drains by local matching; the
            # LIGHT consumer rank 1 parks on server 1, so every light
            # unit must cross through the planner — the path the
            # weight bias arbitrates
            ew.run_app(0, producer)
            ew.run_app(1, consumer(2))
            ew.run_app(2, consumer(1))
            res = ew.finish(timeout=90)
            assert len(res[2]) == n_heavy and len(res[1]) == n_light
            light = sorted(res[1])
            return light[min(len(light) - 1,
                             int(0.99 * len(light)))]

        on_ms, off_ms = [], []
        for rep in range(3):
            order = (True, False) if rep % 2 == 0 else (False, True)
            for m in order:
                (on_ms if m else off_ms).append(one_world(m))
        return {
            "fairness_weighted_p99_ms": round(med(on_ms), 1),
            "fairness_unweighted_p99_ms": round(med(off_ms), 1),
            "fairness_p99_ratio": round(
                med(on_ms) / med(off_ms), 3) if med(off_ms) else 0.0,
            "fairness_weighted_p99_ms_reps": [
                round(x, 1) for x in on_ms],
            "fairness_unweighted_p99_ms_reps": [
                round(x, 1) for x in off_ms],
        }

    try:
        fairness_rows = fairness_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        fairness_rows = {"fairness_error": repr(e)[:200]}

    # fleet controller (round 13, ISSUE 19): autoscale reaction — a
    # put burst drives one server past the scale-out pressure band and
    # the clock runs from the last put acked to the controller-spawned
    # shard LIVE in the membership table (decision latency + the §12
    # scale-out machine, end to end through the closed loop). Median
    # over reps; guarded baseline-relative (bench_guard "control" row,
    # r08 skip-with-note policy). Own containment.
    def control_bench():
        import struct as _struct
        import threading as _th

        from adlb_tpu.runtime.membership import ElasticWorld
        from adlb_tpu.types import ADLB_SUCCESS as _OK

        def med(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        T = 1
        reps = []
        for _ in range(3):
            cfg = Config(
                exhaust_check_interval=0.2, ops_port=0,
                obs_sync_interval=0.1, control=True,
                control_cooldown_s=5.0, control_min_servers=2,
                control_scaleout_pressure=0.25,
                control_scalein_pressure=0.05,
                max_malloc_per_server=256 * 1024,
            )
            ew = ElasticWorld(1, 2, [T], cfg=cfg, timeout=90.0)
            pressured = _th.Event()
            grown = _th.Event()

            def app(ctx, pressured=pressured, grown=grown):
                for i in range(20):
                    assert ctx.put(
                        _struct.pack("<q", i) + b"\0" * 8192, T) == _OK
                ctx._c.flush_puts()
                pressured.set()
                grown.wait(60)
                n = 0
                while True:
                    rc, _w = ctx.get_work([T])
                    if rc != _OK:
                        return n
                    n += 1

            ew.run_app(0, app)
            assert pressured.wait(60)
            t0 = time.perf_counter()
            deadline = t0 + 60.0
            while len(ew.servers) <= 2 and time.perf_counter() < deadline:
                time.sleep(0.005)
            assert len(ew.servers) > 2, "controller never scaled out"
            reps.append((time.perf_counter() - t0) * 1e3)
            grown.set()
            res = ew.finish(timeout=90)
            assert res[0] == 20, f"autoscale bench lost work ({res[0]})"
            acts = ew.master.metrics.value(
                "control_actions", kind="scale_out")
            assert acts >= 1, "scale-out was not controller-driven"
        return {
            "autoscale_react_ms": round(med(reps), 1),
            "autoscale_react_ms_reps": [round(x, 1) for x in reps],
        }

    try:
        control_rows = control_bench()
    except Exception as e:  # noqa: BLE001 — own containment
        control_rows = {"control_error": repr(e)[:200]}

    # measurement provenance (the r07 caveat made policy): every record
    # carries the core count + load so cross-round comparisons can tell
    # a real regression from a different (or busy) box — bench_guard
    # skips-with-note when baseline and candidate disagree on cores
    provenance = {
        "cpu_count": os.cpu_count() or 1,
        "loadavg_1m": round(os.getloadavg()[0], 2)
        if hasattr(os, "getloadavg") else None,
    }

    result = {
        "metric": "hotspot_tasks_per_sec_tpu_balancer",
        "value": round(hot_tpu.tasks_per_sec, 1),
        "unit": "tasks/s",
        "vs_baseline": round(hot_tpu.tasks_per_sec / hot_steal.tasks_per_sec, 3)
        if hot_steal.tasks_per_sec
        else 0.0,
        "detail": {
            **provenance,
            "platform": platform,
            "app_ranks": APPS,
            "servers": SERVERS,
            "baseline": "upstream-faithful stealing (qmstat ring @ 0.1s, "
                        "src/adlb.c:165)",
            "hotspot_steal_tasks_per_sec": round(hot_steal.tasks_per_sec, 1),
            "hotspot_steal_fast_tasks_per_sec": round(
                hot_fast.tasks_per_sec, 1),
            "hotspot_tpu_tasks_per_sec": round(hot_tpu.tasks_per_sec, 1),
            # idle medians taken over the rep distribution directly, not
            # read off the median-RATE run (whose idle draw can be an
            # outlier of its own)
            "hotspot_steal_idle_pct": round(steal_idle_med, 1),
            "hotspot_tpu_idle_pct": round(tpu_idle_med, 1),
            "idle_ratio_vs_upstream": round(
                tpu_idle_med / steal_idle_med, 3) if steal_idle_med else 0.0,
            # best single rep per mode, for the spread floor (medians above
            # are the primary, draw-robust numbers)
            "hotspot_tpu_idle_pct_best": round(
                min(r.idle_pct for r in hot_runs["tpu"]), 1),
            "hotspot_steal_idle_pct_best": round(
                min(r.idle_pct for r in hot_runs["steal"]), 1),
            # continuity: the rounds-1/2 two-call consumer loop
            "hotspot_classic_steal_tasks_per_sec": round(
                hcl_steal.tasks_per_sec, 1),
            "hotspot_classic_tpu_tasks_per_sec": round(
                hcl_tpu.tasks_per_sec, 1),
            "hotspot_classic_ratio": round(
                hcl_tpu.tasks_per_sec / hcl_steal.tasks_per_sec, 3)
            if hcl_steal.tasks_per_sec else 0.0,
            "hotspot_classic_steal_idle_pct": round(hcl_steal_idle, 1),
            "hotspot_classic_tpu_idle_pct": round(hcl_tpu_idle, 1),
            "hotspot_classic_idle_ratio": round(
                hcl_tpu_idle / hcl_steal_idle, 3) if hcl_steal_idle else 0.0,
            "trickle_dispatch_p50_ms_steal": round(
                tric_steal.dispatch_p50_ms, 2),
            "trickle_dispatch_p50_ms_steal_fast": round(
                tric_fast.dispatch_p50_ms, 2),
            "trickle_dispatch_p50_ms_tpu": round(tric_tpu.dispatch_p50_ms, 2),
            "trickle_dispatch_p90_ms_steal": round(
                tric_steal.dispatch_p90_ms, 2),
            "trickle_dispatch_p90_ms_tpu": round(tric_tpu.dispatch_p90_ms, 2),
            # pipelined consumer (get_work_stream depth=4); steal side =
            # broadcast mode (compare with
            # trickle_dispatch_p50_ms_steal_fast, the blocking consumer
            # in the same config)
            "trickle_pipe_p50_ms_steal_fast": round(
                tric_pipe_steal.dispatch_p50_ms, 2),
            "trickle_pipe_p50_ms_tpu": round(
                tric_pipe_tpu.dispatch_p50_ms, 2),
            "trickle_pipe_p90_ms_steal_fast": round(
                tric_pipe_steal.dispatch_p90_ms, 2),
            "trickle_pipe_p90_ms_tpu": round(
                tric_pipe_tpu.dispatch_p90_ms, 2),
            "plan_age_p50_ms": plan_age_p50_ms,
            "plan_age_p90_ms": plan_age_p90_ms,
            **device_rows,
            "dispatch_speedup_vs_upstream": round(
                tric_steal.dispatch_p50_ms / tric_tpu.dispatch_p50_ms, 2)
            if tric_tpu.dispatch_p50_ms else 0.0,
            "solve_4096x512_ms": solve_4k_ms,
            "solve_16384x2048_ms": solve_16k_ms,
            # on-chip kernel time with the null dispatch subtracted (see
            # solve_onchip); the end-to-end rows above keep it
            "solve_onchip_4096x512_ms": onchip_4k,
            "solve_onchip_65536x8192_ms": onchip_65k,
            "device_null_rtt_ms": null_rtt_ms,
            # two-K chained per-solve times: RTT cancels exactly (the
            # robust on-chip numbers; the rows above keep the legacy
            # single-dispatch method for cross-round continuity)
            "solve_chain_4096x512_ms": chain_4k,
            "solve_chain_65536x8192_ms": chain_65k,
            "hotspot_app_ranks": HOT_APPS,
            "hotspot_servers": HOT_SERVERS,
            "nq_n": N,
            "nq_steal_tasks_per_sec": round(steal.tasks_per_sec, 1),
            "nq_tpu_tasks_per_sec": round(tpu.tasks_per_sec, 1),
            "nq_ratio": round(tpu.tasks_per_sec / steal.tasks_per_sec, 3)
            if steal.tasks_per_sec else 0.0,
            "tsp_n_cities": TSP_N,
            "tsp_steal_tasks_per_sec": round(tsp_steal, 1),
            "tsp_tpu_tasks_per_sec": round(tsp_tpu, 1),
            "tsp_ratio": round(tsp_tpu / tsp_steal, 3) if tsp_steal else 0.0,
            "sudoku_steal_tasks_per_sec": round(sudoku_steal, 1),
            "sudoku_tpu_tasks_per_sec": round(sudoku_tpu, 1),
            "sudoku_ratio": round(sudoku_tpu / sudoku_steal, 3)
            if sudoku_steal else 0.0,
            "gfmc_steal_tasks_per_sec": round(gfmc_steal, 1),
            "gfmc_tpu_tasks_per_sec": round(gfmc_tpu, 1),
            "gfmc_ratio": round(gfmc_tpu / gfmc_steal, 3)
            if gfmc_steal else 0.0,
            **native_rows,
            "steal_pop_latency_p50_ms": round(lat_steal.latency_p50_ms, 3),
            "tpu_pop_latency_p50_ms": round(lat_tpu.latency_p50_ms, 3),
            "steal_pops_per_sec": round(lat_steal.pops_per_sec, 1),
            "tpu_pops_per_sec": round(lat_tpu.pops_per_sec, 1),
            "steal_pop_p50_reps": [
                round(r.latency_p50_ms, 3) for r in coin_runs["steal"]],
            "tpu_pop_p50_reps": [
                round(r.latency_p50_ms, 3) for r in coin_runs["tpu"]],
            **failover_rows,
            **master_failover_rows,
            **gray_rows,
            **service_rows,
            **shm_rows,
            **codec_rows,
            **mux_rows,
            **plan_rows,
            **engine_rows,
            **trace_rows,
            **tail_rows,
            **slo_rows,
            **member_rows,
            **hedge_rows,
            **fairness_rows,
            **control_rows,
        },
    }
    # full record first (audit trail for humans / in-tree rehearsal logs)
    print(json.dumps(result))

    # ... then the COMPACT headline as the FINAL line: the only line the
    # driver's 2000-char tail is guaranteed to keep intact. Headline
    # fields + per-rep spreads; short keys; no whitespace.
    def rr(vals, nd=0):
        return [round(v, nd) if nd else int(round(v)) for v in vals]

    rates = lambda runs: [r.tasks_per_sec for r in runs]  # noqa: E731
    idles = lambda runs: [r.idle_pct for r in runs]  # noqa: E731

    def pair_ratio(runs, rate=lambda r: r.tasks_per_sec):
        """Median of per-rep-PAIR tpu/steal ratios: adjacent interleaved
        reps share the host's hour-scale phase, so the per-pair ratio
        cancels it.  ``rate``
        extracts a rep's rate — result objects by default, or
        (tasks, elapsed) tuples via pair_ratio_t."""
        pairs = [
            rate(t) / rate(s)
            for s, t in zip(runs["steal"], runs["tpu"])
            if rate(s)
        ]
        return round(median_by(pairs), 3) if pairs else 0.0

    def pair_ratio_t(runs):
        return pair_ratio(runs, rate=lambda r: r[0] / r[1])
    compact = {
        "metric": "hotspot_tasks_per_sec_tpu_balancer",
        "value": round(hot_tpu.tasks_per_sec, 1),
        "unit": "tasks/s",
        # BAR METRIC = the PAIRED estimator (round 6):
        # median of per-rep-PAIR tpu/steal ratios. Adjacent
        # interleaved reps share the host's hour-scale phase, so pairing
        # cancels it — five rounds of "rehearsals cleared it, the record
        # drew a slow phase" is the pooled median's phase vulnerability.
        # The pooled medians stay as *_pooled for cross-round continuity.
        "vs_baseline": pair_ratio(hot_runs),
        "detail": {
            "hot_pooled": round(
                hot_tpu.tasks_per_sec / hot_steal.tasks_per_sec, 3)
            if hot_steal.tasks_per_sec else 0.0,
            "idle_steal": round(steal_idle_med, 1),
            "idle_tpu": round(tpu_idle_med, 1),
            "idle_ratio": round(tpu_idle_med / steal_idle_med, 3)
            if steal_idle_med else 0.0,
            "classic_ratio": pair_ratio(hcl_runs),
            "classic_pooled": round(
                hcl_tpu.tasks_per_sec / hcl_steal.tasks_per_sec, 3)
            if hcl_steal.tasks_per_sec else 0.0,
            "classic_idle_ratio": round(hcl_tpu_idle / hcl_steal_idle, 3)
            if hcl_steal_idle else 0.0,
            # workload bars: paired first (the bar), pooled second
            "nq": pair_ratio(nq_runs),
            "nq_pooled": round(tpu.tasks_per_sec / steal.tasks_per_sec, 3)
            if steal.tasks_per_sec else 0.0,
            "tsp": pair_ratio_t(tsp_runs),
            "tsp_pooled": round(tsp_tpu / tsp_steal, 3)
            if tsp_steal else 0.0,
            "sudoku": pair_ratio_t(sudoku_runs),
            "sud_pooled": round(sudoku_tpu / sudoku_steal, 3)
            if sudoku_steal else 0.0,
            "gfmc": pair_ratio_t(gfmc_runs),
            "gfmc_pooled": round(gfmc_tpu / gfmc_steal, 3)
            if gfmc_steal else 0.0,
            # HEADLINE scale rows (round 6): both modes on the batched
            # (batch:8) consumer at 64 and 128 ranks —
            # [ratio, steal_wait%, tpu_wait%]. The framework's own best
            # consumer path carries the scale flag; single-fetch rows
            # below are secondary continuity metrics.
            "n64b": [native_rows.get("native_64r_batch8_ratio"),
                     native_rows.get("native_64r_batch8_steal_wait_pct"),
                     native_rows.get("native_64r_batch8_tpu_wait_pct")],
            "n128b": [native_rows.get("native_128r_batch8_ratio"),
                      native_rows.get("native_128r_batch8_steal_wait_pct"),
                      native_rows.get("native_128r_batch8_tpu_wait_pct")],
            # secondary: single-fetch hotspot rows (host-ceiling-bound,
            # kept for cross-round comparison)
            "n16_ratio": native_rows.get("native_16r_ratio"),
            "n64_ratio": native_rows.get("native_64r_ratio"),
            "n16_wait": [native_rows.get("native_16r_steal_wait_pct"),
                         native_rows.get("native_16r_tpu_wait_pct")],
            "n64_wait": [native_rows.get("native_64r_steal_wait_pct"),
                         native_rows.get("native_64r_tpu_wait_pct")],
            # the NAMED north-star workloads at native scale (secondary,
            # single-fetch): [ratio, steal_wait%, tpu_wait%] per scale
            "nq64": [native_rows.get("native_nq_64r_ratio"),
                     native_rows.get("native_nq_64r_steal_wait_pct"),
                     native_rows.get("native_nq_64r_tpu_wait_pct")],
            "nq128": [native_rows.get("native_nq_128r_ratio"),
                      native_rows.get("native_nq_128r_steal_wait_pct"),
                      native_rows.get("native_nq_128r_tpu_wait_pct")],
            "tsp64": [native_rows.get("native_tsp_64r_ratio"),
                      native_rows.get("native_tsp_64r_steal_wait_pct"),
                      native_rows.get("native_tsp_64r_tpu_wait_pct")],
            "tsp128": [native_rows.get("native_tsp_128r_ratio"),
                       native_rows.get("native_tsp_128r_steal_wait_pct"),
                       native_rows.get("native_tsp_128r_tpu_wait_pct")],
            "batch_fetch_delta_pct": native_rows.get(
                "native_batch_fetch_delta_pct"),
            "disp_p50": [round(tric_steal.dispatch_p50_ms, 2),
                         round(tric_tpu.dispatch_p50_ms, 2)],
            # pipelined (get_work_stream) trickle consumer —
            # [steal_fast, tpu]; compare against the blocking consumer in
            # the SAME configs: [disp_fast_p50, disp_p50[1]]
            "disp_pipe_p50": [round(tric_pipe_steal.dispatch_p50_ms, 2),
                              round(tric_pipe_tpu.dispatch_p50_ms, 2)],
            "disp_fast_p50": round(tric_fast.dispatch_p50_ms, 2),
            # pop service latency (coinop), paired-rep medians
            "failover_mttr_ms": failover_rows.get("failover_mttr_ms"),
            "master_failover_mttr_ms":
                master_failover_rows.get("master_failover_mttr_ms"),
            "brain_repl_overhead_ratio":
                master_failover_rows.get("brain_repl_overhead_ratio"),
            "hang_mttr_ms": gray_rows.get("hang_mttr_ms"),
            "storm_backoffs": gray_rows.get("put_storm_backoffs"),
            "restart_replay_ms": service_rows.get("restart_replay_ms"),
            # multichip planning round @ 1k servers / 100k parked (p50)
            "plan_round_1k_ms": plan_rows.get("plan_round_1k_ms"),
            # host-tier round admission @ 100k parked: [array us, py
            # twin us] + the 1k/10k rungs of the same ladder
            "engine_round": [engine_rows.get("engine_round_us_100k"),
                             engine_rows.get("engine_round_py_us_100k")],
            "engine_round_1k": [engine_rows.get("engine_round_us_1k"),
                                engine_rows.get("engine_round_py_us_1k")],
            "engine_round_10k": [engine_rows.get("engine_round_us_10k"),
                                 engine_rows.get("engine_round_py_us_10k")],
            "pop_p50": [round(lat_steal.latency_p50_ms, 3),
                        round(lat_tpu.latency_p50_ms, 3)],
            "pops": [round(lat_steal.pops_per_sec, 1),
                     round(lat_tpu.pops_per_sec, 1)],
            # shm ring fabric (real processes): [shm, tcp, shm-batch:8]
            # classic-consumer pop p50s; large-payload put [shm, tcp];
            # spill fault-in latency and the storm acceptance counters
            # measurement provenance (the r07 caveat made policy)
            "cpu_count": provenance["cpu_count"],
            "load1": provenance["loadavg_1m"],
            # compiled wire codec: [active-impl encode us, py-twin
            # encode us] + speedups (>=5x acceptance) and the impl tag
            "codec_encode_us": codec_rows.get("codec_encode_us"),
            "codec": [codec_rows.get("codec_encode_us"),
                      codec_rows.get("codec_encode_us_py"),
                      codec_rows.get("codec_decode_us"),
                      codec_rows.get("codec_decode_us_py")],
            "codec_speedup": [codec_rows.get("codec_encode_speedup"),
                              codec_rows.get("codec_decode_speedup")],
            "codec_impl": codec_rows.get("codec_impl"),
            # multiplexed channels: [mux pop p50, per-pair pop p50] and
            # the 8-burst submission [coalesced, sequential]
            "coinop_mux": [mux_rows.get("coinop_mux_p50_ms"),
                           mux_rows.get("coinop_mux_tcp_p50_ms")],
            # unit-lifecycle tracing: [p50 @ trace_sample=1.0, p50 @ 0.0,
            # p50 @ default rate] + the default-rate per-pair overhead
            # ratio bench_guard bounds at 1.05 (ISSUE 13 acceptance)
            "trace_overhead": [
                trace_rows.get("coinop_trace_p50_ms"),
                trace_rows.get("coinop_notrace_p50_ms"),
                trace_rows.get("coinop_trace_default_p50_ms"),
            ],
            "trace_overhead_ratio": trace_rows.get("trace_overhead_ratio"),
            "trace_overhead_full_ratio": trace_rows.get(
                "trace_overhead_full_ratio"),
            # tail promotion + continuous profiler (round 10): paired
            # [tail-on p50, profiler-on p50, both-off p50] and the two
            # per-pair ratios bench_guard bounds absolutely at 1.05
            "tail_profile_overhead": [
                tail_rows.get("coinop_tail_p50_ms"),
                tail_rows.get("coinop_prof_p50_ms"),
                tail_rows.get("coinop_tailprof_off_p50_ms"),
            ],
            "trace_tail_overhead_ratio": tail_rows.get(
                "trace_tail_overhead_ratio"),
            "profile_overhead_ratio": tail_rows.get(
                "profile_overhead_ratio"),
            # SLO evaluator (round 12): armed/off coinop run-CPU
            # adjacent-pair ratio — bench_guard absolute arm at 1.05
            "slo_overhead_ratio": slo_rows.get("slo_overhead_ratio"),
            # elastic membership (round 11): attach latency (allocation
            # + fleet fan-out/ack barrier) and server scale-out MTTR
            # (request -> shard bootstrapped + rebalanced + ready),
            # medians over reps — bench_guard "member" row
            "attach_ms": member_rows.get("attach_ms"),
            "scaleout_mttr_ms": member_rows.get("scaleout_mttr_ms"),
            # tail hedging (round 12): straggler completion with the
            # hedge plane on vs off over the same sub-lease stall, and
            # the put-storm budget-subordination counters — bench_guard
            # "hedge" row + absolute zero-excess/zero-breach arms
            "hedge_p999": [hedge_rows.get("hedge_p999_on_ms"),
                           hedge_rows.get("hedge_p999_off_ms")],
            "hedge_storm_launch_excess": hedge_rows.get(
                "hedge_storm_launch_excess"),
            "hedge_storm_veto_breaches": hedge_rows.get(
                "hedge_storm_veto_breaches"),
            # multi-job fairness + fleet controller (round 13): the
            # light tenant's weighted/unweighted p99 sojourn ratio and
            # the closed-loop scale-out reaction — bench_guard
            # "fairness" / "control" rows (r08 skip-with-note arms)
            "fairness_p99": [fairness_rows.get("fairness_weighted_p99_ms"),
                             fairness_rows.get("fairness_unweighted_p99_ms")],
            "fairness_p99_ratio": fairness_rows.get("fairness_p99_ratio"),
            "autoscale_react_ms": control_rows.get("autoscale_react_ms"),
            "mux_burst8": [mux_rows.get("mux_burst8_batched_ms"),
                           mux_rows.get("mux_burst8_sequential_ms")],
            "coinop_shm": [shm_rows.get("coinop_shm_p50_ms"),
                           shm_rows.get("coinop_spawn_tcp_p50_ms"),
                           shm_rows.get("coinop_shm_batch8_p50_ms")],
            "put_large": [shm_rows.get("put_large_p50_ms_shm"),
                          shm_rows.get("put_large_p50_ms_tcp")],
            "spill": [shm_rows.get("spill_faultin_ms")],
            "storm": [shm_rows.get("spill_storm_backoffs"),
                      shm_rows.get("spill_storm_retries"),
                      1 if shm_rows.get("spill_storm_byte_identical")
                      else 0],
            "ndisp_p50": [native_rows.get("native_trickle_p50_ms_steal"),
                          native_rows.get("native_trickle_p50_ms_tpu")],
            # on-chip solve scale (4096x512 / 16384x2048 pools, device
            # path forced) + trickle with EVERY round's solve on the
            # device — the TPU-path evidence in the record
            "solve_ms": [solve_4k_ms, solve_16k_ms],
            "solve_onchip_ms": [onchip_4k, onchip_65k],
            "null_rtt_ms": null_rtt_ms,
            "disp_dev_p50": device_rows.get(
                "trickle_dispatch_p50_ms_tpu_device_solve"),
            # per-rep spreads: every headline claim auditable from this
            # record alone (steal first, tpu second in each pair)
            "reps": {
                "hot_s": rr(rates(hot_runs["steal"])),
                "hot_t": rr(rates(hot_runs["tpu"])),
                "hotidle_s": rr(idles(hot_runs["steal"]), 1),
                "hotidle_t": rr(idles(hot_runs["tpu"]), 1),
                "cls_s": rr(rates(hcl_runs["steal"])),
                "cls_t": rr(rates(hcl_runs["tpu"])),
                "clsidle_s": rr(idles(hcl_runs["steal"]), 1),
                "clsidle_t": rr(idles(hcl_runs["tpu"]), 1),
                "nq_s": rr(rates(nq_runs["steal"])),
                "nq_t": rr(rates(nq_runs["tpu"])),
                "tsp_s": rr(t / s for t, s in tsp_runs["steal"]),
                "tsp_t": rr(t / s for t, s in tsp_runs["tpu"]),
                "sud_s": rr(t / s for t, s in sudoku_runs["steal"]),
                "sud_t": rr(t / s for t, s in sudoku_runs["tpu"]),
                "gfmc_s": rr(t / s for t, s in gfmc_runs["steal"]),
                "gfmc_t": rr(t / s for t, s in gfmc_runs["tpu"]),
            },
        },
    }
    if "native_error" in native_rows:
        compact["detail"]["native_error"] = native_rows["native_error"][:120]
    if "engine_round_error" in engine_rows:
        compact["detail"]["engine_round_error"] = (
            engine_rows["engine_round_error"][:120]
        )
    if "device_solve_error" in device_rows:
        compact["detail"]["device_error"] = (
            device_rows["device_solve_error"][:120]
        )
    if "fairness_error" in fairness_rows:
        compact["detail"]["fairness_error"] = (
            fairness_rows["fairness_error"][:120]
        )
    if "control_error" in control_rows:
        compact["detail"]["control_error"] = (
            control_rows["control_error"][:120]
        )
    line = json.dumps(compact, separators=(",", ":"))
    if len(line) > 1900:  # belt-and-braces: the tail window is ~2000
        compact["detail"].pop("reps", None)
        line = json.dumps(compact, separators=(",", ":"))
    print(line)


if __name__ == "__main__":
    import faulthandler

    # defense-in-depth for the run of record: every world has its own
    # timeout (a wedge raises TimeoutError -> the bench_error line), but
    # if a world/teardown path ever wedges past those, dump all thread
    # stacks to stderr every 30 min instead of hanging silently. A
    # healthy full bench finishes in well under one period; the timer
    # is cancelled the moment main() returns so a clean run never dumps.
    faulthandler.dump_traceback_later(1800, repeat=True)
    t0 = time.time()
    try:
        main()
        faulthandler.cancel_dump_traceback_later()
    except Exception as e:  # surface failures as a parseable line
        print(json.dumps({
            "metric": "bench_error",
            "value": 0,
            "unit": "error",
            "vs_baseline": 0,
            "detail": {"error": repr(e), "elapsed_s": round(time.time() - t0, 1)},
        }))
        sys.exit(1)
