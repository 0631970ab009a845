#!/usr/bin/env python
"""Bench regression guard: diff a fresh BENCH record against a pinned
baseline and FAIL on latency regressions of the guarded per-op rows.

    python scripts/bench_guard.py NEW.json --baseline OLD.json
                                  [--threshold 0.15]

There is no default baseline: ROADMAP A1(e) re-baselines the guard on
the first chip ledger line.

Guarded rows (latencies — higher is worse):

* ``coinop_p50`` — the all-native plane's pop-latency probe
  (``native_coinop_p50_ms_steal`` / ``_tpu``), the per-op transport
  floor;
* ``pop_p50`` — the Python plane's pop service latency
  (``steal_pop_latency_p50_ms`` / ``tpu_pop_latency_p50_ms``).

A guarded value more than ``threshold`` (default 15%) above the
baseline exits 1, naming the row. A guarded value MISSING from the new
record also fails (a silently dropped metric reads as "no regression"
forever); one missing from the baseline is skipped with a note.

Both file shapes are accepted: the driver's wrapper
(``{"tail": ..., "parsed": {...}}`` — values are regex-scanned out of
the raw tail when the parsed compact record lacks them) and bench.py's
own compact JSON line.
"""

from __future__ import annotations

import argparse
import json
import re
import sys

# guarded row -> list of (label, raw-text key) latency scalars
GUARDS = {
    "coinop_p50": [
        ("steal", "native_coinop_p50_ms_steal"),
        ("tpu", "native_coinop_p50_ms_tpu"),
    ],
    "pop_p50": [
        ("steal", "steal_pop_latency_p50_ms"),
        ("tpu", "tpu_pop_latency_p50_ms"),
    ],
    # the batched global solve's end-to-end latency (snapshot->pairs,
    # device path forced, 4096x512 pool) — the balancer-brain budget
    "solve_ms": [
        ("4096x512", "solve_4096x512_ms"),
    ],
    # the multichip planning round on the 8-way simulated mesh: 1,000
    # servers / 100k parked (r06 metric) and 10,000 servers / 1M parked
    # (first carried by the post-r10 record; older baselines skip it
    # with a note, per the missing-baseline rule). Both cells measure
    # the HOST auction tier — on a host-SIMULATED mesh the on-device
    # tier is dominated by the fixed 8-way virtual-device
    # dispatch/rendezvous cost (~90 ms/call at any scale, see
    # MULTICHIP_r08), which would drown real regressions; the device
    # tier is pair-list-fuzzed in CI and its host-sim latency recorded
    # per MULTICHIP round instead.
    "plan_round": [
        ("1k", "plan_round_1k_ms"),
        ("10k", "plan_round_10k_ms"),
    ],
    # host-tier round admission at 1k parked — the r07 2.4x floor the
    # stamp-keyed SnapshotStore sync removed (first carried by the
    # post-r10 record; older baselines skip with a note).
    # MILLISECONDS, array ledger arm.
    "admission": [
        ("1k", "admission_1k_ms"),
    ],
    # host-tier round admission at 100k parked requesters (r08 metric;
    # older baselines skip with a note): engine.round() p50 in
    # MICROSECONDS on the array-resident ledger. Guarded cell is the
    # array path only — the compact pair's second cell is the py twin,
    # kept for reference (it IS the regression the ledger removed).
    "engine_round": [
        ("100k", "engine_round_us_100k"),
    ],
    # shm ring fabric (r07 metrics; older baselines skip with a note):
    # pop latency over real processes on the ring fabric vs the same
    # world on TCP, classic two-call consumer + the batched path
    "coinop_shm": [
        ("shm", "coinop_shm_p50_ms"),
        ("tcp", "coinop_spawn_tcp_p50_ms"),
        ("shm-batch8", "coinop_shm_batch8_p50_ms"),
    ],
    # >1 MiB payload put latency, shm vs tcp (r07)
    "put_large": [
        ("shm", "put_large_p50_ms_shm"),
        ("tcp", "put_large_p50_ms_tcp"),
    ],
    # spill tier: disk fault-in latency for a 1 MiB payload (r07)
    "spill": [
        ("faultin", "spill_faultin_ms"),
    ],
    # compiled wire codec: per-frame encode cost on the wire-native
    # frame mix (r08 metric; older baselines skip with a note). The
    # guarded cell is the ACTIVE implementation's row — a py-fallback
    # record regresses vs a compiled baseline, which is the point.
    "codec": [
        ("encode", "codec_encode_us"),
    ],
    # multiplexed channel plane: pop p50 over real processes with every
    # frame riding the host broker (r08; older baselines skip)
    "coinop_mux": [
        ("mux", "coinop_mux_p50_ms"),
    ],
    # unit-lifecycle tracing (r09 metrics; older baselines skip with a
    # note): pop p50 with every put head-sampled, vs the same world with
    # tracing off — the SLO sensor layer's hot-path cost rows
    "trace_overhead": [
        ("traced", "coinop_trace_p50_ms"),
        ("off", "coinop_notrace_p50_ms"),
    ],
    # tail-based promotion + continuous profiler (r10 metrics; older
    # baselines skip with a note): pop p50 with trace_tail forced on /
    # the 19 Hz profiler sampling / both off, interleaved pairs
    "tail_profile_overhead": [
        ("tail", "coinop_tail_p50_ms"),
        ("prof", "coinop_prof_p50_ms"),
        ("off", "coinop_tailprof_off_p50_ms"),
    ],
    # elastic membership (r11 metrics; older baselines skip with a
    # note): attach latency — rank allocation + the fleet-wide
    # fan-out/ack barrier — and server scale-out MTTR (scale request ->
    # shard spawned + donor-rebalanced + counted ready by the master).
    # Once a baseline carries them, a record MISSING either row fails
    # (the ISSUE 15 missing-row=fail arm).
    "member": [
        ("attach", "attach_ms"),
        ("scaleout", "scaleout_mttr_ms"),
    ],
    # tail hedging (r12 metric; older baselines skip with a note): the
    # SIGSTOP-straggler arm's completion time with the hedge plane ON —
    # a regression here means the speculative rescue got slower (or
    # stopped firing, in which case the value jumps to the stall
    # length). The off arm rides in the compact pair for reference.
    "hedge": [
        ("rescue", "hedge_p999_on_ms"),
    ],
    # multi-job fairness (r13 metric; older baselines skip with a note,
    # the r08 policy): the light tenant's weighted-arm p99 sojourn
    # under a heavy flood through the planned path — a regression means
    # the fair-share bias stopped shielding the tenant. The unweighted
    # arm rides the compact pair for reference (it IS the number the
    # weights exist to beat), and the ratio is recorded alongside.
    "fairness": [
        ("weighted", "fairness_weighted_p99_ms"),
    ],
    # master failover (r20 metrics; older baselines skip with a note,
    # the r08 policy): the ring-deputy's detection->takeover MTTR with
    # the MASTER SIGKILLed mid-run (median over 3 TCP worlds), and the
    # standing deputy's quiet-time cost — put-storm wall-clock with the
    # brain stream on over the identical world with it off. The ratio
    # cell is unitless; a regression there means the always-on brain
    # replication started taxing the hot path while nothing was dying.
    "master_failover": [
        ("mttr", "master_failover_mttr_ms"),
        ("brain-ratio", "brain_repl_overhead_ratio"),
    ],
    # fleet controller (r13 metric; older baselines skip with a note):
    # closed-loop scale-out reaction — pressure step to the
    # controller-spawned shard live in the membership table. Once a
    # baseline carries it, a record missing the row fails.
    "control": [
        ("autoscale", "autoscale_react_ms"),
    ],
}

# Absolute arms: self-contained bounds checked against the NEW record
# alone (no baseline needed — the bound IS the acceptance bar).
# (key, max allowed value, description)
ABSOLUTE = [
    # the DEFAULT sample rate may cost at most 5% of coinop pop p50
    # (ISSUE 13 acceptance); full sampling is gated baseline-relative
    # via the trace_overhead rows above
    ("trace_overhead_ratio", 1.05,
     "default-sample-rate/untraced coinop pop p50 ratio"),
    # tail mode arms spans on EVERY unit (retention decided at close);
    # the profiler samples at 19 Hz — each may add at most 5% to the
    # 2000-token coinop run's CPU (ISSUE 14 acceptance; run-CPU
    # adjacent pairs because pop-p50 pair noise on the 1-core box is
    # +-15%, scheduler-bound — the same caveat behind the cpu-count
    # skip above; added CPU is what surfaces as latency on any
    # saturated core)
    ("trace_tail_overhead_ratio", 1.05,
     "trace_tail-on/off coinop run-CPU adjacent-pair ratio"),
    ("profile_overhead_ratio", 1.05,
     "profiler-19Hz/off coinop run-CPU adjacent-pair ratio"),
    # ISSUE 16: the master-side burn-rate evaluator (8 objectives,
    # tight windows) may cost at most 5% run-CPU over the identical
    # observed-but-unobjectived world
    ("slo_overhead_ratio", 1.05,
     "slo-eval-armed/off coinop run-CPU adjacent-pair ratio"),
    # ISSUE 17: hedging is budget-bounded and backpressure-subordinate
    # STRUCTURALLY — the storm arm may never launch past the token
    # bucket (frac x deliveries + burst) and a sticky-vetoed origin may
    # never launch a sibling afterwards; both bounds are exact zeros
    ("hedge_storm_launch_excess", 0.0,
     "hedge launches over the token-bucket bound under a put storm"),
    ("hedge_storm_veto_breaches", 0.0,
     "sticky-vetoed origins that later launched a sibling"),
]

_NUM = r"(-?[0-9]+(?:\.[0-9]+)?)"


def _load(path: str) -> tuple[dict, str]:
    """(parsed compact detail or {}, raw searchable text)."""
    with open(path) as f:
        raw = f.read()
    try:
        doc = json.loads(raw)
    except ValueError:
        return {}, raw
    if isinstance(doc, dict) and "parsed" in doc:
        parsed = doc.get("parsed") or {}
        detail = parsed.get("detail", {}) if isinstance(parsed, dict) else {}
        # the decoded tail (json.loads already unescaped it) is the
        # searchable text — the raw file holds it \"-escaped
        return detail, str(doc.get("tail") or "")
    if isinstance(doc, dict):
        return doc.get("detail", doc), raw
    return {}, raw


def _scan(text: str, key: str):
    m = re.search(rf'"{re.escape(key)}":\s*{_NUM}', text)
    return float(m.group(1)) if m else None


def extract(detail: dict, text: str, row: str, idx: int,
            raw_key: str):
    """One guarded scalar: the compact pair list first (row key holds
    [steal, tpu]), then a raw-text scan for the long-form key."""
    pair = detail.get(row)
    if isinstance(pair, (list, tuple)) and len(pair) > idx and \
            isinstance(pair[idx], (int, float)):
        return float(pair[idx])
    v = detail.get(raw_key)
    if isinstance(v, (int, float)):
        return float(v)
    return _scan(text, raw_key)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("new", help="fresh BENCH record (json)")
    ap.add_argument("--baseline", required=True,
                    help="the record to compare against (json)")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="allowed fractional regression (0.15 = 15%%)")
    args = ap.parse_args(argv)

    new_detail, new_text = _load(args.new)
    base_detail, base_text = _load(args.baseline)

    # measurement-provenance gate (the r07 caveat made policy): latency
    # rows measured on different core counts are not comparable — a
    # 1-core box's numbers are scheduler-bound, a 4-core box's are not.
    # Records carry cpu_count since r08; when both sides have it and
    # they disagree, print a skip-note instead of failing the build.
    base_cpus = extract(base_detail, base_text, "", 0, "cpu_count")
    new_cpus = extract(new_detail, new_text, "", 0, "cpu_count")
    if base_cpus and new_cpus and int(base_cpus) != int(new_cpus):
        print(
            f"[bench-guard] SKIP: baseline measured on {int(base_cpus)} "
            f"core(s), candidate on {int(new_cpus)} — latency rows are "
            f"scheduler-bound incomparable across core counts; "
            f"re-measure both on one box to re-arm the guard"
        )
        return 0

    failures = []
    checked = 0
    for row, cells in GUARDS.items():
        for idx, (label, raw_key) in enumerate(cells):
            base = extract(base_detail, base_text, row, idx, raw_key)
            if base is None or base <= 0:
                print(f"[bench-guard] {row}[{label}]: no usable baseline "
                      f"in {args.baseline}; skipped")
                continue
            new = extract(new_detail, new_text, row, idx, raw_key)
            if new is None:
                failures.append(
                    f"{row}[{label}]: MISSING from {args.new} "
                    f"(baseline {base:.3f} ms) — a dropped metric is "
                    f"not a pass"
                )
                continue
            checked += 1
            ratio = new / base
            verdict = "OK"
            if ratio > 1.0 + args.threshold:
                verdict = "REGRESSION"
                failures.append(
                    f"{row}[{label}]: {new:.3f} ms vs baseline "
                    f"{base:.3f} ms ({(ratio - 1) * 100:+.1f}% > "
                    f"{args.threshold * 100:.0f}% allowed)"
                )
            print(f"[bench-guard] {row}[{label}]: new {new:.3f} ms, "
                  f"baseline {base:.3f} ms ({(ratio - 1) * 100:+.1f}%) "
                  f"{verdict}")
    # absolute arms: bound the NEW record directly (the bound is the
    # acceptance bar, so no baseline row is needed); a metric absent
    # from BOTH records is a not-yet-armed row, skipped with a note
    for key, bound, desc in ABSOLUTE:
        new = extract(new_detail, new_text, "", 0, key)
        if new is None:
            if extract(base_detail, base_text, "", 0, key) is None:
                print(f"[bench-guard] {key}: not present yet; skipped "
                      f"(arms once a record carries it)")
            else:
                failures.append(
                    f"{key}: MISSING from {args.new} but present in the "
                    f"baseline — a dropped metric is not a pass"
                )
            continue
        checked += 1
        if new > bound:
            failures.append(
                f"{key}: {new:.3f} > {bound:.3f} allowed ({desc})"
            )
        print(f"[bench-guard] {key}: {new:.3f} (bound {bound:.3f}, "
              f"{desc}) {'REGRESSION' if new > bound else 'OK'}")
    if failures:
        print("[bench-guard] FAIL:")
        for f in failures:
            print(f"  - {f}")
        return 1
    if checked == 0:
        print("[bench-guard] FAIL: no guarded metric found in either "
              "record")
        return 1
    print(f"[bench-guard] PASS ({checked} guarded rows within "
          f"{args.threshold * 100:.0f}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
