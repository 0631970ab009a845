"""What the native daemons say of themselves, from their flight artefacts.

A daemon that was given a flight directory writes
``flight-serverd-r<rank>-p<pid>.json`` there at its end
(``adlb_tpu/native/serverd.cpp``, ``docs/USERGUIDE.md`` §5): where its
reactor thread's time went, by phase for the whole world (``phase_s``,
``phase_n``) and by group for every ``CLOCK_MONOTONIC`` second
(``by_second``); how long parked reserves waited, by what ended the wait
(``park_wait_s``); the plan entries it received and found stale; and the
eight counters of its ``STATS`` trailer. The native plane passes
``<scratch>/flight`` to every world, so the files are there after a run,
traced or not.

``analyse(run)`` is what the metric readers call. It loads the artefacts
once a run, names the **hot daemon** (rank ``app_ranks``: rank 0 produces
and its home is the first server), clips ``by_second`` to the whole seconds
inside the window as ``metrics/reactor_busy_pct.py`` does, merges the
histograms over the daemons, says on earlier lines what it found, and keeps
the result in ``run``. A run that left no artefact (a parent commit, another
plane) gives None, and every reader then reports nothing.

Where the trace holds the planner's clock marks (``adlb.clock``,
``runtime/trace.py::clock_mark``: a ``TraceAnnotation`` that carries
``time.monotonic_ns()``), ``start_ns - ns`` of a mark is the offset between
``CLOCK_MONOTONIC`` and the trace's host plane. With it the clients' fetch
records lie on the trace's clock, and ``overlay`` splits the time that the
workers not homed with the producer spent inside a fetch call by what the
planner's thread was doing at that time.
"""

from __future__ import annotations

import glob
import json
import math
import os
import statistics

from benchmarks.reduce import hostspans, xplane

GROUPS = ("asleep", "poll", "decode", "flush", "put", "fetch", "enact",
          "snapshot", "other")
CAUSES = ("local", "migrated", "plan", "steal")
COUNTERS = ("waits_polled", "waits_slept", "bells_rung", "bells_elided",
            "frames_ring", "frames_sock", "conns_unix", "conns_tcp")
MARK = "adlb.clock"
_KEY = "_daemons"


def flight_dir(run: dict) -> str:
    """``<checkout>/.bench_scratch/<cell>/flight``, where the plane told
    the world to leave its artefacts (as ``reduce/servers.py`` finds the
    scratch directory)."""
    root = os.path.dirname(run["bench_dir"])
    return os.path.join(root, ".bench_scratch", run["cell"], "flight")


def load(run: dict) -> dict:
    """``{rank: artefact}`` of the daemons of this run's world."""
    docs = {}
    for path in sorted(glob.glob(os.path.join(
            flight_dir(run), "flight-serverd-r*.json"))):
        with open(path) as f:
            doc = json.load(f)
        docs[int(doc["rank"])] = doc
    return docs


def window_seconds(run: dict) -> range:
    """The whole ``CLOCK_MONOTONIC`` seconds that lie inside the window."""
    return range(math.ceil(run["window"].t0), math.floor(run["window"].t_end))


def clip(doc: dict, seconds: range) -> dict | None:
    """A daemon's ``by_second`` summed over ``seconds``: ``{"seconds": k,
    "s": {group: seconds}, "n": {group: count}}``; None unless the daemon
    recorded every one of them."""
    by_second, groups = doc.get("by_second") or {}, doc.get("groups") or []
    if not len(seconds) or any(str(sec) not in by_second for sec in seconds):
        return None
    s, n = dict.fromkeys(groups, 0.0), dict.fromkeys(groups, 0)
    for sec in seconds:
        rec = by_second[str(sec)]
        for g, ds, dn in zip(groups, rec["s"], rec["n"]):
            s[g] += ds
            n[g] += dn
    return {"seconds": len(seconds), "s": s, "n": n}


def merged(hists: list) -> dict | None:
    """Histograms of one bucket layout, added up."""
    hists = [h for h in hists if h]
    if not hists:
        return None
    return {"bounds": list(hists[0]["bounds"]),
            "counts": [sum(col) for col in zip(*(h["counts"] for h in hists))],
            "sum": sum(h["sum"] for h in hists),
            "n": sum(h["n"] for h in hists)}


def quantile_ms(hist: dict | None, q: float) -> float | None:
    """``adlb_tpu.obs.metrics.quantile_of`` over an artefact's histogram,
    in milliseconds; None where nothing was observed."""
    from adlb_tpu.obs.metrics import quantile_of

    if not hist or not hist["n"]:
        return None
    return quantile_of(hist["bounds"], hist["counts"], hist["n"], q) * 1e3


def fed_wait_ms(red: dict | None, q: float) -> float | None:
    """Quantile ``q`` of the park waits that the planner ended, causes
    ``plan`` and ``migrated`` merged, in milliseconds."""
    if red is None:
        return None
    return quantile_ms(
        merged([red["park"]["plan"], red["park"]["migrated"]]), q)


def reduce(docs: dict, hot_rank: int, seconds: range) -> dict | None:
    """Everything the readers need, from loaded artefacts."""
    if hot_rank not in docs:
        return None
    hot = docs[hot_rank]
    clips = [c for c in (clip(d, seconds) for d in docs.values()) if c]
    every = None
    if len(clips) == len(docs):  # a sum over some daemons is no one's number
        every = {"seconds": clips[0]["seconds"],
                 "s": {g: sum(c["s"].get(g, 0.0) for c in clips)
                       for g in GROUPS},
                 "n": {g: sum(c["n"].get(g, 0) for c in clips)
                       for g in GROUPS}}
    others = [d for r, d in docs.items() if r != hot_rank]
    return {
        "daemons": len(docs), "hot_rank": hot_rank,
        "hot": hot, "hot_window": clip(hot, seconds), "all_window": every,
        "park": {c: merged([d["park_wait_s"].get(c) for d in docs.values()])
                 for c in CAUSES},
        "plan_entries": sum(d["plan_entries"] for d in docs.values()),
        "plan_stale": sum(d["plan_stale"] for d in docs.values()),
        "counters": {k: [hot.get(k, 0), sum(d.get(k, 0) for d in others)]
                     for k in COUNTERS},
    }


def per_frame_us(window: dict | None, group: str) -> float | None:
    """Microseconds of ``group`` a frame of it, over a clipped window."""
    if not window or not window["n"].get(group):
        return None
    return window["s"][group] / window["n"][group] * 1e6


def flood_second(doc: dict) -> dict | None:
    """The second in which a daemon handled most puts, and what each group
    of it cost a put: under a synchronous producer every turn is one put,
    so this is the daemon's half of a put's round trip, piece by piece
    (``poll`` is the wait for the producer's next one); a pipelined
    producer's frames share their turns."""
    groups = doc.get("groups") or []
    if "put" not in groups or not doc.get("by_second"):
        return None
    g_put = groups.index("put")
    sec, rec = max(doc["by_second"].items(), key=lambda kv: kv[1]["n"][g_put])
    puts = rec["n"][g_put]
    if not puts:
        return None
    return {"sec": int(sec), "puts": puts,
            "asleep": rec["s"][groups.index("asleep")] / sum(rec["s"]),
            "us": {g: s / puts * 1e6 for g, s in zip(groups, rec["s"])}}


def handler_us(doc: dict, tag: str) -> float | None:
    """Self time of one handler a frame, whole world, in microseconds."""
    n = doc["phase_n"].get("handler:" + tag)
    return doc["phase_s"]["handler:" + tag] / n * 1e6 if n else None


# ---------------------------------------------------------------- the overlay


def load_planner(path: str) -> tuple:
    """From an ``.xplane.pb``: a trace (``xplane.load``'s shape) of the
    host planes' ``adlb.*`` events, and the clock marks among them as
    ``[[start_ns, monotonic_ns], ...]``. The mark's reading is the event's
    ``ns`` argument, which ``xplane.load`` does not keep."""
    from jax.profiler import ProfileData

    planes, marks = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xplane.HOST_PREFIX):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name
                if name == MARK:
                    ns = dict(ev.stats).get("ns")
                    if ns is not None:
                        marks.append([int(ev.start_ns), int(ns)])
                elif name.startswith(hostspans.PREFIX):
                    events.append([name, int(ev.start_ns),
                                   int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}, sorted(marks)


def clock(marks: list) -> dict | None:
    """The offset to add to a ``CLOCK_MONOTONIC`` reading in nanoseconds
    to lay it on the trace's host plane: the median over the marks, with
    their spread (largest less smallest)."""
    if not marks:
        return None
    offsets = [start - ns for start, ns in marks]
    return {"marks": len(marks),
            "offset_ns": int(statistics.median(offsets)),
            "spread_ns": max(offsets) - min(offsets)}


def overlay(events: list, offset_ns: int, fetches, fetch_rank,
            nservers: int, producer_rank: int = 0) -> dict | None:
    """Worker-seconds that the workers not homed with the producer spent
    inside a fetch call within the planner's window (first to last of
    ``events``, the planner thread's ``adlb.*`` spans), by the innermost
    span of the planner's thread at that time. ``fetches`` are the
    clients' FETCH records on ``CLOCK_MONOTONIC``, moved by ``offset_ns``."""
    if not events:
        return None
    lo, hi = events[0][1], max(e[1] + e[2] for e in events)
    segments = hostspans.innermost(events)
    remote = (fetch_rank % nservers) != (producer_rank % nservers)
    calls = []
    for t_call, t_ret in zip(fetches["t_call"][remote],
                             fetches["t_ret"][remote]):
        start = max(int(t_call * 1e9) + offset_ns, lo)
        end = min(int(t_ret * 1e9) + offset_ns, hi)
        if end > start:
            calls.append([start, end])
    calls.sort()
    total = sum(end - start for start, end in calls)
    if not total:
        return None
    by = hostspans.overlap_by_name(calls, segments)
    by[hostspans.NO_SPAN] = total - sum(by.values())
    return {"window_s": (hi - lo) * 1e-9, "calls": len(calls),
            "fetch_s": total * 1e-9,
            "by_s": {k: v * 1e-9 for k, v in by.items() if v > 0}}


# ------------------------------------------------------------- what it says


def _shares(by: dict, whole: float, n: int) -> str:
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return ", ".join(f"{k} {100.0 * v / whole:.2f}%" for k, v in top)


def describe(red: dict) -> list:
    """The earlier lines of a traced run."""
    hot, lines = red["hot"], []
    win = red["hot_window"]
    if win:
        busy = 100.0 * (1.0 - (win["s"]["asleep"] + win["s"]["poll"])
                        / win["seconds"])
        lines.append(
            f"{red['daemons']} artefacts; hot daemon rank {red['hot_rank']} "
            f"over {win['seconds']} whole seconds of the window (sum "
            f"{sum(win['s'].values()):.6f}s): "
            + _shares(win["s"], win["seconds"], len(GROUPS))
            + f"; busy {busy:.2f}%")
    else:
        lines.append(f"{red['daemons']} artefacts; hot daemon rank "
                     f"{red['hot_rank']} recorded no whole second of the "
                     f"window")
    world = hot["t_end"] - hot["t_start"]
    lines.append(f"hot daemon's phases over its {world:.3f}s of the world: "
                 + _shares(hot["phase_s"], world, 8))
    handlers = sorted(((k[len("handler:"):], v)
                       for k, v in hot["phase_s"].items()
                       if k.startswith("handler:")), key=lambda kv: -kv[1])
    lines.append("hot daemon's handlers by self time: " + ", ".join(
        f"{tag} {s:.4f}s = {handler_us(hot, tag):.2f}us x "
        f"{hot['phase_n']['handler:' + tag]}" for tag, s in handlers[:6]))
    flood = flood_second(hot)
    if flood:
        lines.append(
            f"hot daemon's second with most puts ({flood['sec']}): "
            f"{flood['puts']} puts, a put "
            + ", ".join(f"{g} {flood['us'][g]:.3f}us"
                        for g in ("decode", "put", "flush", "other", "poll"))
            + f"; asleep {100.0 * flood['asleep']:.2f}%")
    lines.append("counters, hot daemon | the others: " + ", ".join(
        f"{k} {a} | {b}" for k, (a, b) in red["counters"].items()))
    waits = []
    for cause in CAUSES:
        h = red["park"][cause]
        if h and h["n"]:
            waits.append(f"{cause} n={h['n']} p50 {quantile_ms(h, 0.5):.3f} "
                         f"p95 {quantile_ms(h, 0.95):.3f} mean "
                         f"{h['sum'] / h['n'] * 1e3:.3f} ms")
        else:
            waits.append(f"{cause} n=0")
    lines.append("park waits by cause, all daemons, whole world: "
                 + "; ".join(waits))
    stale = (f"{100.0 * red['plan_stale'] / red['plan_entries']:.2f}%"
             if red["plan_entries"] else "n/a")
    enact = per_frame_us(red["all_window"], "enact")
    lines.append(f"plan entries {red['plan_entries']}, stale "
                 f"{red['plan_stale']} ({stale}); enactment in the window "
                 + ("n/a" if enact is None else
                    f"{enact:.2f}us a frame over "
                    f"{red['all_window']['n']['enact']} frames"))
    return lines


def describe_overlay(clk: dict | None, over: dict | None) -> list:
    if clk is None:
        return ["clock: the trace holds no adlb.clock mark — the program "
                "has none; no overlay"]
    lines = [f"clock: {clk['marks']} marks; trace host plane = "
             f"CLOCK_MONOTONIC {clk['offset_ns']:+d} ns, spread "
             f"{clk['spread_ns'] * 1e-3:.3f} us"]
    if over is None:
        lines.append("overlay: no remote worker's fetch call lies in the "
                     "planner's window")
    else:
        lines.append(
            f"overlay: {over['calls']} fetch calls of remote workers, "
            f"{over['fetch_s']:.3f} worker-seconds inside them in the "
            f"planner's {over['window_s']:.3f}s (mean "
            f"{over['fetch_s'] / over['window_s']:.2f} workers in a fetch), "
            f"by the planner's innermost span: "
            + _shares(over["by_s"], over["fetch_s"], 10))
    return lines


def analyse(run: dict) -> dict | None:
    """The reduction of this run's artefacts, made once a run; None when
    the run left none for its hot daemon."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    docs = load(run)
    red = reduce(docs, run["config"]["app_ranks"], window_seconds(run))
    cell = run.get("cell", "")
    if red is None:
        print(f"[{cell}] daemons: no flight-serverd artefact of the hot "
              f"daemon — the program writes none; the daemon metrics are "
              f"left out", flush=True)
        return None
    lines = describe(red)
    path = hostspans.trace_path(run) if run.get("trace") is not None else None
    if path is not None:
        trace, marks = load_planner(path)
        clk, over = clock(marks), None
        if clk is not None:
            logs = run["logs"]
            over = overlay(hostspans.planner_events(trace), clk["offset_ns"],
                           logs.fetches, logs.fetch_rank,
                           run["config"]["servers"])
        red["clock"], red["overlay"] = clk, over
        lines += describe_overlay(clk, over)
    for line in lines:
        print(f"[{cell}] daemons: {line}", flush=True)
    run[_KEY] = red
    return red
