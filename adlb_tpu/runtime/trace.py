"""Event tracing — the rebuild's MPE-equivalent profiling layer.

The reference's wrapper layer can emit MPE state events around every API
call (``LOG_ADLB_INTERNALS``, reference ``src/adlb_prof.c:46-74``) and infer
per-work-type "user state" intervals between consecutive ``Get_reserved``
calls (``LOG_GUESS_USER_STATE``, reference ``src/adlb_prof.c:5-12,185-236``).

Here tracing is a run-time flag (``Config(trace=True)``) instead of a
compile-time one. Each rank's :class:`Tracer` records:

* one complete-span event per public API call (``adlb:put``,
  ``adlb:reserve``, ...), and
* one inferred ``user:type<T>`` span from the moment a ``get_reserved`` of
  type T returns until the rank's next API call — the app's presumed compute
  time on that unit, exactly the reference's user-state guess.

Since the observability unification, **servers trace too**: the reactor
wraps each message handler in a ``srv:<TAG>`` span and the balancer wraps
each planning round in ``balancer:round``, on a tracer whose ``pid``
marks the role. Client tracers run as ``pid=0`` ("apps"), server tracers
as ``pid=1`` ("servers"), so one merged Perfetto/chrome://tracing file
shows both sides of every reserve as two process lanes on a shared
clock (all ranks in one ``run_world`` share ``time.monotonic``). The
native ranks trace alike under ``ADLB_TRACE=<prefix>``: a C client
(``libadlb.cpp``) its API calls, a native daemon (``serverd.cpp``) its
reactor's ``srv:<TAG>`` handlers with ``srv:decode``, ``srv:flush`` and
``srv:snapshot``, as ``pid=1``, each into ``<prefix>.<rank>.trace.json``
on ``CLOCK_MONOTONIC``, which is ``time.monotonic`` here.

Every span goes through one primitive, :class:`span`: it also writes the
span into a running ``jax.profiler`` session (the device trace's clock)
when JAX is loaded, and observes its duration in the ``span_s{name=...}``
histogram of an obs registry. The planner's loop, round and solve carry a
fixed set of ``adlb.*`` spans through it (docs/USERGUIDE.md §5).

A profiler session has a clock of its own. :func:`clock_mark` ties it to
``CLOCK_MONOTONIC``, so that what any process of the host stamped (Chrome
files, the daemons' flight artefacts, an application's own logs) can be
laid over a device trace.

Events use the Chrome trace-event format (``ph: "X"``, microsecond
timestamps, ``tid`` = world rank) so a merged dump loads directly in
Perfetto / chrome://tracing. :func:`merge` combines per-rank tracers;
:func:`save_chrome_trace` writes the JSON file.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Iterable, Optional

PID_APP = 0
PID_SERVER = 1


def _now_us() -> float:
    return time.monotonic() * 1e6


class span:
    """``with span(name, metrics, tracer):`` — one span, up to three sinks.

    * If JAX is already loaded in this process, a
      ``jax.profiler.TraceAnnotation``: inside a profiler session the span
      lands on the host plane of the same ``.xplane.pb`` as the device
      planes, on their clock; with no session it is one activity check.
      This module never imports JAX itself (clients import it).
    * ``metrics`` (an obs ``Registry``): the duration is observed in the
      histogram ``span_s{name=<name>}``, so a flight artefact or
      ``/metrics`` carries count, sum and buckets with no profiler at all.
    * ``tracer`` (a :class:`Tracer`): the Chrome-trace event, with ``args``.
    """

    __slots__ = ("name", "_hist", "_tracer", "_args", "_t0", "_ann")

    def __init__(self, name: str, metrics=None, tracer=None, **args) -> None:
        self.name = name
        self._hist = (
            metrics.histogram("span_s", name=name)
            if metrics is not None else None
        )
        self._tracer = tracer
        self._args = args

    def __enter__(self) -> "span":
        # a jax still half-way through its import has no profiler yet
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self._ann = None
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t0 = self._t0
        dur = time.monotonic() - t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(dur)
        tr = self._tracer
        if tr is not None:
            tr._emit(
                {
                    "name": self.name,
                    "ph": "X",
                    "ts": t0 * 1e6,
                    "dur": dur * 1e6,
                    "pid": tr.pid,
                    "tid": tr.rank,
                    **({"args": self._args} if self._args else {}),
                }
            )


#: the least time between two clock marks of a process
CLOCK_MARK_GAP_S = 0.5
_next_clock_mark = 0.0


def clock_mark() -> None:
    """Where JAX is loaded, one ``adlb.clock`` ``TraceAnnotation`` that
    carries ``time.monotonic_ns()``, read as it starts, as its ``ns``
    argument; at most one every ``CLOCK_MARK_GAP_S``, and nothing at all
    without JAX. In a profiler session the mark lands on the host plane
    with the session's own time stamp, so ``start_ns - ns`` is the offset
    between ``CLOCK_MONOTONIC`` and the trace, and the marks of a window
    give its spread (``benchmarks/reduce/daemons.py`` reads them). The
    planner's loops call it between their spans."""
    global _next_clock_mark
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return
    now = time.monotonic()
    if now < _next_clock_mark:
        return
    _next_clock_mark = now + CLOCK_MARK_GAP_S
    with profiler.TraceAnnotation("adlb.clock", ns=time.monotonic_ns()):
        pass


class Tracer:
    """Per-rank event buffer. Cheap enough to leave on: one dict append per
    event, no locks on the hot path (each rank owns its tracer; the one
    cross-thread writer — the balancer thread into its server's tracer —
    rides CPython's atomic list.append). ``max_events`` bounds memory on
    long server runs; overflow increments ``dropped`` instead of growing."""

    def __init__(
        self,
        rank: int,
        pid: int = PID_APP,
        process_name: Optional[str] = None,
        max_events: int = 500_000,
    ) -> None:
        self.rank = rank
        self.pid = pid
        self.max_events = max_events
        self.dropped = 0
        self.events: list[dict] = []
        if process_name:
            # Chrome-trace metadata: names the pid lane in Perfetto
            self.events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "ts": 0.0,
                    "pid": pid,
                    "tid": 0,
                    "args": {"name": process_name},
                }
            )
        # pending user-state inference: (work_type, span start in us)
        self._user_since: Optional[tuple[int, float]] = None

    def _emit(self, ev: dict) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(ev)

    def span(self, name: str, **args) -> span:
        return span(name, tracer=self, **args)

    def instant(self, name: str, **args) -> None:
        self._emit(
            {
                "name": name,
                "ph": "i",
                "ts": _now_us(),
                "s": "t",
                "pid": self.pid,
                "tid": self.rank,
                **({"args": args} if args else {}),
            }
        )

    # -- user-state inference (reference src/adlb_prof.c:185-236) -----------

    def api_entry(self) -> None:
        """Close any open inferred user-state span: the app was presumed
        computing on the last fetched unit until it came back to the API."""
        if self._user_since is None:
            return
        work_type, t0 = self._user_since
        self._user_since = None
        self._emit(
            {
                "name": f"user:type{work_type}",
                "ph": "X",
                "ts": t0,
                "dur": _now_us() - t0,
                "pid": self.pid,
                "tid": self.rank,
                "args": {"work_type": work_type},
            }
        )

    def got_work(self, work_type: int) -> None:
        """A get_reserved of `work_type` just returned — start presuming
        user compute."""
        self._user_since = (work_type, _now_us())


def merge(tracers: Iterable[Tracer]) -> list[dict]:
    events: list[dict] = []
    for t in tracers:
        events.extend(t.events)
    events.sort(key=lambda e: e["ts"])
    return events


def save_chrome_trace(events: list[dict], path: str) -> None:
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


def span_names(events: Iterable[dict]) -> set[str]:
    return {e["name"] for e in events}
