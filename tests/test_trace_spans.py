"""The span primitive (runtime/trace.py) and the fixed set of ``adlb.*``
spans in the planner's round and solve: which names a round leaves in
``span_s{name=...}``, how they nest, and that a ``jax.profiler`` session
carries them on a host plane of the same file as the device's."""

import glob
import os
import sys

import pytest

from adlb_tpu.obs.metrics import Registry
from adlb_tpu.runtime.trace import PID_SERVER, Tracer, span

T1 = 1

ROUND_GATED = {"adlb.round", "adlb.round.admit", "adlb.round.admit.sync"}
ROUND_PLANNED = ROUND_GATED | {
    "adlb.round.plan", "adlb.round.view", "adlb.solve", "adlb.round.mark",
    "adlb.round.migrations", "adlb.round.account", "adlb.solve.pack",
    "adlb.solve.extract"}
DEVICE_SOLVE = {"adlb.solve.put", "adlb.solve.call", "adlb.solve.wait",
                "adlb.solve.get"}
HOST_SOLVE = {"adlb.solve.host"}

#: parent -> the spans that run inside it
CHILDREN = {
    "adlb.round": ["adlb.round.admit", "adlb.round.plan"],
    "adlb.round.admit": ["adlb.round.admit.sync"],
    "adlb.round.plan": ["adlb.round.view", "adlb.solve", "adlb.round.mark",
                        "adlb.round.migrations", "adlb.round.account"],
    "adlb.solve": ["adlb.solve.pack", "adlb.solve.put", "adlb.solve.call",
                   "adlb.solve.wait", "adlb.solve.get", "adlb.solve.host",
                   "adlb.solve.extract"],
}


def span_hists(reg: Registry) -> dict:
    """``{span name: histogram dict}`` of a registry's ``span_s`` family."""
    hists = reg.snapshot()["histograms"]
    prefix = "span_s{name="
    return {k[len(prefix):-1]: h for k, h in hists.items()
            if k.startswith(prefix)}


def starved_world() -> dict:
    """Server 10 holds eight units, server 11 none and a parked requester:
    the round solves (a cross-server pair) and pumps (11 is under half its
    share)."""
    return {
        10: {"tasks": [(i, T1, 1, 8) for i in range(8)], "reqs": [],
             "consumers": 1},
        11: {"tasks": [], "reqs": [(0, 1, [T1])], "consumers": 1},
    }


def local_only_world() -> dict:
    """Supply and demand on one server: the gate closes the round."""
    return {10: {"tasks": [(1, T1, 5, 8)], "reqs": [(0, 1, [T1])],
                 "consumers": 1}}


def engine(reg, host_threshold_reqs=0, host_ledger="array"):
    from adlb_tpu.balancer.engine import PlanEngine

    return PlanEngine(types=(T1,), max_tasks=16, max_requesters=4,
                      backend="xla", metrics=reg, host_ledger=host_ledger,
                      host_threshold_reqs=host_threshold_reqs)


# ------------------------------------------------------------ the primitive


class FakeAnnotation:
    seen: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.seen.append(self.name)

    def __exit__(self, *exc):
        FakeAnnotation.seen.append("/" + self.name)


@pytest.mark.parametrize("jax_loaded", [False, True])
def test_primitive_annotates_only_when_jax_is_loaded(monkeypatch, jax_loaded):
    import jax

    monkeypatch.setattr(FakeAnnotation, "seen", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    if not jax_loaded:
        monkeypatch.delitem(sys.modules, "jax")
    reg, tr = Registry(), Tracer(4, pid=PID_SERVER)
    with span("adlb.round", reg, tr, src=7):
        pass
    assert "jax" in sys.modules or not jax_loaded  # never imported by it
    assert FakeAnnotation.seen == (
        ["adlb.round", "/adlb.round"] if jax_loaded else [])
    # the histogram and the Chrome event do not depend on JAX
    assert span_hists(reg)["adlb.round"]["count"] == 1
    (ev,) = tr.events
    assert (ev["name"], ev["ph"], ev["pid"], ev["tid"], ev["args"]) == (
        "adlb.round", "X", PID_SERVER, 4, {"src": 7})


def test_primitive_with_no_sink_and_with_a_raising_body():
    reg = Registry()
    with span("adlb.nothing"):
        pass
    with pytest.raises(KeyError):
        with span("adlb.raises", reg):
            raise KeyError("x")
    assert span_hists(reg)["adlb.raises"]["count"] == 1  # still observed


class FakeMark:
    seen: list = []

    def __init__(self, name, **args):
        FakeMark.seen.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


@pytest.mark.parametrize("jax_loaded", [False, True])
def test_clock_mark_is_silent_without_jax_and_paced_with_it(
        monkeypatch, jax_loaded):
    import time

    import jax

    from adlb_tpu.runtime import trace

    monkeypatch.setattr(FakeMark, "seen", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeMark)
    monkeypatch.setattr(trace, "_next_clock_mark", 0.0)
    if not jax_loaded:
        monkeypatch.delitem(sys.modules, "jax")
    before = time.monotonic_ns()
    for _ in range(50):  # a loop that turns far more often than marks go
        trace.clock_mark()
    after = time.monotonic_ns()
    assert "jax" in sys.modules or not jax_loaded  # never imported by it
    if not jax_loaded:
        assert FakeMark.seen == []
        return
    ((name, args),) = FakeMark.seen  # one a CLOCK_MARK_GAP_S
    assert name == "adlb.clock" and list(args) == ["ns"]
    assert before <= args["ns"] <= after  # CLOCK_MONOTONIC, read at the mark
    monkeypatch.setattr(trace, "_next_clock_mark", 0.0)  # the gap has passed
    trace.clock_mark()
    assert len(FakeMark.seen) == 2


def test_a_profiler_session_keeps_the_marks_reading(tmp_path, monkeypatch):
    """The reading a mark carries comes back out of the ``.xplane.pb`` as
    the event's ``ns`` argument, beside the session's own time stamp: the
    two clocks' offset, to microseconds."""
    import jax
    from jax.profiler import ProfileData

    from adlb_tpu.runtime import trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            monkeypatch.setattr(trace, "_next_clock_mark", 0.0)
            trace.clock_mark()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    offsets = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "adlb.clock":
                    assert plane.name.startswith("/host:")
                    offsets.append(ev.start_ns - dict(ev.stats)["ns"])
    assert len(offsets) == 3
    assert max(offsets) - min(offsets) < 1e6  # within a millisecond here


@pytest.mark.parametrize("args", [{}, {"src": 3, "tag": "PUT"}])
def test_tracer_span_keeps_its_event(args):
    tr = Tracer(rank=3)
    with tr.span("adlb:reserve", **args):
        pass
    (ev,) = tr.events
    assert set(ev) == {"name", "ph", "ts", "dur", "pid", "tid"} | (
        {"args"} if args else set())
    assert ev["name"] == "adlb:reserve" and ev["ph"] == "X"
    assert ev["tid"] == 3 and ev["pid"] == 0
    assert ev["dur"] >= 0 and ev["ts"] > 0
    assert ev.get("args", {}) == args


def test_tracer_span_respects_the_event_cap():
    tr = Tracer(0, max_events=2)
    for _ in range(5):
        with tr.span("x"):
            pass
    assert len(tr.events) == 2 and tr.dropped == 3


# ------------------------------------------------ the planner's span set


@pytest.mark.parametrize("case,world,host_threshold,want", [
    ("device solve and pump", starved_world, 0, ROUND_PLANNED | DEVICE_SOLVE),
    ("host solve and pump", starved_world, 64, ROUND_PLANNED | HOST_SOLVE),
    ("gated", local_only_world, 0, ROUND_GATED),
])
@pytest.mark.parametrize("host_ledger", ["array", "py"])
def test_a_round_leaves_exactly_the_fixed_span_names(
        case, world, host_threshold, want, host_ledger):
    reg = Registry()
    eng = engine(reg, host_threshold, host_ledger)
    matches, migrations = eng.round(world(), None)
    hists = span_hists(reg)
    assert set(hists) == want, case
    if case == "gated":
        assert (matches, migrations) == ([], [])
        assert "balancer_round_s" not in reg.snapshot()["histograms"]
        return
    assert matches == [(10, 0, 11, 0, 1)] and migrations
    assert all(h["count"] == 1 for h in hists.values())
    # the old number is still taken, once, for the round that planned
    assert reg.snapshot()["histograms"]["balancer_round_s"]["count"] == 1
    facts = eng.solver_facts()
    assert (facts["device_solves"], facts["host_solves"]) == (
        (1, 0) if host_threshold == 0 else (0, 1))
    for parent, children in CHILDREN.items():
        inside = sum(hists[c]["sum"] for c in children if c in hists)
        assert inside <= hists[parent]["sum"], parent


def test_gated_and_planned_rounds_are_counted_apart():
    reg = Registry()
    eng = engine(reg)
    eng.round(local_only_world(), None)
    eng.round(local_only_world(), None)
    eng.round(starved_world(), None)
    hists = span_hists(reg)
    assert hists["adlb.round"]["count"] == 3
    assert hists["adlb.round.admit"]["count"] == 3
    assert hists["adlb.round.admit.sync"]["count"] == 3
    assert hists["adlb.round.plan"]["count"] == 1  # the planning rounds
    assert hists["adlb.solve.wait"]["count"] == 1


def test_a_failed_device_solve_is_counted_and_raised():
    reg = Registry()
    eng = engine(reg)

    def broken(*_args):
        raise RuntimeError("device lost")

    eng.solver._device_fn = broken
    with pytest.raises(RuntimeError, match="device lost"):
        eng.round(starved_world(), None)
    assert eng.solver.device_failures == 1
    assert eng.solver.device_solve_count == 0
    hists = span_hists(reg)
    assert hists["adlb.solve.call"]["count"] == 1
    assert "adlb.solve.wait" not in hists


def test_engine_without_a_registry_plans_the_same():
    with_reg = engine(Registry()).round(starved_world(), None)
    without = engine(None).round(starved_world(), None)
    assert with_reg[0] == without[0]
    assert [m[:3] for m in with_reg[1]] == [m[:3] for m in without[1]]


# ------------------------------------------- on the profiler's own clock


@pytest.mark.parametrize("name", ["adlb.round", "adlb.round.plan",
                                  "adlb.solve", "adlb.solve.wait"])
def test_profiler_session_holds_the_spans_on_a_host_plane(
        profiled_round, name):
    by_plane = profiled_round
    planes = [p for p, names in by_plane.items() if name in names]
    assert planes and all(p.startswith("/host:") for p in planes), by_plane


@pytest.fixture(scope="module")
def profiled_round(tmp_path_factory):
    """One planning round inside a short ``jax.profiler`` session on the
    CPU backend: ``{plane name: set of adlb.* event names}``."""
    import jax
    from jax.profiler import ProfileData

    eng = engine(Registry())
    eng.round(starved_world(), None)  # compile outside the session
    eng2 = engine(Registry())
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        eng2.round(starved_world(), None)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    by_plane: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("adlb."):
                    by_plane.setdefault(plane.name, set()).add(ev.name)
    return by_plane
