"""The chip's owner traces itself and reports its device.

``POST /device_trace`` on the master's ops endpoint (obs/device_trace.py),
driven as an operator would: against a forked master rank under
``spawn_world``, whose planner runs device programs. The world is started
from a fresh interpreter that has never touched JAX, so the forked master
brings its own backend up (a forked child of a process that has run JAX
hangs in its first jit, ROADMAP B8; pytest's process has). CPU backend.

And ``solver_facts()["memory_peak_bytes"]`` on both planners.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from adlb_tpu.balancer.engine import NO_PLANNER, PlanEngine
from adlb_tpu.obs.device_trace import DeviceTracer, TraceRefused

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import json, os, sys, threading, time, urllib.error, urllib.request
sys.path.insert(0, sys.argv[1])
out = sys.argv[2]
from adlb_tpu.runtime.transport_tcp import probe_free_ports, spawn_world
from adlb_tpu.runtime.world import Config
from adlb_tpu.types import ADLB_SUCCESS

port = probe_free_ports(1)[0]
flag = os.path.join(out, "end")
T = 1


def app(ctx):
    if ctx.rank == 0:
        for i in range(400):
            ctx.put(b"%04d" % i, T)
        while not os.path.exists(flag):  # holds the world open
            time.sleep(0.05)
        return 0
    n = 0
    while True:
        rc, got = ctx.get_work_batch([T], max_units=2)
        if rc != ADLB_SUCCESS:
            return n
        for _ in got:
            time.sleep(0.005)
            n += 1


def post(query):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/device_trace?{query}", data=b"",
        method="POST")
    give_up = time.monotonic() + 120.0
    while True:
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                return r.status, json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()
        except OSError as e:  # the endpoint is not up yet
            if time.monotonic() >= give_up:
                return 0, repr(e)
            time.sleep(0.05)


seen = {}


def operator():
    try:
        requests()
    finally:
        open(flag, "w").close()  # whatever happened, let the world end


def requests():
    give_up = time.monotonic() + 120.0
    while time.monotonic() < give_up:  # until the endpoint is up
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                   timeout=5).close()
            break
        except OSError:
            time.sleep(0.05)
    # asked before the master's first solve: waits for the backend
    first = {}
    t = threading.Thread(target=lambda: first.update(
        a=post(f"seconds=1.5&dir={out}/a")))
    t.start()
    time.sleep(1.0)
    seen["second"] = post(f"seconds=0.2&dir={out}/b")
    t.join()
    seen["first"] = first["a"]
    seen["bad"] = post("seconds=0&dir=/nowhere")
    # a session the world's end cuts short
    last = {}
    t = threading.Thread(target=lambda: last.update(
        c=post(f"seconds=300&dir={out}/c")))
    t.start()
    time.sleep(1.0)
    open(flag, "w").close()
    t.join()
    seen["last"] = last["c"]


# every lazy import of an HTTP request happens here, on the main thread:
# a fork while another thread holds a module's import lock leaves the
# child unable to import that module
try:
    urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=1)
except OSError:
    pass
op = threading.Thread(target=operator)
op.start()
cfg = Config(balancer="tpu", solver_host_threshold=0, solver_backend="xla",
             put_routing="home", exhaust_check_interval=0.2, ops_port=port)
res = spawn_world(5, 3, [T], app, cfg=cfg, timeout=150.0)
op.join()
assert "jax" not in sys.modules  # the caller never touched it
seen["facts"] = res.solver_facts()
seen["done"] = sum(v for r, v in res.app_results.items() if r)

sys.path.insert(0, os.path.join(sys.argv[1]))
from benchmarks.reduce import hostspans, xplane

for key in ("a", "c"):
    try:
        path = xplane.find_trace_file(os.path.join(out, key))
    except FileNotFoundError:
        continue  # the tests say what the request answered
    trace = xplane.load(path, host_min_ns=0)
    names = {}
    for name, _start, _dur in hostspans.planner_events(trace):
        names[name] = names.get(name, 0) + 1
    seen["names_" + key] = names
seen["b_exists"] = os.path.exists(os.path.join(out, "b"))
print("RESULT " + json.dumps(seen))
"""


@pytest.fixture(scope="module")
def traced_world(tmp_path_factory):
    out = tmp_path_factory.mktemp("device-trace")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, ROOT, str(out)], env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, proc.stdout[-2000:]
    seen = json.loads(line[-1][len("RESULT "):])
    seen["stderr"] = proc.stderr[-2000:]
    return seen


def test_a_session_starts_and_stops_inside_the_owner(traced_world):
    status, doc = traced_world["first"]
    assert status == 200, (doc, traced_world["stderr"])
    assert doc["cut_short"] is False
    assert doc["ended"] - doc["began"] == pytest.approx(doc["seconds"])
    assert 1.5 <= doc["seconds"] < 2.5
    facts = traced_world["facts"]
    assert facts["path"] == "xla" and facts["device_solves"] >= 1
    assert facts["platform"] == "cpu" and facts["memory_peak_bytes"] == 0
    assert traced_world["done"] == 400


def test_the_trace_holds_the_master_loops_spans(traced_world):
    names = traced_world["names_a"]
    assert names.get("adlb.master.wait") and names.get("adlb.master.pace")
    assert names.get("adlb.round") and names.get("adlb.round.admit")
    assert not any(name.startswith("adlb.sidecar.") for name in names)


def test_a_second_request_during_a_session_is_refused(traced_world):
    status, body = traced_world["second"]
    assert status == 409 and "already running" in body
    assert traced_world["b_exists"] is False
    status, _body = traced_world["bad"]
    assert status == 400


def test_a_worlds_end_closes_the_session_and_leaves_a_readable_file(
        traced_world):
    status, doc = traced_world["last"]
    assert status == 200 and doc["cut_short"] is True
    assert doc["seconds"] < 60.0
    assert traced_world["names_c"].get("adlb.round")


# ------------------------------------------------ the tracer, no world


def test_the_tracer_never_starts_jax_for_a_planner_without_a_device():
    tracer = DeviceTracer(lambda: False, ready_wait=0.2)
    with pytest.raises(TraceRefused) as e:
        tracer.trace(1.0, "/nowhere")
    assert e.value.status == 503 and "no device" in str(e.value)
    with pytest.raises(ValueError):
        tracer.trace(-1.0, "/nowhere")
    tracer = DeviceTracer(lambda: False)
    # a request that waits for the backend ends with the world
    result = {}

    def ask():
        try:
            tracer.trace(1.0, "/nowhere")
        except TraceRefused as refused:
            result["status"] = refused.status

    t = threading.Thread(target=ask)
    t.start()
    tracer.close(timeout=10.0)
    t.join(timeout=10.0)
    assert result == {"status": 503} and not t.is_alive()
    with pytest.raises(TraceRefused, match="ending"):
        tracer.trace(1.0, "/nowhere")


# ------------------------------------------------------- the device fact


@pytest.mark.parametrize("threshold,path", [(64, "numpy"), (0, "xla")])
def test_solver_facts_carry_memory_peak_bytes(threshold, path):
    """The in-server master and the sidecar both report the engine's
    ``solver_facts()``; the key is there on every path, and a planner
    that never ran a device program asks no backend for it."""
    assert NO_PLANNER["memory_peak_bytes"] == 0
    from adlb_tpu.runtime.world import WorldSpec

    engine = PlanEngine(types=(1,), max_tasks=8, max_requesters=4,
                        backend="xla", host_threshold_reqs=threshold,
                        nservers=2)
    world = WorldSpec(nranks=4, nservers=2, types=(1,))
    snaps = {
        2: {"tasks": [(1, 1, 0, 8), (2, 1, 0, 8)], "reqs": [],
            "stamp": 1.0, "nbytes": 16, "consumers": 0},
        3: {"tasks": [], "reqs": [(1, 7, None)], "stamp": 1.0,
            "nbytes": 0, "consumers": 1},
    }
    engine.round(snaps, world)
    facts = engine.solver_facts()
    assert facts["path"] == path
    assert facts["memory_peak_bytes"] == 0  # the CPU backend keeps no count
    assert list(NO_PLANNER) == [k for k in facts if k in NO_PLANNER]
