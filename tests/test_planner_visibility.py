"""The device path cannot fail, or be skipped, without the caller knowing.

* a solver error in a planning round ends the world with an error the
  caller sees — for both hosts of the planner (the master's balancer
  thread, the native plane's sidecar) and for a mesh that cannot be built;
* which path answered is in ``WorldResult.server_stats`` (read through
  ``WorldResult.solver_facts()``) for steal, tpu/numpy, tpu/xla and mesh
  worlds;
* ``spawn_world`` refuses to fork a planner-hosting child from a parent
  that holds an accelerator backend.
"""

import time

import pytest

from adlb_tpu import ADLB_SUCCESS, run_world
from adlb_tpu.runtime.transport_tcp import spawn_world
from adlb_tpu.runtime.world import Config

T = 1


def _hot_app(ctx):
    """Hotspot in miniature, for worlds of 3 servers: with
    put_routing="home" every unit enters rank 0's server, and nobody homed
    there consumes — so the world cannot end unless cross-server plans
    feed the consumers on the other two servers."""
    if ctx.rank == 0:
        for i in range(40):
            ctx.put(b"p%d" % i, T, work_prio=5)
        return 0
    if ctx.rank % 3 == 0:
        return 0  # homed with the producer
    n = 0
    while True:
        rc, r = ctx.reserve([T])
        if rc != ADLB_SUCCESS:
            return n
        ctx.get_reserved(r.handle)
        time.sleep(0.002)
        n += 1


def _cfg(**kw) -> Config:
    return Config(put_routing="home", exhaust_check_interval=0.2, **kw)


# ------------------------------------------------- which path answered


@pytest.mark.parametrize("name,cfg,want", [
    ("steal", _cfg(balancer="steal"),
     dict(path="none", platform=None, device_solves=0, host_solves=0)),
    ("tpu-numpy", _cfg(balancer="tpu"),
     dict(path="numpy", platform=None, device_solves=0)),
    ("tpu-xla", _cfg(balancer="tpu", solver_host_threshold=0),
     dict(path="xla", platform="cpu", device_kind="cpu", host_solves=0)),
    ("tpu-pallas", _cfg(balancer="tpu", solver_host_threshold=0,
                        solver_backend="pallas"),
     dict(path="pallas-interpret", platform="cpu", host_solves=0)),
    ("mesh", _cfg(balancer="tpu", balancer_mesh="auto"),
     dict(path="mesh-device", platform="cpu", device_count=8,
          table_devices=8, host_solves=0)),
    ("mesh-host", _cfg(balancer="tpu", balancer_mesh="auto",
                       balancer_auction="host"),
     dict(path="mesh-host", platform="cpu", host_solves=0)),
])
def test_solver_facts_reach_world_result(name, cfg, want):
    res = run_world(6, 3, [T], _hot_app, cfg=cfg, timeout=100.0)
    assert sum(v for r, v in res.app_results.items() if r) == 40
    master = res.server_stats[min(res.server_stats)]
    facts = master["solver"]
    assert facts is res.solver_facts()
    # non-master servers host no planner and say nothing
    assert [r for r, s in res.server_stats.items() if "solver" in s] == [
        min(res.server_stats)]
    for key in ("path", "platform", "device_kind", "device_count",
                "device_solves", "host_solves", "device_failures"):
        assert key in facts, (name, facts)
    for key, value in want.items():
        assert facts[key] == value, (name, key, facts)
    assert facts["device_failures"] == 0
    if want["path"] == "numpy":
        assert facts["host_solves"] >= 1
    elif want["path"] != "none":
        assert facts["device_solves"] >= 1


def test_sidecar_facts_under_pseudo_rank():
    """Native plane: the planner is the sidecar thread of the calling
    process; its facts sit under its pseudo-rank, one past the world."""
    cfg = _cfg(server_impl="native", balancer="tpu",
               solver_host_threshold=0)
    res = spawn_world(6, 3, [T], _hot_app, cfg=cfg, timeout=90.0)
    assert sum(v for r, v in res.app_results.items() if r) == 40
    facts = res.server_stats[6 + 3]["solver"]
    assert facts is res.solver_facts()
    assert facts["path"] == "xla" and facts["platform"] == "cpu"
    assert facts["device_solves"] >= 1 and facts["host_solves"] == 0
    assert facts["rounds"] >= 1


# ---------------------------------------- a failing solver ends the world


def _boom(*_a, **_k):
    raise RuntimeError("boom: device refused the program")


def test_solver_error_ends_inproc_world(monkeypatch):
    from adlb_tpu.balancer.solve import AssignmentSolver

    monkeypatch.setattr(AssignmentSolver, "solve", _boom)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="balancer failed.*boom"):
        run_world(6, 3, [T], _hot_app, cfg=_cfg(balancer="tpu"),
                  timeout=60.0)
    assert time.monotonic() - t0 < 30.0  # an error, not a timeout


def test_device_error_is_counted_and_fatal(monkeypatch):
    """The device call itself raising (not the whole solve): counted as a
    device failure, never retried on the numpy twin."""
    from adlb_tpu.balancer import solve as solve_mod

    monkeypatch.setattr(solve_mod, "_greedy_assign", _boom)
    solver = solve_mod.AssignmentSolver(
        types=(T,), max_tasks=8, max_requesters=4, host_threshold_reqs=0,
        backend="xla")
    snaps = {0: {"tasks": [(1, T, 1, 8)], "reqs": []},
             1: {"tasks": [], "reqs": [(5, 1, None)]}}
    with pytest.raises(RuntimeError, match="boom"):
        solver.solve(snaps, None)
    assert solver.facts()["device_failures"] == 1
    assert solver.facts()["host_solves"] == 0


def test_solver_error_ends_sidecar_world(monkeypatch):
    from adlb_tpu.balancer.solve import AssignmentSolver

    monkeypatch.setattr(AssignmentSolver, "solve", _boom)
    cfg = _cfg(server_impl="native", balancer="tpu")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="sidecar failed.*boom"):
        spawn_world(6, 3, [T], _hot_app, cfg=cfg, timeout=60.0)
    assert time.monotonic() - t0 < 30.0


def test_solver_error_ends_all_native_world(monkeypatch):
    """C clients + C++ daemons: nobody but the launcher can notice that
    the planner died, and it must not wait out the timeout to say so."""
    import shutil

    if shutil.which("gcc") is None:
        pytest.skip("no C toolchain")
    from adlb_tpu.balancer.solve import AssignmentSolver
    from adlb_tpu.workloads import hotspot_native

    monkeypatch.setattr(AssignmentSolver, "solve", _boom)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="sidecar failed.*boom"):
        hotspot_native.run(n_tasks=300, work_us=2000, num_app_ranks=8,
                           nservers=3, cfg=Config(balancer="tpu"),
                           timeout=60.0)
    assert time.monotonic() - t0 < 30.0


def test_mesh_that_cannot_be_built_ends_world(monkeypatch):
    from adlb_tpu.balancer.distributed import DistributedAssignmentSolver

    monkeypatch.setattr(DistributedAssignmentSolver, "__init__", _boom)
    with pytest.raises(RuntimeError, match="balancer failed.*boom"):
        run_world(6, 3, [T], _hot_app,
                  cfg=_cfg(balancer="tpu", balancer_mesh="auto"),
                  timeout=60.0)


# ------------------------------------------------------ one owner per chip


def test_spawn_world_refuses_parent_holding_accelerator(monkeypatch):
    from adlb_tpu.utils import jaxenv

    monkeypatch.setattr(jaxenv, "accelerator_held", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already initialized the tpu"):
        spawn_world(2, 1, [T], _hot_app, cfg=_cfg(balancer="tpu"))
    # the sidecar plane keeps the planner in THIS process: allowed
    res = spawn_world(
        6, 3, [T], _hot_app,
        cfg=_cfg(server_impl="native", balancer="tpu"), timeout=90.0)
    assert sum(v for r, v in res.app_results.items() if r) == 40


def test_cpu_backend_in_parent_is_not_held():
    import jax

    from adlb_tpu.utils.jaxenv import accelerator_held

    assert jax.devices()[0].platform == "cpu"
    assert accelerator_held() is None
