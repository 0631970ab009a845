"""``PlanEngine.from_config``: the one place that knows how a world and
its ``Config`` become a planner. Every field it maps reaches the engine
or its solver, both planner hosts (the in-server balancer thread and the
sidecar) build their engine through it and nowhere else, ``Config`` no
longer carries the pump's window and credit horizons or the ledger twin,
and the module still loads without JAX."""

import dataclasses
import subprocess
import sys

import pytest

import tests.conftest  # noqa: F401  (forces the CPU platform)

from adlb_tpu import ADLB_SUCCESS, run_world
from adlb_tpu.balancer.engine import PlanEngine
from adlb_tpu.balancer.jobdim import bias_vector
from adlb_tpu.balancer.sidecar import run_sidecar
from adlb_tpu.obs.metrics import Registry
from adlb_tpu.runtime import codec as codec_mod
from adlb_tpu.runtime.messages import Tag, msg
from adlb_tpu.runtime.world import Config, WorldSpec

T1, T2 = 1, 2
REG = Registry(rank=0)


def _world(nservers=2, types=(T1, T2)):
    return WorldSpec(nranks=4 + nservers, nservers=nservers, types=types)


def _mesh_solver(eng):
    from adlb_tpu.balancer.distributed import DistributedAssignmentSolver

    return isinstance(eng.solver, DistributedAssignmentSolver)


# ---- every mapped field reaches the engine -------------------------------

#: (case, world, Config keywords, metrics, what the engine must then hold)
CASES = [
    ("types", _world(types=(3, 7)), {}, None,
     lambda e: e.base_types == (3, 7) and e.solver.base_types == (3, 7)),
    ("nservers", _world(nservers=5), {}, None,
     lambda e: e.solver.nservers == 5),
    ("balancer_max_tasks", _world(), dict(balancer_max_tasks=48), None,
     lambda e: e.solver.K == 48),
    ("balancer_max_requesters", _world(),
     dict(balancer_max_requesters=24), None,
     lambda e: e.solver.R == 24),
    ("solver_backend", _world(), dict(solver_backend="pallas"), None,
     lambda e: e.solver.backend == "pallas"),
    ("max_malloc_per_server", _world(),
     dict(max_malloc_per_server=12345.0), None,
     lambda e: e.max_malloc_per_server == 12345.0),
    ("balancer_mesh-off", _world(), dict(balancer_mesh="off"), None,
     lambda e: not _mesh_solver(e)),
    ("balancer_mesh-auto", _world(), dict(balancer_mesh="auto"), None,
     lambda e: _mesh_solver(e) and e.solver.auction == "device"),
    ("solver_host_threshold", _world(), dict(solver_host_threshold=7),
     None, lambda e: e.solver.host_threshold_reqs == 7),
    ("solver_host_threshold-default", _world(), {}, None,
     lambda e: e.solver.host_threshold_reqs
     == e.solver.DEFAULT_HOST_THRESHOLD),
    ("balancer_auction", _world(),
     dict(balancer_mesh="auto", balancer_auction="host"), None,
     lambda e: _mesh_solver(e) and e.solver.auction == "host"),
    ("balancer_max_jobs", _world(), dict(balancer_max_jobs=3), None,
     lambda e: e.max_jobs == 3 and e.solver.max_jobs == 3),
    ("job_weights", _world(),
     dict(balancer_max_jobs=2, job_weights={1: 2.0}), None,
     lambda e: e._job_weights == {1: 2.0}
     and e.solver.job_bias == bias_vector({1: 2.0}, 2)
     and e.solver.job_bias != bias_vector(None, 2)),
    ("metrics", _world(), {}, REG,
     lambda e: e.metrics is REG and e.solver.metrics is REG),
    # what Config no longer says is the engine's own statement
    ("pump-and-ledger-defaults", _world(), {}, None,
     lambda e: (e.LOOKAHEAD, e.LOOK_MAX, e.LOOK_GROW_WINDOW, e.INFLOW_TTL,
                e.INFLOW_MIN_AGE) == (8, 512, 0.25, 2.0, 0.05)
     and e._ledger.is_array),
]


@pytest.mark.parametrize("case,world,kw,metrics,holds", CASES,
                         ids=[c[0] for c in CASES])
def test_from_config_maps_the_field(case, world, kw, metrics, holds):
    eng = PlanEngine.from_config(world, Config(balancer="tpu", **kw),
                                 metrics=metrics)
    assert holds(eng), case


# ---- both planner hosts build their engine there -------------------------

@pytest.fixture
def built(monkeypatch):
    """Record every ``from_config`` call and every engine constructed."""
    calls, engines = [], []
    real_from_config = PlanEngine.from_config.__func__
    real_init = PlanEngine.__init__

    def from_config(cls, world, cfg, metrics=None):
        calls.append((world, cfg, metrics))
        return real_from_config(cls, world, cfg, metrics=metrics)

    def init(self, *a, **k):
        engines.append(self)
        real_init(self, *a, **k)

    monkeypatch.setattr(PlanEngine, "from_config", classmethod(from_config))
    monkeypatch.setattr(PlanEngine, "__init__", init)
    return calls, engines


def _app(ctx):
    if ctx.rank == 0:
        for i in range(6):
            ctx.put(b"u%d" % i, T1, work_prio=1)
        return 0
    n = 0
    while True:
        rc, r = ctx.reserve([T1])
        if rc != ADLB_SUCCESS:
            return n
        ctx.get_reserved(r.handle)
        n += 1


def test_the_in_server_balancer_builds_through_from_config(built):
    calls, engines = built
    cfg = Config(balancer="tpu", put_routing="home",
                 exhaust_check_interval=0.2, balancer_max_tasks=32,
                 balancer_max_requesters=8)
    res = run_world(3, 2, [T1], _app, cfg=cfg, timeout=60.0)
    assert sum(res.app_results.values()) == 6
    # one planner, on the master server, built from the world's own cfg
    ((world, seen, metrics),) = calls
    assert seen == cfg and world.nservers == 2 and metrics is not None
    (eng,) = engines
    assert (eng.solver.K, eng.solver.R) == (32, 8)
    assert res.solver_facts()["path"] == "numpy"


def test_the_sidecar_builds_through_from_config(built, tmp_path):
    calls, engines = built
    world = _world()
    s0, s1 = world.server_ranks

    def frame(tag, src, **fields):
        return codec_mod.decode_binary(
            codec_mod.encode_binary(msg(tag, src, **fields)))

    class ScriptedEp:
        script = [
            frame(Tag.SS_STATE, s0, tasks_flat=[100, T1, 5, 8],
                  reqs_flat=[], nbytes=8, consumers=1),
            frame(Tag.SS_STATE, s1, tasks_flat=[],
                  reqs_flat=[0, 1, 1, T1], nbytes=0, consumers=1),
            None,
            frame(Tag.DS_END, s0),
            frame(Tag.DS_END, s1),
        ]

        def recv(self, timeout=None):
            return self.script.pop(0) if self.script else None

        def send(self, dest, m, **kw):
            pass

        def close(self):
            pass

    cfg = Config(balancer="tpu", balancer_min_gap=0.0,
                 flight_dir=str(tmp_path), balancer_max_tasks=32,
                 balancer_max_requesters=8)
    facts = run_sidecar(world, cfg, ScriptedEp())
    assert facts["rounds"] >= 1
    ((seen_world, seen, metrics),) = calls
    assert seen_world is world and seen is cfg
    (eng,) = engines
    assert eng.metrics is metrics and (eng.solver.K, eng.solver.R) == (32, 8)


# ---- what Config no longer carries, and who checks it now ----------------

def test_config_has_76_fields():
    assert len(dataclasses.fields(Config)) == 76


@pytest.mark.parametrize("gone", [
    "balancer_lookahead", "balancer_look_max", "balancer_grow_window",
    "balancer_inflow_ttl", "balancer_inflow_min_age", "host_ledger"])
def test_config_refuses_a_field_it_no_longer_has(gone):
    with pytest.raises(TypeError):
        Config(**{gone: 1})


@pytest.mark.parametrize("kw", [
    dict(lookahead=-1), dict(look_max=-1), dict(grow_window=-0.1),
    dict(inflow_ttl=-1.0), dict(inflow_min_age=-0.01),
    dict(inflow_min_age=3.0),              # above the default TTL of 2 s
    dict(inflow_ttl=0.01),                 # under the default min age
    dict(look_max=4),                      # under the default lookahead
    dict(host_ledger="dict"),
], ids=lambda kw: ",".join(f"{k}={v}" for k, v in kw.items()))
def test_the_engine_validates_its_own_keywords(kw):
    with pytest.raises(ValueError):
        PlanEngine(types=(T1,), max_tasks=16, max_requesters=4, **kw)


# ---- only the planner's host imports JAX ---------------------------------

def test_importing_the_engine_leaves_jax_out():
    code = ("import sys; import adlb_tpu.balancer.engine as e; "
            "assert hasattr(e.PlanEngine, 'from_config'); "
            "assert 'jax' not in sys.modules, 'engine pulled in jax'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
