"""The device programs compile for the v5e — checked without a chip.

``jax.experimental.topologies`` describes a ``v5e:2x2`` host to the
installed libtpu, which then compiles ahead of time for ``TPU v5 lite``
exactly as it would on the machine: Mosaic accepts or refuses the Pallas
sweep here, not after chip time has been spent. Whether the programs RUN
and give the numpy twin's answer on hardware is ``chip_smoke.py``'s job.

The compiles run in a child process (this file, run as a script): loading
libtpu starts threads, and the rest of tier-1 forks worlds from the
pytest process.

Quick set: the Pallas sweep in its int32 layout, the XLA scan, and the
two mesh programs at small shapes. ``-m slow``: the int8 layout (needs
>= 16 Mi compat elements, ~12 s) and the 65,536 x 8,192 stress shape.
"""

import json
import os
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> ("pallas" | "xla", tasks, requesters, types)
SOLVE_CASES = {
    "pallas_int32_1024x256": ("pallas", 1024, 256, 4),
    "pallas_int32_4096x512": ("pallas", 4096, 512, 4),
    "xla_1024x256": ("xla", 1024, 256, 4),
    "pallas_int8_32768x4096": ("pallas", 32768, 4096, 4),
    "pallas_int8_65536x8192": ("pallas", 65536, 8192, 4),
    "xla_65536x8192": ("xla", 65536, 8192, 4),
}
#: name -> ("gather" | "plan", servers, K, R, types)
MESH_CASES = {
    "mesh_gather_32x64": ("gather", 32, 64, 16, 4),
    "mesh_plan_32x64": ("plan", 32, 64, 16, 4),
    "mesh_plan_32x2048": ("plan", 32, 2048, 256, 1),
}
QUICK = ["pallas_int32_1024x256", "pallas_int32_4096x512", "xla_1024x256",
         "mesh_gather_32x64", "mesh_plan_32x64"]
SLOW = ["pallas_int8_32768x4096", "pallas_int8_65536x8192",
        "xla_65536x8192", "mesh_plan_32x2048"]


# ------------------------------------------------------------ child process


def _compile_solve(topo, kind, NT, NR, T):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from adlb_tpu.balancer.pallas_solve import pallas_greedy_assign
    from adlb_tpu.balancer.solve import _greedy_assign

    sh = SingleDeviceSharding(topo.devices[0])
    args = [
        jax.ShapeDtypeStruct(shape, dt, sharding=sh)
        for shape, dt in (((NT,), jnp.int32), ((NT,), jnp.int32),
                          ((NR, T), jnp.bool_), ((NR,), jnp.bool_))
    ]
    if kind == "pallas":
        return pallas_greedy_assign.lower(*args, interpret=False).compile()
    return _greedy_assign.lower(*args).compile()


def _compile_mesh(topo, kind, S, K, R, T):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from adlb_tpu.balancer.distributed import (
        _build_gather_fn, _build_plan_fn, _slot_sizes)

    mesh = Mesh(np.array(topo.devices), ("s",))
    rounds, m = 16, 32  # DistributedAssignmentSolver's defaults
    C, D = _slot_sizes(None, m, rounds, S * R)

    def spec(shape, dt, p):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, p))

    table = [spec((S, K), jnp.int32, P("s", None)),
             spec((S, K), jnp.int32, P("s", None)),
             spec((S,), jnp.int32, P("s"))]
    if kind == "gather":
        return _build_gather_fn(mesh, T, D).lower(*table).compile()
    return _build_plan_fn(mesh, T, D, C, rounds, m).lower(
        *table,
        spec((T, C), jnp.int32, P(None, None)),
        spec((T,), jnp.int32, P(None)),
        spec((T * C + 1,), jnp.bool_, P(None)),
    ).compile()


def _key_from_two_stacks(topo) -> list:
    """The cache-key hash of the Pallas sweep's computation, traced from
    two different Python call depths, after ensure_compile_cache() has
    run as it would on a TPU."""
    import hashlib
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax._src import cache_key  # what the persistent cache keys on
    from jax.sharding import SingleDeviceSharding

    from adlb_tpu.balancer.pallas_solve import pallas_greedy_assign
    from adlb_tpu.utils import jaxenv

    sh = SingleDeviceSharding(topo.devices[0])
    args = [
        jax.ShapeDtypeStruct(shape, dt, sharding=sh)
        for shape, dt in (((1024,), jnp.int32), ((1024,), jnp.int32),
                          ((256, 4), jnp.bool_), ((256,), jnp.bool_))
    ]

    def key():
        jax.clear_caches()  # retrace: locations are taken at trace time
        lowered = pallas_greedy_assign.lower(*args, interpret=False)
        h = hashlib.sha256()
        cache_key._hash_computation(
            h, lowered.compiler_ir("stablehlo"), cache_key.IgnoreCallbacks.NO)
        return h.hexdigest()

    def from_deeper():
        return key()

    # as on an accelerator; any cache file goes to a throwaway directory
    real = jax.default_backend
    with tempfile.TemporaryDirectory() as tmp:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = tmp
        jax.default_backend = lambda: "tpu"
        try:
            jaxenv.ensure_compile_cache()
        finally:
            jax.default_backend = real
        return [key(), from_deeper()]


def _child(names) -> int:
    sys.path.insert(0, _REPO)
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 — reported as the skip reason
        print("RESULT " + json.dumps({"skip": repr(e)[:300]}))
        return 0
    out = {"device_kind": topo.devices[0].device_kind,
           "devices": len(topo.devices)}
    for name in names:
        t0 = time.perf_counter()
        if name == "cache_key_two_stacks":
            out[name] = _key_from_two_stacks(topo)
            continue
        if name in SOLVE_CASES:
            compiled = _compile_solve(topo, *SOLVE_CASES[name])
        else:
            compiled = _compile_mesh(topo, *MESH_CASES[name])
        text = compiled.as_text()
        out[name] = {
            "seconds": round(time.perf_counter() - t0, 2),
            "mosaic": "tpu_custom_call" in text,
            "collective": "all-gather" in text,
        }
    print("RESULT " + json.dumps(out))
    return 0


# -------------------------------------------------------------------- tests


def _aot(names) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *names],
        capture_output=True, text=True, timeout=600, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    out = json.loads(line[len("RESULT "):])
    if "skip" in out:
        pytest.skip(f"no v5e topology from this installation: {out['skip']}")
    assert out["device_kind"] == "TPU v5 lite" and out["devices"] == 4
    return out


def _check(out, names) -> None:
    for name in names:
        kind = (SOLVE_CASES.get(name) or MESH_CASES[name])[0]
        # the Pallas sweep must have gone through Mosaic, and the fused
        # planning round must hold its cross-shard gather
        assert out[name]["mosaic"] == (kind == "pallas"), (name, out[name])
        assert out[name]["collective"] == (kind == "plan"), (name, out[name])


def test_device_programs_compile_for_v5e():
    out = _aot(QUICK + ["cache_key_two_stacks"])
    _check(out, QUICK)
    # a Mosaic kernel travels inside its custom call WITH its locations:
    # unless ensure_compile_cache() keeps the caller's stack out of them,
    # each host of the planner compiles the same sweep under its own key
    shallow, deeper = out["cache_key_two_stacks"]
    assert shallow == deeper


@pytest.mark.slow
def test_stress_shapes_compile_for_v5e():
    _check(_aot(SLOW), SLOW)


if __name__ == "__main__":
    sys.exit(_child(sys.argv[1:]))
