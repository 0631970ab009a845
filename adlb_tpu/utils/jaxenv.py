"""Process-level JAX set-up: virtual CPU devices for tests, who holds the
chip, and where the persistent compilation cache lives.

No helper selects a platform. Which backend JAX uses is the
caller's environment (``JAX_PLATFORMS``; the tier-1 test line sets
``cpu``) — code that re-pins it would hide a chip that failed to start.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def virtual_cpu_devices(n_devices: int = 8) -> None:
    """Ask XLA's host platform for ``n_devices`` virtual devices, so
    sharding code can be checked on a mesh without chips (the analogue of
    testing the reference's multi-rank protocols under ``mpiexec -n k``
    on one host, reference ``examples/nq.c:179-183``).

    Only the CPU platform reads the flag, and only when its backend is
    created — call this before anything touches ``jax.devices()``."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()


def accelerator_held() -> Optional[str]:
    """The platform of the accelerator backend THIS process has
    initialized, or None (no JAX, no backend yet, or the CPU backend).
    Never initializes one: a chip belongs to the process that brought
    its backend up, so the question must not be what takes it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    # JAX has no public "is a backend up?" query, and jax.devices()
    # would answer by bringing one up
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


def ensure_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for an accelerator
    backend; returns its directory (None on the CPU backend).

    Call where the first device program of the process is about to be
    built (the cache binds its directory at the first compile; this
    initializes the backend, as that build would). The directory is
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it — JAX reads
    that itself, so nothing is set here — and ``<checkout>/.jax_cache``
    otherwise: a fixed path, because the path is part of what a later
    process must find again. Sub-second programs are kept too: the first
    device solve compiles inside the first planning round, while every
    worker is parked.

    MLIR locations are cut down to the innermost user frame. JAX's default
    puts the caller's whole Python stack into them; XLA programs are keyed
    with locations stripped, but a Mosaic kernel is serialized INTO its
    custom call with them, so the same Pallas sweep traced from the
    sidecar thread, from the master's balancer thread and from
    chip_smoke.py had three different keys and never hit (measured on the
    v5e: 4 of 8 programs missed in a second process until this was set).

    The CPU backend is left alone: its compiles are cheap, XLA:CPU logs a
    machine-feature mismatch on every reload (even on the machine that
    compiled), and a checkout copied to a machine with another CPU would
    carry code built for the first."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path
