"""Where the persistent compilation cache is placed (utils/jaxenv.py)."""

import os

import jax

from adlb_tpu.utils import jaxenv

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch, backend: str) -> list:
    """Run ensure_compile_cache() as if on ``backend``, recording (not
    applying) what it would set in jax.config."""
    updates: list = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    updates.append(jaxenv.ensure_compile_cache())
    return updates


def test_env_set_means_code_sets_no_path(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    *updates, returned = _recorded_updates(monkeypatch, "tpu")
    assert returned == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(updates)
    # sub-second programs are kept either way, and the key of a Pallas
    # program must not hold the caller's stack (test_tpu_lowering.py)
    assert dict(updates)["jax_persistent_cache_min_compile_time_secs"] == 0
    assert dict(updates)["jax_include_full_tracebacks_in_locations"] is False


def test_env_unset_means_checkout_jax_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    *updates, returned = _recorded_updates(monkeypatch, "tpu")
    want = os.path.join(_REPO, ".jax_cache")
    assert returned == want
    assert dict(updates)["jax_compilation_cache_dir"] == want
    assert dict(updates)["jax_persistent_cache_min_compile_time_secs"] == 0
    # a fixed path: no temp name, pid or time in it
    assert str(os.getpid()) not in want and "tmp" not in want.lower()


def test_cpu_backend_is_left_alone(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert _recorded_updates(monkeypatch, "cpu") == [None]
