"""Build + load the native core.

Compiles the C/C++ sources beside this file with the system ``g++`` and
loads them with ctypes. No pip/pybind11/setuptools involvement — the
reference's build layer is plain CMake over C sources (reference
``CMakeLists.txt:44-56``); this is the same spirit with less machinery.

Every output lands in one git-ignored directory inside the checkout,
``adlb_tpu/native/_build/<key>/<name>``, where ``<key>`` hashes the
CONTENT of the sources and headers the compile reads plus the compile
command. A checkout therefore never runs a binary built from other
sources — not a stale one left by an older commit (mtimes say nothing
after a ``git checkout`` or a copy), and not another checkout's (nothing
is shared through the system temp directory). Deleting ``_build/`` is
always safe: the next use rebuilds.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence

_DIR = os.path.dirname(os.path.abspath(__file__))
BUILD_DIR = os.path.join(_DIR, "_build")

_build_lock = threading.Lock()


class BuildError(RuntimeError):
    """A native artefact could not be compiled (the compiler's stderr, or
    the OS error, is the message)."""


def build_artifact(name: str, cmd: Sequence[str],
                   inputs: Sequence[str]) -> str:
    """Return the path of ``name`` as built by ``cmd`` from ``inputs``,
    compiling it first unless exactly this content was already built.

    ``cmd`` is the compiler argv with ``"{out}"`` where the output path
    goes; ``inputs`` lists every file whose content the result depends on
    (sources AND the headers they include). A failed compile leaves a
    ``<name>.err`` marker under the same key, so the dozens of ranks a
    world spawns do not each re-pay a doomed g++ run; it clears when the
    content (hence the key) changes."""
    h = hashlib.sha256("\0".join(cmd).encode())
    for path in inputs:
        h.update(b"\0" + os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    out_dir = os.path.join(BUILD_DIR, h.hexdigest()[:16])
    out = os.path.join(out_dir, name)
    with _build_lock:
        if os.path.exists(out):
            return out
        errmark = out + ".err"
        if os.path.exists(errmark):
            with open(errmark) as f:
                raise BuildError(
                    f"{name} build failed previously ({errmark}):\n"
                    f"{f.read()}")
        # compile to a private temp name and rename into place: concurrent
        # processes racing to build must never load a half-written file
        tmp = f"{out}.{os.getpid()}.tmp"
        try:
            os.makedirs(out_dir, exist_ok=True)
            subprocess.run(
                [tmp if a == "{out}" else a for a in cmd],
                check=True, capture_output=True, text=True,
            )
            os.replace(tmp, out)
        except (OSError, subprocess.CalledProcessError) as e:
            detail = (getattr(e, "stderr", "") or str(e))[:800]
            try:
                with open(errmark, "w") as f:
                    f.write(detail)
            except OSError:
                pass
            raise BuildError(f"{name} build failed:\n{detail}") from None
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return out


_WQ_SRC = os.path.join(_DIR, "wqcore.cpp")
_WQ_HDR = os.path.join(_DIR, "wqcore.hpp")
# the socket family between ranks of one host: read by serverd.cpp and by
# libadlb.cpp (capi.build_libadlb), so both spell the name one way
HOSTSOCK_HDR = os.path.join(_DIR, "hostsock.hpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i32, i64, p = ctypes.c_int32, ctypes.c_int64, ctypes.c_void_p
    i32p, i64p = ctypes.POINTER(i32), ctypes.POINTER(i64)
    sig = {
        "adlb_wq_new": (p, []),
        "adlb_wq_free": (None, [p]),
        "adlb_wq_add": (i32, [p, i64, i32, i32, i32, i32, i32, i64]),
        "adlb_wq_remove": (i32, [p, i64]),
        "adlb_wq_pin": (i32, [p, i64, i32]),
        "adlb_wq_unpin": (i32, [p, i64]),
        "adlb_wq_find_match": (i64, [p, i32, i32p, i32]),
        "adlb_wq_find_targeted": (i64, [p, i32, i32p, i32]),
        "adlb_wq_find_untargeted": (i64, [p, i32p, i32]),
        "adlb_wq_hi_prio_of_type": (i32, [p, i32, i32p]),
        "adlb_wq_count": (i64, [p]),
        "adlb_wq_max_count": (i64, [p]),
        "adlb_wq_total_bytes": (i64, [p]),
        "adlb_wq_num_unpinned": (i64, [p]),
        "adlb_wq_num_unpinned_untargeted": (i64, [p]),
        "adlb_wq_depth_sample": (None, [p, i64p]),
        "adlb_wq_snapshot_untargeted": (i64, [p, i64, i64p, i32p, i32p, i64p]),
        "adlb_wq_get": (i32, [p, i64, i32p, i32p, i32p, i32p, i64p]),
    }
    for name, (restype, argtypes) in sig.items():
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes
    # The O(1) getters are ALSO bound through a PyDLL view of the same
    # library: CDLL releases the GIL around every call, and on a loaded
    # host each re-acquire can stall the calling (reactor) thread for
    # up to a scheduler switch interval — milliseconds — which made the
    # periodic tick's depth gauges a measurable slice of tpu-mode pop
    # latency. PyDLL keeps the GIL held: correct for these functions
    # (no I/O, no blocking, nanoseconds of C) and ~1000x cheaper under
    # thread contention. Heavy calls (snapshot sorts, matching) stay on
    # the GIL-releasing CDLL where parallelism pays.
    fast = ctypes.PyDLL(lib._name)
    for name in (
        "adlb_wq_count", "adlb_wq_max_count", "adlb_wq_total_bytes",
        "adlb_wq_num_unpinned", "adlb_wq_num_unpinned_untargeted",
        "adlb_wq_depth_sample", "adlb_wq_hi_prio_of_type",
    ):
        restype, argtypes = sig[name]
        fn = getattr(fast, name)
        fn.restype = restype
        fn.argtypes = argtypes
    lib._fast = fast
    return lib


def ensure_built() -> Optional[ctypes.CDLL]:
    """Build if needed and load; returns None (and records why) on failure."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            return None
        try:
            path = build_artifact(
                "libadlbwq.so",
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                 "-o", "{out}", _WQ_SRC],
                [_WQ_SRC, _WQ_HDR],
            )
            _lib = _bind(ctypes.CDLL(path))
            return _lib
        except (OSError, BuildError) as e:
            _build_error = f"native core unavailable: {str(e)[:500]}"
            return None


def native_available() -> bool:
    return ensure_built() is not None


def build_error() -> Optional[str]:
    return _build_error


# ------------------------------------------------------------------ codec

_CODEC_SRC = os.path.join(_DIR, "codec.cpp")

_codec_lock = threading.Lock()
_codec_lib = None  # the _adlbcodec module object once loaded
_codec_error: Optional[str] = None


def _bind_codec(lib: ctypes.PyDLL):
    # PyDLL (the wqcore O(1)-getter discipline, GIL held throughout): the
    # ONE ctypes call asks the library for a fully-formed module object,
    # whose encode/decode are METH_FASTCALL builtins — per-frame calls
    # cost a builtin vector call, not a ctypes FFI marshal
    lib.adlb_codec_module.restype = ctypes.py_object
    lib.adlb_codec_module.argtypes = []
    return lib.adlb_codec_module()


def ensure_codec():
    """Build (if needed) and load the compiled TLV codec; returns the
    codec MODULE object, or None (recording why) when the toolchain or
    headers are unavailable."""
    global _codec_lib, _codec_error
    with _codec_lock:
        if _codec_lib is not None:
            return _codec_lib
        if _codec_error is not None:
            return None
        import sysconfig

        inc = sysconfig.get_paths()["include"]
        try:
            if not os.path.exists(os.path.join(inc, "Python.h")):
                raise OSError(f"Python.h not found under {inc}")
            path = build_artifact(
                "libadlbcodec.so",
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                 f"-I{inc}", "-o", "{out}", _CODEC_SRC],
                [_CODEC_SRC],
            )
            _codec_lib = _bind_codec(ctypes.PyDLL(path))
            return _codec_lib
        except (OSError, BuildError) as e:
            _codec_error = f"compiled codec unavailable: {str(e)[:500]}"
            return None


def codec_error() -> Optional[str]:
    return _codec_error


# ---------------------------------------------------------------- serverd

_SERVERD_SRC = os.path.join(_DIR, "serverd.cpp")


def ensure_serverd() -> str:
    """Build (if needed) and return the path of the native server daemon.

    Raises BuildError (a RuntimeError) when the toolchain is unavailable —
    callers asked for server_impl="native" explicitly, so there is no
    silent fallback.
    """
    return build_artifact(
        "adlb_serverd",
        ["g++", "-O2", "-std=c++17", "-o", "{out}",
         _SERVERD_SRC],
        [_SERVERD_SRC, _WQ_HDR, HOSTSOCK_HDR],
    )
