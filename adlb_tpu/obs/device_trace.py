"""A device trace on request, made by the process that owns the chip.

Only the process that holds a chip can trace it, and under
``spawn_world`` with Python servers that process is the forked master
rank: no caller can wrap it in ``jax.profiler``. So the master's ops
endpoint (``Config(ops_port=...)``) takes the request —
``POST /device_trace?seconds=<s>&dir=<path>`` — and runs one
``jax.profiler`` session on the request's own thread, in the master's
process, while the world goes on. The planner's ``adlb.*`` spans
(``runtime/trace.py``) land in the same ``.xplane.pb`` as the device
planes, on their clock.

JAX is never started from here. A request that arrives before the
planner's first device program waits for it and gives up after a
minute; a planner whose every solve runs the numpy twin holds no device,
and the request says so. One
session at a time: a second request during one is refused. A world that
ends mid-session stops it and waits for the file (``close``).
"""

from __future__ import annotations

import threading
import time
from typing import Callable


class TraceRefused(Exception):
    """The request cannot be served now; ``status`` is the HTTP answer."""

    def __init__(self, status: int, why: str) -> None:
        super().__init__(why)
        self.status = status


class DeviceTracer:
    def __init__(self, device_ready: Callable[[], bool],
                 ready_wait: float = 60.0) -> None:
        # whether the planner has run a device program in this process,
        # and how long a request waits for that
        self._device_ready = device_ready
        self._ready_wait = ready_wait
        self._session = threading.Lock()
        self._stop = threading.Event()

    def trace(self, seconds: float, out_dir: str) -> dict:
        """Trace for ``seconds`` into ``out_dir``, on the caller's thread.
        Returns where the session began and ended on CLOCK_MONOTONIC
        (``time.monotonic``), taken just inside ``start_trace`` and
        ``stop_trace``."""
        if not 0 < seconds <= 600:
            raise ValueError(f"seconds={seconds!r}: 0 < seconds <= 600")
        if not self._session.acquire(blocking=False):
            raise TraceRefused(409, "a device trace is already running")
        try:
            give_up = time.monotonic() + self._ready_wait
            while not self._stop.is_set() and not self._device_ready():
                if time.monotonic() >= give_up:
                    raise TraceRefused(
                        503, f"the planner ran no device program within "
                             f"{self._ready_wait:g}s: this process holds "
                             f"no device")
                self._stop.wait(0.05)
            if self._stop.is_set():
                raise TraceRefused(503, "the world is ending")
            import jax  # loaded by the planner's solve long since

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(out_dir, profiler_options=options)
            began = time.monotonic()
            try:
                cut_short = self._stop.wait(seconds)
            finally:
                ended = time.monotonic()
                jax.profiler.stop_trace()
            return {"dir": out_dir, "began": began, "ended": ended,
                    "seconds": ended - began, "cut_short": cut_short}
        finally:
            self._session.release()

    def close(self, timeout: float = 60.0) -> None:
        """The world is ending: stop a running session and wait until
        its file is written; refuse every later request."""
        self._stop.set()
        if self._session.acquire(timeout=timeout):
            self._session.release()
