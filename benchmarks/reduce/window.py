"""From the clients' logs to the end-to-end metrics of one window.

The window is ``[t_end - seconds, t_end]`` with ``t_end`` as the units
carry it (the producer fixed it at its first put). Every metric is taken
over all the work and all the time of the window; nothing is trimmed.
"""

from __future__ import annotations

import numpy as np


class Window:
    """The reduced window. Arrays are kept for the per-layer readers."""

    def __init__(self, logs, seconds: float, workers: int, nservers: int,
                 needs_backlog: bool, producer_rank: int = 0):
        if logs.producer is None:
            raise ValueError("no producer record: the window has no t_end")
        self.seconds = float(seconds)
        self.workers = int(workers)
        self.t_end = float(logs.producer["t_end"])
        self.t0 = self.t_end - self.seconds
        u, f = logs.units, logs.fetches
        t0, t1 = self.t0, self.t_end

        done_in = (u["t_done"] >= t0) & (u["t_done"] <= t1)
        self.units_done = int(done_in.sum())
        self.units_per_s = self.units_done / self.seconds
        # units done in each whole second of the window: shows a ramp or a
        # stall that the one rate averages over
        self.per_second = np.bincount(
            np.clip((u["t_done"][done_in] - t0).astype(np.int64), 0,
                    max(int(np.ceil(self.seconds)) - 1, 0)),
            minlength=int(np.ceil(self.seconds))).tolist()

        got = f["rc"] == 1  # ADLB_SUCCESS
        clipped = np.clip(f["t_ret"], t0, t1) - np.clip(f["t_call"], t0, t1)
        self.blocked_s = float(clipped.sum())
        self.worker_blocked_pct = 100.0 * self.blocked_s / (
            self.workers * self.seconds)

        deliv_in = (u["t_ret"] >= t0) & (u["t_ret"] <= t1)
        self.units_delivered = int(deliv_in.sum())
        wait = u["t_ret"] - np.maximum(u["t_put"], u["t_call"])
        self.match_wait_s = wait[deliv_in]
        self.match_wait_p95_ms = (
            float(np.percentile(self.match_wait_s, 95)) * 1e3
            if self.units_delivered else None)

        f_in = got & (f["t_ret"] >= t0) & (f["t_ret"] <= t1)
        self.fetch_calls = int(f_in.sum())
        self.fetch_s = (f["t_ret"] - f["t_call"])[f_in]

        # steadiness: was the planner warm, did the backlog last
        remote = (logs.unit_rank % nservers) != (producer_rank % nservers)
        self.first_remote = float(u["t_ret"][remote].min()) \
            if remote.any() else None
        self.least_backlog = self._least_backlog(u, t0, t1)
        p = logs.producer
        put_s = float(p["t_last"] - p["t_first"])
        self.put_rate = float(p["n_acked"]) / put_s if put_s > 0 else None
        self.put_times = logs.put_s  # a synchronous producer's, else empty
        self.flags = []
        if self.first_remote is None or self.first_remote > t0:
            self.flags.append("planner-not-warm")
        if needs_backlog and self.least_backlog <= 0:
            self.flags.append("backlog-empty")

    @staticmethod
    def _least_backlog(u, t0: float, t1: float) -> int:
        """min over the window of (puts so far - deliveries so far)."""
        times = np.concatenate([u["t_put"], u["t_ret"]])
        step = np.concatenate([np.ones(len(u), dtype=np.int64),
                               -np.ones(len(u), dtype=np.int64)])
        # a put and its own delivery never tie; among equal times puts
        # first, so the backlog is never read lower than it was
        order = np.lexsort((-step, times))
        times, level = times[order], np.cumsum(step[order])
        before = int(np.searchsorted(times, t0, side="right"))
        upto = int(np.searchsorted(times, t1, side="right"))
        start = int(level[before - 1]) if before > 0 else 0
        inside = level[before:upto]
        return int(min(start, inside.min())) if inside.size else start

    @property
    def unsteady(self) -> bool:
        return bool(self.flags)

    def describe(self) -> str:
        fr = "none" if self.first_remote is None else \
            f"{self.first_remote - self.t0:+.3f}s from window start"
        rate = "n/a" if self.put_rate is None else f"{self.put_rate:.0f}/s"
        if len(self.put_times):
            q = np.percentile(self.put_times, [50, 99, 100]) * 1e3
            mean = float(self.put_times.mean())
            rate += (f" (a put took p50 {q[0]:.4f} p99 {q[1]:.3f} max "
                     f"{q[2]:.1f} ms, mean {mean * 1e3:.4f} ms = "
                     f"{1.0 / mean:.0f}/s)")
        return (f"window: {self.seconds:g}s, {self.units_done} units done, "
                f"first remote delivery {fr}, least backlog "
                f"{self.least_backlog}, producer put rate {rate}, "
                f"unsteady={self.flags or False}; units done per second "
                f"{self.per_second}")
