"""Device time of the solve program's operations, per solve, from the
profiler's trace of a few seconds inside the window."""

from benchmarks.reduce import xplane

#: the program's name in the trace (``jit`` of ``pallas_greedy_assign`` or
#: of ``_greedy_assign``)
PROGRAM = "greedy_assign"


def read(run):
    if run.get("trace") is None:
        return None
    runs, seconds = xplane.program_runs(run["trace"], PROGRAM)
    return seconds / runs * 1e3 if runs else None
