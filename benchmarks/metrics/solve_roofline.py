"""The least time the chip needs for one solve (``roofline/greedy_sweep``)
over the time the solve program took on the device, in percent. Pairs per
solve are the planner's own count: ``balancer_pairs`` over its rounds."""

from benchmarks.metrics.solve_kernel_ms import read as solve_kernel_ms
from benchmarks.roofline import greedy_sweep


def read(run):
    kernel_ms = solve_kernel_ms(run)
    if not kernel_ms:
        return None
    config, facts = run["config"], run["facts"]
    nt, nr = config["solve_shape"]
    pairs = 0.0
    flight = run.get("flight")
    if flight and facts.get("device_solves"):
        pairs = flight["metrics"]["counters"].get("balancer_pairs", 0) \
            / facts["device_solves"]
    least_s, _bound = greedy_sweep.least_seconds(
        nt, nr, len(config["types"]), pairs, run["device"]["kind"])
    return 100.0 * least_s / (kernel_ms * 1e-3)
