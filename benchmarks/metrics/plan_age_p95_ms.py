"""p95 of ``balancer_plan_age_s`` in the sidecar's flight artefact: the
age of the oldest snapshot a plan was computed from, when it was handed to
the transport. Over the whole world (warm phase and drain included), not
the traced window: the histogram has no clock. Log buckets, interpolated
as ``metrics/plan_round_ms.py`` does."""

Q = 0.95


def read(run):
    flight = run.get("flight")
    if not flight:
        return None
    hist = flight["metrics"]["histograms"].get("balancer_plan_age_s")
    if not hist or not hist["count"]:
        return None
    target, seen = Q * hist["count"], 0
    bounds, counts = hist["bounds"], hist["counts"]
    for i, c in enumerate(counts):
        if c and seen + c >= target:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            return (lo + (hi - lo) * (target - seen) / c) * 1e3
        seen += c
    return None
