"""Median planning round of the planner host, from ``balancer_round_s``
in the sidecar's flight artefact (log buckets, interpolated)."""


def read(run):
    flight = run.get("flight")
    if not flight:
        return None
    hist = flight["metrics"]["histograms"].get("balancer_round_s")
    if not hist or not hist["count"]:
        return None
    half, seen = hist["count"] / 2.0, 0
    bounds, counts = hist["bounds"], hist["counts"]
    for i, c in enumerate(counts):
        if c and seen + c >= half:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            return (lo + (hi - lo) * (half - seen) / c) * 1e3
        seen += c
    return None
