"""Units delivered per fetch call in the window: the batching realised."""


def read(run):
    w = run["window"]
    return w.units_delivered / w.fetch_calls if w.fetch_calls else None
