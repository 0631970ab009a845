"""Units whose work finished inside the window, over its seconds."""


def read(run):
    return run["window"].units_per_s
