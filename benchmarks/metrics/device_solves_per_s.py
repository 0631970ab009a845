"""Device solves the planner made, over the world's seconds."""


def read(run):
    solves = run["facts"].get("device_solves")
    return solves / run["world_s"] if solves and run["world_s"] else None
