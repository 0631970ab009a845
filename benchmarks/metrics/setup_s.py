"""Process start to window start: JAX start-up, program load or compile,
native build on a checkout's first run, world launch, warm phase."""


def read(run):
    return run["setup_s"]
