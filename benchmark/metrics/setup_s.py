"""Process start to the first timed call: JAX start-up, compiling or
loading every program of the cell's shapes, and one warm-up call."""


def read(run):
    return run.setup_s
