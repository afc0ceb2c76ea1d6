"""Programs compiled or loaded from the persistent cache inside the
measured window (JAX's backend-compile event); every shape is warmed up
before it, so this reads 0."""


def read(run):
    return run.window_compiles
