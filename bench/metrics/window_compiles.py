"""Programs compiled or loaded from the compile cache inside the window
(JAX monitoring events).  Set-up warms every shape, so this reads 0."""


def read(run):
    return run.window_compiles
