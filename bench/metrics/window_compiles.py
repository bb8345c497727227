"""Backend compile events (JAX's ``backend_compile_duration``, which also
fires when a program comes from the persistent cache) inside the window."""


def read(run):
    return run.window_compiles
