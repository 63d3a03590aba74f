"""Compile: programs compiled, or loaded from the persistent cache,
inside the measured window (jax's
``/jax/core/compile/backend_compile_duration`` events)."""


def read(run):
    return run.compiles
