"""Engine: programs compiled, or loaded from the persistent compilation
cache, between the window's start and its last completion
(``jax.monitoring`` events)."""


def read(run):
    return run.compiles
