"""Backend compiles (``jax.monitoring`` events) inside the window; warm-up
is meant to leave none."""


def read(run):
    return run.compiles_in_window
