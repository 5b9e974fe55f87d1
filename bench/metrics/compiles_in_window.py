"""XLA programs compiled or loaded while the window ran (the
``jax.monitoring`` compile events): every shape should be warm."""


def read(run):
    return run.compiles_in_window
