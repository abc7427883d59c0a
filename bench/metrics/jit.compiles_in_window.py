"""Executables produced inside the measured window (``jax.monitoring``
backend-compile events, persistent-cache hits included); should be 0."""


def read(run):
    return run.compiles_in_window
