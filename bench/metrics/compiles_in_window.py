"""Executor / JAX: programs JAX compiled or loaded from its cache inside
the measured window (its backend-compile events). Every shape is warmed
up before the window, so a sound run reads 0."""


def read(run):
    return run.compiles_in_window
