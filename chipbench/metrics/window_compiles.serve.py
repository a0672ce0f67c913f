"""Compiles and compile-cache loads inside the window (should be 0)."""


def read(run):
    return float(run.window_compiles)
