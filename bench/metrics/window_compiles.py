"""Compiles and persistent-cache loads inside the measured window,
counted from JAX's monitoring events (``benchlib/compiles.py``)."""


def read(run):
    return run.window_compiles
