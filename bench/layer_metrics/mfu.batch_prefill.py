"""Model operations of the traced window's tokens over the window and the
bf16 peak (the configuration's ``work`` module)."""

from bench import readers


def read(run):
    return readers.mfu(run)
