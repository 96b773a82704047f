"""Model operations of the traced window's tokens over the window and the
bf16 peak (bench/work/model_flops.py)."""

from bench import readers


def read(run):
    return readers.mfu(run)
