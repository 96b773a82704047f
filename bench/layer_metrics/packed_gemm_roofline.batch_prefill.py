"""The packed linears' roofline time (bench/work/packed_gemm.py) over the
device time of their kernel's events."""

from bench import readers


def read(run):
    return readers.packed_gemm_roofline(run)
