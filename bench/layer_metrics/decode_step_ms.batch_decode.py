"""Device time of the decode executable per decode step, from the trace."""

from bench import readers


def read(run):
    return readers.decode_step_ms(run)
