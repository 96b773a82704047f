"""Device time of the prefill executable per prefill step, from the trace."""

from bench import readers


def read(run):
    return readers.prefill_step_ms(run)
