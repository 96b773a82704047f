"""Device time of the head (layer name lm_head: the tied bf16 embedding as
the output projection) per decode step."""

from bench import scope_time


def read(run):
    return scope_time.decode_ms(run, "lm_head")
