"""Device time of the prefill executable's operations that carry no layer
name (those XLA inserts, such as loop-carry copies) per prefill step."""

from bench import scope_time


def read(run):
    return scope_time.prefill_ms(run, scope_time.UNSCOPED)
