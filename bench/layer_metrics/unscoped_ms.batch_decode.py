"""Device time of the decode executable's operations that carry no layer
name (those XLA inserts, such as loop-carry copies) per decode step."""

from bench import scope_time


def read(run):
    return scope_time.decode_ms(run, scope_time.UNSCOPED)
