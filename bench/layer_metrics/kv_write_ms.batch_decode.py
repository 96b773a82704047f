"""Device time of the cache write (layer name attn/kv_write: quantise the new
K/V, update the dense int8 cache) per decode step."""

from bench import scope_time


def read(run):
    return scope_time.decode_ms(run, "attn/kv_write")
