"""Device time of attention (layer name attn/core: the flash-attention scan)
per prefill step."""

from bench import scope_time


def read(run):
    return scope_time.prefill_ms(run, "attn/core")
