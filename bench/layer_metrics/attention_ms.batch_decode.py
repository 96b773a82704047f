"""Device time of attention (layer name attn/core: the reduce over the dense
int8 cache, its dequantisation and mask) per decode step."""

from bench import scope_time


def read(run):
    return scope_time.decode_ms(run, "attn/core")
