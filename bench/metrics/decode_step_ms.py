"""Device time of the decode-chunk program per decode step (ms), traced."""
from bench import measures


def read(ctx):
    return measures.decode_step_ms(ctx)
