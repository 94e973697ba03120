"""Device time of the decode-chunk program per decode step (ms), traced, in the offline cells."""
from bench import measures


def read(ctx):
    return measures.decode_step_ms(ctx)
