"""Device time of one prefill-lane chunk program (ms), traced."""
from bench import measures


def read(ctx):
    return measures.lane_chunk_ms(ctx)
