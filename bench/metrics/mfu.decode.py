"""Model FLOPs of a decode step over its device time at the peak bf16 rate (%)."""
from bench import measures


def read(ctx):
    return measures.mfu_decode(ctx)
