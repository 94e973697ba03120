"""Model FLOPs of all tokens the traced window processed over the window at the peak bf16 rate (%)."""
from bench import measures


def read(ctx):
    return measures.mfu_offline(ctx)
