"""Share of the device's busy time spent in prefill-lane chunks (%)."""
from bench import measures


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    s, n = measures.program(ctx, measures.LANE)
    return 100.0 * s / ctx.trace.busy_s if n else None
