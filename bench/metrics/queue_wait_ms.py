"""Median over the requests sent in the window of the time from each one's
due time to its admission (the engine's ``prefill-start`` event), on the
harness clock (ms).  In a traced run, over the requests due before the
profiler started (its stop holds the serve loop)."""
import statistics


def read(ctx):
    w = ctx.window
    start = w.times("prefill-start")
    until = ctx.untraced_until()
    waits = [start[u] - d for u, d in w.due.items()
             if u in start and w.start <= d < until]
    return 1e3 * statistics.median(waits) if waits else None
