"""Mean over the window's decode chunks of the slots that decoded in each,
as a share of the engine's slots (%).  In a traced run, over the chunks
before the profiler started (its stop holds the serve loop)."""
import statistics


def read(ctx):
    w = ctx.window
    chunks = w.chunks_in(w.start, ctx.untraced_until())
    if not chunks:
        return None
    return 100.0 * statistics.fmean(c[2] for c in chunks) / w.n_slots
