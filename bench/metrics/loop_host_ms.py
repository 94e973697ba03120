"""Median over the decode iterations of the serve loop before the
profiler started of the host time each spent in its lifecycle, lane,
upload, dispatch and harvest phases, from the engine's ``iteration``
records; the waits on the device and the sleep to the next arrival are
left out (ms)."""
import statistics

HOST = ("lifecycle_ms", "lane_ms", "upload_ms", "dispatch_ms",
        "harvest_ms")


def read(ctx):
    w = ctx.window
    until = ctx.untraced_until()
    host = [sum(ev[k] for k in HOST) for t, ev in w.events
            if ev["event"] == "iteration" and ev.get("steps")
            and w.start <= t < until]
    return statistics.median(host) if host else None
