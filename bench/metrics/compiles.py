"""Programs traced or compiled during the serve's loop iterations, summed
over the engine's ``iteration`` records (count; set-up warms every
program, so a steady window reads 0)."""


def read(ctx):
    counts = [ev["compiles"] for _, ev in ctx.window.events
              if ev["event"] == "iteration"]
    return sum(counts) if counts else None
