"""Roofline share of the decode program's nxfp_decode_attention calls
traced (%).  The work comes from the engine's ``iteration`` records of
the decode chunks inside the traced span: ``rows`` valid K/V rows read and
``live x steps`` queries, in each of the model's layers; the time is the
device time of the kernel's calls (only the decode program calls it)."""
from bench.counts import decode_attention_call, roofline_share


def records(ctx):
    """The ``iteration`` records of the decode chunks traced.  The span
    opens in one chunk's ``progress_cb`` and closes in the first one at
    least ``length_s`` later (``trace.Capture``); each chunk's record comes
    just before its callback, so the records after the opening callback up
    to the closing one cover the chunks whose kernel calls the span holds.
    The close is the closing callback's time (a window sample), not the
    span's end on the profiler's clock: a record stamped microseconds
    before that callback can fall after it."""
    t0, _ = ctx.trace_span()
    length = ctx.traffic["trace"]["length_s"]
    close = next((s[0] for s in ctx.window.samples if s[0] >= t0 + length),
                 float("inf"))      # else the span closed after the serve
    return [ev for t, ev in ctx.window.events
            if ev["event"] == "iteration" and ev.get("steps")
            and t0 < t <= close]


def read(ctx):
    if ctx.trace is None:
        return None
    recs = records(ctx)
    secs = sum(e.dur for e in ctx.trace.calls("nxfp_decode_attention"))
    if not recs or secs == 0:
        return None
    m = ctx.model
    rows = sum(r["rows"] for r in recs)
    queries = sum(r["live"] * r["steps"] for r in recs)
    # one entry per query, its valid rows: only their sum and count enter
    flops, nbytes = decode_attention_call(
        [rows] + [0] * (queries - 1), m["n_heads"], m["n_kv_heads"],
        m.get("head_dim") or m["d_model"] // m["n_heads"])
    layers = m["n_layers"]
    return roofline_share(layers * flops, layers * nbytes, secs,
                          ctx.peaks)[0]
