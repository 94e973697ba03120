"""Arithmetic the per-layer metric readers share (``bench/metrics``).

The engine's programs are found in the trace by the names that
``run.probe_programs`` read in set-up from a traced call of each alone
(``ctx.programs``: "decode" and "lane" to their trace names), since JAX
names them by fingerprint.
"""
from __future__ import annotations

import statistics

from bench.counts import matmul_call, model_flops_per_token, roofline_share
from bench.trace import matmul_shape

DECODE = "decode"
LANE = "lane"


def program(ctx, which: str):
    """(device seconds per device, runs) of the engine's ``which``
    programs ("decode" or "lane") in the traced window."""
    if ctx.trace is None:
        return 0.0, 0
    got = [ctx.trace.program(n) for n in ctx.programs.get(which, [])]
    return sum(g[0] for g in got), sum(g[1] for g in got)


def decode_step_ms(ctx):
    if ctx.trace is None:
        return None
    s, n = program(ctx, DECODE)
    if n == 0:
        return None
    return 1e3 * s / (n * ctx.traffic["engine"]["chunk"])


def lane_chunk_ms(ctx):
    if ctx.trace is None:
        return None
    s, n = program(ctx, LANE)
    return 1e3 * s / n if n else None


def in_span(ctx):
    """Decode chunks (samples) that ended inside the traced window."""
    t0, t1 = ctx.trace_span()
    return ctx.window.chunks_in(t0, t1)


def mean_context(ctx, t: float) -> float:
    """Mean context of the requests decoding at harness time ``t``: each
    one's prompt plus the tokens it has had since its first token."""
    w = ctx.window
    first, fin = w.times("prefill-done"), w.finishes()
    chunk = ctx.traffic["engine"]["chunk"]
    lens = []
    for u, t_first in first.items():
        if t_first <= t and fin.get(u, (float("inf"),))[0] > t:
            steps = sum(1 for s in w.samples if t_first < s[0] <= t)
            lens.append(ctx.prompt_len[u] + chunk * steps)
    return statistics.fmean(lens) if lens else 0.0


def mfu_decode(ctx):
    """Model FLOPs of one decode step of the slots that decoded, over the
    decode step's device time at the chip's peak bf16 rate."""
    step = decode_step_ms(ctx)
    chunks = in_span(ctx) if ctx.trace is not None else []
    if step is None or not chunks:
        return None
    t0, t1 = ctx.trace_span()
    slots = statistics.fmean(c[2] for c in chunks)
    flops = slots * model_flops_per_token(ctx.model,
                                          mean_context(ctx, (t0 + t1) / 2))
    return 100.0 * flops / (step * 1e-3 * ctx.peaks["bf16_flops"])


def mfu_offline(ctx):
    """Model FLOPs of every token the traced window processed -- the
    decoded ones and the prompt tokens the lane took in -- over the
    window at the chip's peak bf16 rate."""
    if ctx.trace is None:
        return None
    w, m = ctx.window, ctx.model
    t0, t1 = ctx.trace_span()
    ctxlen = mean_context(ctx, (t0 + t1) / 2)
    decoded = w.tokens_at(t1) - w.tokens_at(t0)
    flops = decoded * model_flops_per_token(m, ctxlen)
    _, lane_runs = program(ctx, LANE)
    p = ctx.traffic["engine"]["p_chunk"]
    finals = [u for u, t in w.times("prefill-done").items() if t0 < t <= t1]
    tail = [ctx.prompt_len[u] % p or p for u in finals]
    prompt_tokens = max(lane_runs - len(finals), 0) * p + sum(tail)
    mean_pos = statistics.fmean(ctx.prompt_len.values()) / 2
    flops += prompt_tokens * model_flops_per_token(m, mean_pos, head=False)
    flops += len(finals) * 2 * m["d_model"] * m["vocab"]
    if flops == 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks["bf16_flops"])


def matmul_roofline(ctx):
    """Share of the roofline over every ``nxfp_matmul`` call traced, each
    call's work counted from its operand shapes (``trace.matmul_shape``)."""
    if ctx.trace is None:
        return None
    calls = ctx.trace.calls("nxfp_matmul")
    flops = nbytes = secs = 0.0
    for e in calls:
        shape = matmul_shape(e)
        if shape is None:
            continue
        f, b = matmul_call(*shape)
        flops, nbytes, secs = flops + f, nbytes + b, secs + e.dur
    if secs == 0:
        return None
    return roofline_share(flops, nbytes, secs, ctx.peaks)[0]

