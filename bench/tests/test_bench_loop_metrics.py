"""The readers of the engine's ``iteration`` records, on a hand-made
context: the decode attention roofline, the serve loop's host time and
the compile count."""
import pytest

from bench import run, spec
from bench.counts import NXFP4
from bench.serve import Window
from bench.trace import Ev, reduce

CHAT = spec.load_cell("danube3-4b.chat")
DOCS = spec.load_cell("deepseek-67b-s8.docs")
PHASES = ("lifecycle", "lane", "lane_wait", "upload", "dispatch", "wait",
          "harvest", "sleep")
ATTN = ("%nxfp_decode_attention.11 = f32[32,8,2,4,64]{4,3,2,1,0} "
        "custom-call(f32[32,8,4,128] %q)")
STARTED, ON, SPAN_S = 40.0, 41.0, 6.0       # profiler on; traced span
CLOSE = ON + SPAN_S + 5e-5                  # the closing chunk's callback


class Capture:
    """What a ``Context`` reads of ``trace.Capture``."""
    t_started, t_on_window = STARTED, ON


def rec(live=6, steps=4, rows=7000, compiles=0, **ms):
    return {"event": "iteration", "i": 0, "live": live, "steps": steps,
            "rows": rows, "lane_tokens": 0, "compiles": compiles,
            **{f"{p}_ms": float(ms.get(p, 0.0)) for p in PHASES}}


def context(cell, events, attn_ms=(1.0, 1.0)):
    """A traced run's context: ``events`` (harness time, record) in the
    window, and kernel calls of ``attn_ms`` each in the traced span, which
    opens in the decode chunk at ``ON`` and closes in the first one at
    least ``SPAN_S`` later, at ``CLOSE``."""
    ops = [Ev("/device:TPU:0", "XLA Ops", ATTN, 0.01 * k, 1e-3 * d)
           for k, d in enumerate(attn_ms)]
    ops.append(Ev("/device:TPU:0", "XLA Ops", "fusion.1", 1.0, 0.5))
    red = reduce(ops, window_s=SPAN_S)
    chunks = [(t, 0, 0) for t in (ON - 2, ON, ON + 2.5, CLOSE, CLOSE + 30)]
    w = Window(seconds=51.0, end=51.0, events=events, n_slots=32,
               samples=chunks)
    return run.Context(cell, w, red, Capture(), "TPU v5 lite", 0, {})


def reader(name):
    return CHAT.reader(name)


def hand_share(m, rows, queries, secs):
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    d = -(-hd // 32) * 32
    bpv = NXFP4["bits"] / 8 + NXFP4["meta_bytes"] / NXFP4["block_size"]
    flops = m["n_layers"] * 4 * rows * m["n_heads"] * hd
    nbytes = m["n_layers"] * (2 * rows * m["n_kv_heads"] * d * bpv
                              + 2 * queries * m["n_heads"] * d * 4)
    return 100 * max(flops / 197e12, nbytes / 819e9) / secs


@pytest.mark.parametrize("cell,name", [
    (CHAT, "nxfp_attention_roofline"),
    (DOCS, "nxfp_attention_roofline.offline")])
def test_attention_roofline_is_the_hand_arithmetic_inside_the_span(
        cell, name):
    events = [
        (ON - 0.1, rec(rows=99999)),            # before the span opened
        (ON + 0.5, rec(live=6, steps=4, rows=7000)),
        (ON + 1.0, rec(live=0, steps=0, rows=0)),   # a lane-only pass
        (ON + 2.4, rec(live=5, steps=4, rows=6100)),
        # the closing chunk's record, stamped after the span's end on the
        # profiler's clock but before the callback that closed it
        (CLOSE - 1e-5, rec(live=2, steps=4, rows=1000)),
        (CLOSE + 29, rec(rows=99999)),          # after it closed
        (ON + 0.7, {"event": "finish", "uid": 3}),
    ]
    got = cell.reader(name)(context(cell, events, attn_ms=(1.5, 2.5)))
    want = hand_share(cell.config["model"], 14100, 52, 4e-3)
    assert got == pytest.approx(want, rel=1e-12)
    # danube: 24 layers x 14100 rows x 8 KV heads x 128 x 4.5 bits, twice
    if cell is CHAT:
        assert got == pytest.approx(
            100 * 24 * (2 * 14100 * 8 * 128 * 0.5625
                        + 2 * 52 * 32 * 128 * 4) / 819e9 / 4e-3)


def test_loop_host_time_reads_only_iterations_before_the_profiler():
    busy = dict(wait=300.0, lane_wait=50.0, sleep=20.0)   # not host time
    events = [
        (10.0, rec(lifecycle=1, lane=2, upload=1, dispatch=0.5,
                   harvest=0.5, **busy)),               # 5 ms
        (15.0, rec(steps=0, lifecycle=40)),             # no decode chunk
        (20.0, rec(lifecycle=1, upload=2, dispatch=2, harvest=2,
                   **busy)),                            # 7 ms
        (30.0, rec(lane=6, harvest=3, **busy)),         # 9 ms
        (STARTED + 0.5, rec(lifecycle=100)),            # profiler running
    ]
    got = reader("loop_host_ms")(context(CHAT, events))
    assert got == pytest.approx(7.0)


def test_compiles_sum_over_the_serve():
    events = [(1.0, rec(compiles=2)), (20.0, rec(steps=0, compiles=1)),
              (45.0, rec(compiles=0)), (50.0, {"event": "admit", "uid": 1})]
    assert reader("compiles")(context(CHAT, events)) == 3
    quiet = [(t, rec(compiles=0)) for t in (1.0, 2.0)]
    assert reader("compiles")(context(CHAT, quiet)) == 0


@pytest.mark.parametrize("name", [
    "nxfp_attention_roofline", "nxfp_attention_roofline.offline",
    "loop_host_ms", "compiles"])
def test_no_records_read_none(name):
    # a program without iteration records (one before they existed)
    events = [(1.0, {"event": "prefill-start", "uid": 0}),
              (ON + 1.0, {"event": "finish", "uid": 0})]
    assert reader(name)(context(CHAT, events)) is None
