"""The trace reduction, on hand-made events and on a trace recorded here
on the CPU at a tiny size."""
import pytest

from bench import trace
from bench.trace import Ev

DEV = "/device:TPU:0"
MM = "%nxfp_matmul.3 = f32[8,64]{1,0} custom-call(bf16[2,8,32]{2,1,0} %x)"


def op(name, start_ms, dur_ms, plane=DEV, **stats):
    return Ev(plane, "XLA Ops", name, start_ms * 1e-3, dur_ms * 1e-3, stats)


def module(name, start_ms, dur_ms, plane=DEV):
    return Ev(plane, "XLA Modules", name, start_ms * 1e-3, dur_ms * 1e-3)


def test_union_merges_overlaps_and_keeps_gaps():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)]
    assert trace.merge(iv) == [(0.0, 3.0), (5.0, 6.0)]
    assert trace.union_s(iv) == 4.0


def hand_events():
    # device 0: ops 0-4 ms, 3-6 ms (overlap), 10-12 ms; device 1: 0-2 ms
    return [
        op("fusion.1", 0, 4), op(MM, 3, 3),
        op("fusion.1", 10, 2),
        op("fusion.2", 0, 2, plane="/device:TPU:1"),
        module("jit__unknown(17)", 0, 6), module("jit__unknown(17)", 10, 2),
        module("jit__unknown(3)", 0, 2, plane="/device:TPU:1"),
        Ev("/host:CPU", "python", "_dispatch_chunk", 6.5e-3, 3e-3),
        Ev("/host:CPU", "python", "sleep", 6.2e-3, 0.5e-3),
    ]


def test_busy_is_the_union_averaged_over_devices():
    red = trace.reduce(hand_events(), window_s=0.020)
    # device 0 busy 6 + 2 = 8 ms, device 1 2 ms -> mean 5 ms
    assert red.n_devices == 2
    assert red.busy_s == pytest.approx(0.005)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.75)


def test_per_name_device_time_sums():
    red = trace.reduce(hand_events(), window_s=0.020)
    assert red.op_s["fusion.1"] == pytest.approx(0.006)
    assert red.op_s[MM] == pytest.approx(0.003)
    assert red.op_s["fusion.2"] == pytest.approx(0.002)
    # programs keep the fingerprint the trace gives them, so two programs
    # of one jitted name stay apart
    assert red.programs["jit__unknown(17)"] == pytest.approx((0.008, 2))
    assert red.programs["jit__unknown(3)"] == pytest.approx((0.002, 1))
    assert trace.longest_program(hand_events()) == "jit__unknown(17)"
    assert [e.name for e in red.calls("nxfp_matmul")] == [MM]
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]


def test_idle_gaps_are_named_by_the_host_work_that_covers_them():
    red = trace.reduce(hand_events(), window_s=0.020)
    # device 0 idles 6-10 ms; _dispatch_chunk covers 6.5-9.5 ms of it
    assert red.gaps[0][0] == "_dispatch_chunk"
    assert red.gaps[0][1] == pytest.approx(0.004)


def test_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([Ev("/host:CPU", "python", "x", 0, 1)], 1.0)


def test_reduces_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tiny_prog(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((128, 128))
    tiny_prog(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(4):
        tiny_prog(x).block_until_ready()
    jax.profiler.stop_trace()
    evs = trace.load(str(tmp_path))
    red = trace.reduce(evs, window_s=1.0)
    s, n = red.program("jit_tiny_prog")
    assert n == 4 and s > 0
    assert 0 < red.busy_s <= 1.0
    assert sum(red.op_s.values()) >= red.busy_s * 0.999


def test_leaves_drop_ops_that_enclose_others():
    outer = op("while.1", 0, 10)
    inner = [op("fusion.1", 1, 2), op("nxfp_matmul.2", 4, 3)]
    later = op("copy.3", 11, 1)
    got = {e.name for e in trace.leaves([outer, *inner, later])}
    assert got == {"fusion.1", "nxfp_matmul.2", "copy.3"}
    red = trace.reduce([outer, *inner, later], window_s=0.02)
    assert "while.1" not in red.op_s
    assert red.busy_s == pytest.approx(0.011)          # union keeps the loop


def test_only_events_inside_the_window_span_count():
    evs = hand_events() + [Ev("/host:CPU", "python", "win", 9e-3, 4e-3)]
    red = trace.reduce(evs, window_s=1.0, span="win")
    assert red.window_s == pytest.approx(0.004)
    assert red.busy_s == pytest.approx(0.002)          # fusion.1 at 10-12 ms


def test_kernel_calls_and_their_shapes_come_from_the_op_text():
    text = ("%nxfp_matmul.34 = f32[8,8192]{1,0:T(8,128)S(1)} custom-call("
            "bf16[2,8,11008]{2,1,0:T(8,128)(2,1)S(1)} %fusion.85, "
            "u8[1,11008,8192]{2,1,0} %b, u16[688,8192]{1,0} %m), "
            "custom_call_target=\"tpu_custom_call\"")
    use = "%fusion.9 = f32[8,8192] fusion(f32[8,8192] %nxfp_matmul.34)"
    red = trace.reduce([op(text, 0, 1), op(use, 1, 1)], window_s=1.0)
    calls = red.calls("nxfp_matmul")
    assert [e.name for e in calls] == [text]
    assert trace.matmul_shape(calls[0]) == (8, 22016, 8192)
    assert trace.matmul_shape(op(use, 0, 1)) is None
