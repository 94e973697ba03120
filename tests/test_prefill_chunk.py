"""Chunked-prefill lane: bit-equality oracle + ragged-scatter units.

The ISSUE-4 acceptance gate: a prompt split across fixed-shape
``prefill_chunk`` dispatches — including a padded, non-divisor final
chunk — must leave the engine in a state that generates tokens
IDENTICAL to the monolithic ``prefill_mode="whole"`` path (itself
oracle-tested against solo host-loop serving), for dense AND
NxFP-packed KV, across the dense / SWA / hybrid / ssm families.
Admission-policy selection logic rides along.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.qtensor import QuantPolicy
from repro.models import init_cache, init_lane, init_params, prefill, \
    prefill_chunk
from repro.models.kvcache import attn_cache_init, write_prefill_at
from repro.serving import (ContinuousEngine, FifoPolicy, Request,
                           ServeEngine, ShortestPromptFirst, SlotScheduler,
                           TtftDeadline)


def _params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _prompt(cfg, t, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (t,)).astype(np.int32)


# ---------------------------------------------------------------------------
# prefill_chunk unit: logits bit-identical to the whole-prompt prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,fmt,p_chunk,t", [
    ("llama3_8b", None, 4, 11),          # dense KV, ragged final chunk
    ("llama3_8b", "nxfp4", 16, 24),      # packed KV, chunk-divisible
    ("llama3_8b", "nxfp4", 16, 17),      # packed KV, non-divisor prompt
    ("h2o_danube_3_4b", "nxfp4", 16, 40),   # SWA: prompt wraps the ring
    ("hymba_1_5b", "nxfp4", 16, 24),     # hybrid: SSM carry + SWA ring
    ("falcon_mamba_7b", None, 16, 17),   # pure recurrent, ragged chunk
])
def test_prefill_chunk_logits_match_whole(arch, fmt, p_chunk, t):
    """The lane's final-chunk logits ARE the whole-prompt prefill logits
    (bitwise), and the slot's cache rows match wherever the whole path
    defines them (rows past the prompt are never read — stale vs zero)."""
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    max_len = 64
    toks = _prompt(cfg, t)

    want, _ = jax.jit(lambda p, b: prefill(
        cfg, p, b, max_len=max_len, kv_fmt=fmt))(
            params, {"tokens": toks[None]})

    cache = init_cache(cfg, 2, max_len, fmt)
    lane = init_lane(cfg, max_len, p_chunk)
    fn = jax.jit(lambda p, tk, c, ln, s, o, n: prefill_chunk(
        cfg, p, tk, c, s, o, n, ln, fmt))
    logits = None
    for off in range(0, t, p_chunk):
        n_valid = min(p_chunk, t - off)
        chunk = np.zeros((1, p_chunk), np.int32)
        chunk[0, :n_valid] = toks[off:off + n_valid]
        logits, cache, lane = fn(params, chunk, cache, lane,
                                 jnp.int32(1), jnp.int32(off),
                                 jnp.int32(n_valid))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))


def test_prefill_chunk_distinct_lengths_share_one_program():
    """The whole point of the fixed (1, P) shape: serving a NEW prompt
    length must not trace (or compile) another lane program."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    traces = [0]

    def counted(p, tk, c, ln, s, o, n):
        traces[0] += 1
        return prefill_chunk(cfg, p, tk, c, s, o, n, ln, None)

    fn = jax.jit(counted)
    lane = init_lane(cfg, 64, 8)
    for t in (5, 8, 11, 19):
        cache = init_cache(cfg, 2, 64, None)
        for off in range(0, t, 8):
            n_valid = min(8, t - off)
            chunk = np.zeros((1, 8), np.int32)
            chunk[0, :n_valid] = _prompt(cfg, t)[off:off + n_valid]
            _, cache, lane = fn(params, chunk, cache, lane, jnp.int32(0),
                                jnp.int32(off), jnp.int32(n_valid))
    assert traces[0] == 1, f"lane retraced {traces[0]}x across lengths"


# ---------------------------------------------------------------------------
# engine-level: chunked admission == whole admission == solo host loop
# ---------------------------------------------------------------------------

def _solo(cfg, params, policy, req):
    eng = ServeEngine(cfg, params, policy, max_len=64, rng_seed=req.seed)
    return eng.generate({"tokens": req.tokens[None]}, max_new=req.max_new,
                        temperature=req.temperature,
                        stop_token=req.stop_token, loop="host")


@pytest.mark.parametrize("arch,fmt,p_chunk", [
    ("llama3_8b", None, 4),
    ("llama3_8b", "nxfp4", 16),
    ("h2o_danube_3_4b", "nxfp4", 16),    # SWA ring + chunked admission
    ("hymba_1_5b", "nxfp4", 16),         # hybrid
    ("falcon_mamba_7b", None, 16),       # attention-free
])
def test_chunked_admission_matches_solo(arch, fmt, p_chunk):
    """Greedy bit-equality through the FULL chunked lane: mixed prompt
    lengths (divisible and not, spanning 1..3 chunks, one wrapping the
    SWA ring where there is one) admitted into live decode traffic."""
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=fmt, kv_fmt=fmt)
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=4, prefill_mode="chunked", p_chunk=p_chunk)
    lens = [8, 3 * p_chunk - 7, 8, 2 * p_chunk, p_chunk + 1]
    reqs = [Request(uid=i, tokens=_prompt(cfg, t, seed=i), max_new=m)
            for i, (t, m) in enumerate(zip(lens, [5, 11, 3, 8, 6]))]
    results = eng.serve(reqs)
    assert sorted(r.uid for r in results) == list(range(5))
    for r in results:
        req = reqs[r.uid]
        solo = _solo(cfg, params, policy, req)
        assert r.n_generated == req.max_new
        np.testing.assert_array_equal(r.tokens, solo.tokens[0],
                                      err_msg=f"uid={r.uid}")


def test_chunked_admission_seeded_sampling_and_stop():
    """The lane's first-token sample walks the request's own key chain
    (same as monolithic admission), and stop tokens still terminate."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=None, kv_fmt=None)
    probe = _solo(cfg, params, policy,
                  Request(uid=0, tokens=_prompt(cfg, 11), max_new=9))
    stop = int(probe.tokens[0, 3])
    reqs = [
        Request(uid=0, tokens=_prompt(cfg, 11), max_new=9, stop_token=stop),
        Request(uid=1, tokens=_prompt(cfg, 18, seed=5), max_new=7,
                temperature=1.3, seed=17),
    ]
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=4, prefill_mode="chunked", p_chunk=8)
    results = {r.uid: r for r in eng.serve(reqs)}
    for uid, req in enumerate(reqs):
        solo = _solo(cfg, params, policy, req)
        n = int(solo.n_generated[0])
        assert results[uid].n_generated == n
        np.testing.assert_array_equal(results[uid].tokens,
                                      solo.tokens[0, :n])
    assert results[0].tokens[-1] == stop


def test_chunked_rejects_prompt_beyond_lane_scratch():
    """The lane scratch only rings when it covers a full SWA window plus
    an incoming chunk (``_lane_ring``); below that, a prompt past the
    scratch must still fail loudly instead of clamp-writing over rows the
    next chunk attends.  A scratch that DOES clear the bound admits the
    same prompt by wrapping (bit-equality vs whole is asserted in
    tests/test_paged.py::test_paged_ring_lane_admits_swa_prompt_past_max_len)."""
    cfg = get_smoke_config("h2o_danube_3_4b")       # sliding_window=32
    policy = QuantPolicy(weight_fmt=None, kv_fmt=None)
    # 32-row scratch < window (32) + p_chunk (32): ring OFF, loud reject
    eng = ContinuousEngine(cfg, _params(cfg), policy,
                           n_slots=2, max_len=32, chunk=4,
                           prefill_mode="chunked", p_chunk=32)
    assert not eng._lane_ring
    bad = Request(uid=0, tokens=np.zeros((100,), np.int32), max_new=4)
    with pytest.raises(ValueError, match="lane scratch"):
        eng.serve([bad])
    # 64-row scratch >= 32 + 32: ring ON, the same prompt is admitted
    eng = ContinuousEngine(cfg, _params(cfg), policy,
                           n_slots=2, max_len=64, chunk=4,
                           prefill_mode="chunked", p_chunk=32)
    assert eng._lane_ring
    eng._check_request(bad)                         # no raise


def test_chunked_rejects_bad_chunk_sizes():
    """Config guards fail loudly: a lane chunk bigger than the SWA ring
    would collide in-chunk rows; one misaligned with ssm_chunk would
    break the associative-scan grouping the oracle depends on."""
    policy = QuantPolicy(weight_fmt=None, kv_fmt=None)
    cfg = get_smoke_config("h2o_danube_3_4b")       # sliding_window=32
    with pytest.raises(ValueError, match="sliding_window"):
        ContinuousEngine(cfg, _params(cfg), policy, n_slots=2, max_len=64,
                         prefill_mode="chunked", p_chunk=64)
    cfg = get_smoke_config("falcon_mamba_7b")       # ssm_chunk=16
    with pytest.raises(ValueError, match="ssm_chunk"):
        ContinuousEngine(cfg, _params(cfg), policy, n_slots=2, max_len=64,
                         prefill_mode="chunked", p_chunk=8)


# ---------------------------------------------------------------------------
# write_prefill_at unit: ragged scatter, ring wrap, neighbor isolation
# ---------------------------------------------------------------------------

def test_write_prefill_at_crosses_ring_boundary():
    """A chunk whose rows straddle the SWA ring edge lands at pos % w,
    rows past n_valid are dropped, and neighbor slots are untouched."""
    cfg = get_smoke_config("h2o_danube_3_4b")       # w=32
    w = cfg.sliding_window
    layer = {k: v[0] for k, v in
             attn_cache_init(cfg, 1, 3, 64, None).items()}   # (B=3, w, ...)
    sentinel = jax.tree.map(lambda x: x + 7.0, layer)
    rng = np.random.default_rng(0)
    p_chunk = 8
    k = rng.standard_normal((1, p_chunk, cfg.n_kv_heads, cfg.hd)) \
        .astype(np.float32)
    v = rng.standard_normal((1, p_chunk, cfg.n_kv_heads, cfg.hd)) \
        .astype(np.float32)
    offset, n_valid = w - 3, 6        # rows 29,30,31 then wrap to 0,1,2
    out = jax.jit(lambda c, kk, vv: write_prefill_at(
        cfg, c, kk, vv, 1, offset, n_valid, None))(
            sentinel, jnp.asarray(k), jnp.asarray(v))
    got_k = np.asarray(out["k"])
    want_rows = [(offset + i) % w for i in range(n_valid)]
    for i, r in enumerate(want_rows):
        np.testing.assert_array_equal(got_k[1, r],
                                      k[0, i].astype(got_k.dtype))
    # dropped padding rows: whatever stood there before
    for i in range(n_valid, p_chunk):
        r = (offset + i) % w
        np.testing.assert_array_equal(got_k[1, r],
                                      np.asarray(sentinel["k"])[1, r])
    # neighbors untouched
    np.testing.assert_array_equal(got_k[0], np.asarray(sentinel["k"])[0])
    np.testing.assert_array_equal(got_k[2], np.asarray(sentinel["k"])[2])


def test_write_prefill_at_quantized_dense_buffer():
    """Packed-KV caches scatter all four leaves at the same rows."""
    cfg = get_smoke_config("llama3_8b")
    layer = {k: v[0] for k, v in
             attn_cache_init(cfg, 1, 2, 16, "nxfp4").items()}
    rng = np.random.default_rng(1)
    k = rng.standard_normal((1, 4, cfg.n_kv_heads, cfg.hd)).astype(
        np.float32)
    v = rng.standard_normal((1, 4, cfg.n_kv_heads, cfg.hd)).astype(
        np.float32)
    out = jax.jit(lambda c, kk, vv: write_prefill_at(
        cfg, c, kk, vv, 0, 5, 3, "nxfp4"))(layer, jnp.asarray(k),
                                           jnp.asarray(v))
    # the stored plane view keeps the context rows on the last axis
    packed = np.asarray(out["k_packed"])
    assert packed[0, ..., 5:8].any() and not packed[0, ..., 8:].any()
    assert not packed[0, ..., :5].any()
    assert not packed[1].any()                       # neighbor untouched
    assert not np.asarray(out["k_meta"])[0, ..., 8:].any()  # padding dropped


# ---------------------------------------------------------------------------
# admission policies
# ---------------------------------------------------------------------------

def _req(uid, t, arrival):
    return Request(uid=uid, tokens=np.zeros((t,), np.int32), max_new=1,
                   arrival_time=arrival)


def test_admission_policy_selection_order():
    """FIFO takes arrival order; SPF takes the shortest arrived prompt;
    the deadline policy takes least NON-NEGATIVE slack (longer prompt =
    less slack at equal deadlines) and refuses expired requests rather
    than admitting them; none admit the future."""
    queue = [_req(0, 32, 0.0), _req(1, 8, 0.1), _req(2, 64, 0.2),
             _req(3, 4, 9.9)]                       # uid 3 hasn't arrived
    assert FifoPolicy().select(queue, now=1.0) == 0
    assert ShortestPromptFirst().select(queue, now=1.0) == 1
    # least slack: deadline_s equal, prefill estimate makes the 64-token
    # prompt the most urgent of the arrived three (all slacks positive)
    pol = TtftDeadline(deadline_s=1.0, prefill_s_per_tok=0.01)
    assert pol.select(queue, now=0.3) == 2
    assert pol.expired(queue, now=0.3) == []
    # once every arrived request's slack is negative the policy selects
    # NONE of them (the old behavior admitted the least-expired — work
    # guaranteed to miss its deadline) and reports them for expiry
    stale = TtftDeadline(deadline_s=0.5, prefill_s_per_tok=0.01)
    assert stale.select(queue, now=1.0) is None
    assert stale.expired(queue, now=1.0) == [0, 1, 2]
    # with no prefill estimate it degrades to earliest deadline = FIFO
    assert TtftDeadline(deadline_s=1.5).select(queue, now=1.0) == 0
    assert FifoPolicy().select(queue[3:], now=1.0) is None


def test_scheduler_policy_changes_admission_order():
    """SlotScheduler + SPF admits the short prompt first even when it
    arrived later, and tracks PREFILLING -> DECODING phases."""
    sched = SlotScheduler(1, policy=ShortestPromptFirst())
    sched.submit(_req(0, 32, 0.0))
    sched.submit(_req(1, 8, 0.0))
    slot, req = sched.next_admission(now=1.0)
    assert req.uid == 1
    sched.mark_prefilling(slot)
    assert sched.phase[slot] == "PREFILLING"
    sched.mark_decoding(slot)
    assert sched.phase[slot] == "DECODING"
    sched.release(slot)
    _, req2 = sched.next_admission(now=1.0)
    assert req2.uid == 0


def test_chunked_engine_with_spf_policy_matches_solo():
    """Policies only reorder admission — per-request bit-equality to the
    solo oracle must survive a non-FIFO policy on the chunked lane."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=None, kv_fmt=None)
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=4, prefill_mode="chunked", p_chunk=8,
                           admission_policy=ShortestPromptFirst())
    reqs = [Request(uid=0, tokens=_prompt(cfg, 24), max_new=6),
            Request(uid=1, tokens=_prompt(cfg, 5, seed=1), max_new=6),
            Request(uid=2, tokens=_prompt(cfg, 13, seed=2), max_new=6)]
    for r in eng.serve(reqs):
        solo = _solo(cfg, params, policy, reqs[r.uid])
        np.testing.assert_array_equal(r.tokens, solo.tokens[0],
                                      err_msg=f"uid={r.uid}")
