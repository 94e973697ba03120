"""Continuous-batching scheduler: bit-equality oracle + ragged-pos units.

The ISSUE-3 acceptance gate: every request served through the continuous
scheduler (staggered admissions, slot reuse, ragged lengths) must produce
tokens IDENTICAL to serving it alone via ``ServeEngine(loop="host")`` —
for dense and NxFP-packed KV caches — because per-slot decode is
row-independent end to end (rope, ring write, masked attend, sampling).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.models.kvcache import attend_decode, write_prefill
from repro.serving import ContinuousEngine, Request, ServeEngine


def _params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, n, t, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (t,)).astype(np.int32)
            for _ in range(n)]


def _solo(cfg, params, policy, req, seed=0):
    """The oracle: this request served alone via the per-token host loop."""
    eng = ServeEngine(cfg, params, policy, max_len=64, rng_seed=req.seed)
    return eng.generate({"tokens": req.tokens[None]}, max_new=req.max_new,
                        temperature=req.temperature,
                        stop_token=req.stop_token, loop="host")


@pytest.mark.parametrize("arch,fmt", [
    ("llama3_8b", None),          # dense cache
    ("llama3_8b", "nxfp4"),       # NxFP-packed KV + weights
    ("hymba_1_5b", "nxfp4"),      # hybrid: SWA ring + SSM state reset
    ("falcon_mamba_7b", None),    # attention-free: pure recurrent slots
    ("qwen2_moe_a2_7b", "nxfp4"), # MoE: per-slot expert capacity decouples
                                  # rows (un-skipped — moe_ffn_decode)
])
def test_continuous_matches_solo_host(arch, fmt):
    """Greedy bit-equality through staggered admissions and slot reuse:
    5 requests with MIXED max_new over 2 slots force evictions,
    re-admissions and ragged per-slot positions mid-stream."""
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=fmt, kv_fmt=fmt)
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=4)
    reqs = [Request(uid=i, tokens=p, max_new=m)
            for i, (p, m) in enumerate(zip(_prompts(cfg, 5, 8),
                                           [5, 11, 3, 8, 14]))]
    results = eng.serve(reqs)
    assert sorted(r.uid for r in results) == list(range(5))
    for r in results:
        req = reqs[r.uid]
        solo = _solo(cfg, params, policy, req)
        assert r.n_generated == req.max_new
        np.testing.assert_array_equal(r.tokens, solo.tokens[0],
                                      err_msg=f"uid={r.uid}")


def test_continuous_ring_wrap_matches_solo():
    """A request long enough to wrap the SWA ring (pos > window) while its
    neighbor slots churn — per-slot ring pointers must not interfere."""
    cfg = get_smoke_config("h2o_danube_3_4b")      # sliding_window=32
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=None, kv_fmt="nxfp4")
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=8)
    reqs = [Request(uid=0, tokens=_prompts(cfg, 1, 8)[0], max_new=40),
            Request(uid=1, tokens=_prompts(cfg, 1, 8, seed=1)[0],
                    max_new=6),
            Request(uid=2, tokens=_prompts(cfg, 1, 8, seed=2)[0],
                    max_new=6)]
    for r in eng.serve(reqs):
        solo = _solo(cfg, params, policy, reqs[r.uid])
        np.testing.assert_array_equal(r.tokens, solo.tokens[0],
                                      err_msg=f"uid={r.uid}")


def test_continuous_stop_token_and_seeded_sampling():
    """Stop tokens and per-request seeds survive the scheduler: a sampled
    request reproduces ``ServeEngine(rng_seed=seed)`` serving it alone,
    stop-terminated rows emit exactly through their stop hit."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=None, kv_fmt=None)
    probe = _solo(cfg, params, policy,
                  Request(uid=0, tokens=_prompts(cfg, 1, 8)[0], max_new=9))
    stop = int(probe.tokens[0, 3])     # solo run stops after 4 tokens
    reqs = [
        Request(uid=0, tokens=_prompts(cfg, 1, 8)[0], max_new=9,
                stop_token=stop),
        Request(uid=1, tokens=_prompts(cfg, 1, 8, seed=5)[0], max_new=7,
                temperature=1.3, seed=17),
        Request(uid=2, tokens=_prompts(cfg, 1, 8, seed=6)[0], max_new=7,
                temperature=0.8, seed=23),
    ]
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=4)
    results = {r.uid: r for r in eng.serve(reqs)}
    for uid, req in enumerate(reqs):
        solo = _solo(cfg, params, policy, req)
        n = int(solo.n_generated[0])
        assert results[uid].n_generated == n
        np.testing.assert_array_equal(results[uid].tokens,
                                      solo.tokens[0, :n])
    assert results[0].tokens[-1] == stop


def test_continuous_rejects_overflowing_request():
    """prompt + max_new beyond max_len must fail loudly at submit time —
    a full slot would clamp-write its last row and return garbage."""
    cfg = get_smoke_config("llama3_8b")
    eng = ContinuousEngine(cfg, _params(cfg),
                           QuantPolicy(weight_fmt=None, kv_fmt=None),
                           n_slots=2, max_len=32, chunk=4)
    bad = Request(uid=0, tokens=np.zeros((20,), np.int32), max_new=20)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.serve([bad])


def test_continuous_staggered_arrivals_metrics():
    """Arrival times gate admission; metrics stay causal (queue_delay >= 0,
    ttft >= queue_delay, every token accounted)."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=None, kv_fmt=None)
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=4)
    reqs = [Request(uid=i, tokens=p, max_new=6,
                    arrival_time=0.0 if i < 2 else 0.05)
            for i, p in enumerate(_prompts(cfg, 4, 8))]
    results = eng.serve(reqs)
    assert len(results) == 4
    for r in results:
        assert r.n_generated == 6
        assert r.queue_delay >= 0.0
        assert r.ttft >= r.queue_delay
        assert r.decode_seconds > 0.0
        solo = _solo(cfg, params, policy, reqs[r.uid])
        np.testing.assert_array_equal(r.tokens, solo.tokens[0])


# ---------------------------------------------------------------------------
# ragged per-slot positions: unit tests under the engine
# ---------------------------------------------------------------------------

def _ragged_cache_and_q(cfg, pos, s, kv_fmt, seed=0):
    """Build one layer's cache holding `s` rope-free random rows."""
    rng = np.random.default_rng(seed)
    b = len(pos)
    k = rng.standard_normal((b, s, cfg.n_kv_heads, cfg.hd)).astype(
        np.float32)
    v = rng.standard_normal((b, s, cfg.n_kv_heads, cfg.hd)).astype(
        np.float32)
    q = jnp.asarray(rng.standard_normal(
        (b, cfg.n_heads, cfg.hd)).astype(np.float32))
    cache = write_prefill(cfg, jnp.asarray(k), jnp.asarray(v), kv_fmt, s)
    return cache, q, k, v


def _dense_reference(cfg, q, k, v, lengths):
    """Per-row full-precision attention over each row's valid prefix."""
    b, h, hd = q.shape
    g = h // cfg.n_kv_heads
    out = np.zeros((b, h, hd), np.float32)
    for i in range(b):
        n = int(lengths[i])
        qg = q[i].reshape(cfg.n_kv_heads, g, hd) * (hd ** -0.5)
        s = np.einsum("hgd,shd->hgs", np.asarray(qg), k[i, :n])
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hgs,shd->hgd", p, v[i, :n]).reshape(h, hd)
    return out


def test_attend_decode_ragged_lengths_dense():
    """attend_decode with a ragged (B,) pos must equal per-row attention
    truncated to each row's own length — the `lengths` arg is honest now."""
    cfg = get_smoke_config("llama3_8b")
    pos = np.array([2, 7, 11, 0], np.int32)     # ragged; row 3 sees 1 tok
    cache, q, k, v = _ragged_cache_and_q(cfg, pos, 12, None)
    got = attend_decode(cfg, cache, q, jnp.asarray(pos), None)
    want = _dense_reference(cfg, q, k, v, pos + 1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2, atol=2e-2)


def test_attend_decode_ragged_matches_quantized_path():
    """Quantized decode attention honors the same ragged lengths as the
    dense path: run both on the SAME ragged pos and compare against the
    same-format lockstep reference computed row by row."""
    cfg = get_smoke_config("llama3_8b")
    pos = np.array([1, 5, 9, 3], np.int32)
    cache_q, q, k, v = _ragged_cache_and_q(cfg, pos, 12, "nxfp4")
    ragged = np.asarray(attend_decode(cfg, cache_q, q, jnp.asarray(pos),
                                      "nxfp4"))
    for i, p in enumerate(pos):
        uni = jnp.full((len(pos),), p, jnp.int32)   # lockstep at row i's pos
        solo = np.asarray(attend_decode(cfg, cache_q, q, uni, "nxfp4"))
        np.testing.assert_array_equal(ragged[i], solo[i])


def test_serve_engine_per_slot_temperature_and_stop():
    """One fixed batch, mixed sampling configs: greedy rows of a mixed
    temperature batch match the all-greedy run bit for bit, per-row stop
    ids halt only their own row — and nothing recompiles per config."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params, QuantPolicy(weight_fmt=None,
                                               kv_fmt=None), max_len=48)
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab, (3, 10)).astype(np.int32)}
    ref = eng.generate(batch, max_new=8)             # all-greedy
    mixed = eng.generate(batch, max_new=8,
                         temperature=np.array([0.0, 1.5, 0.0], np.float32))
    np.testing.assert_array_equal(mixed.tokens[0], ref.tokens[0])
    np.testing.assert_array_equal(mixed.tokens[2], ref.tokens[2])

    stops = np.array([ref.tokens[0, 2], -1, -1], np.int32)
    halted = eng.generate(batch, max_new=8, stop_token=stops)
    assert halted.n_generated[0] == 3                # its own stop hit
    assert (halted.n_generated[1:] == 8).all()       # others unaffected
    np.testing.assert_array_equal(halted.tokens[1], ref.tokens[1])


def test_serve_engine_per_slot_vectors_host_device_identical():
    """Mixed per-slot configs stay bit-identical across loop modes."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    temp = np.array([0.0, 1.2, 0.7], np.float32)
    rng = np.random.default_rng(2)
    batch = {"tokens": rng.integers(0, cfg.vocab, (3, 10)).astype(np.int32)}
    mk = lambda: ServeEngine(cfg, params, QuantPolicy(weight_fmt=None,
                                                      kv_fmt=None),
                             max_len=48, rng_seed=7)
    rh = mk().generate(batch, max_new=9, temperature=temp, loop="host")
    rd = mk().generate(batch, max_new=9, temperature=temp, loop="device",
                       chunk=4)
    np.testing.assert_array_equal(rh.tokens, rd.tokens)
    np.testing.assert_array_equal(rh.n_generated, rd.n_generated)


def test_engine_serves_precast_params_identically():
    """Weights cast one layer at a time (``init_cast_params``) equal the
    whole-tree cast bit for bit, the dense embedding and head stored in
    the model's compute dtype; the engine leaves them as they are
    (casting a cast tree is the identity), and greedy streams match an
    engine handed the raw float32 tree."""
    from repro.core.qtensor import QTensor, direct_cast_tree
    from repro.kernels.ops import quantize_qtensor
    from repro.models import init_cast_params
    cfg = get_smoke_config("llama3_8b")
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    key = jax.random.PRNGKey(0)
    whole = jax.jit(lambda: direct_cast_tree(
        init_params(cfg, key), policy, quantize_qtensor))()
    for name in ("tok_embed", "lm_head"):
        whole[name] = whole[name].astype(cfg.dtype)
    cast = init_cast_params(cfg, key, policy)
    again = direct_cast_tree(cast, policy, quantize_qtensor)
    assert isinstance(cast["layers"]["wq"], QTensor)
    assert cast["lm_head"].dtype == cfg.dtype
    for other in (whole, again):
        assert jax.tree.structure(other) == jax.tree.structure(cast)
        for a, b in zip(jax.tree.leaves(other), jax.tree.leaves(cast)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    reqs = [Request(uid=i, tokens=p, max_new=6)
            for i, p in enumerate(_prompts(cfg, 3, 8))]
    kw = dict(n_slots=2, max_len=64, chunk=4)
    raw = jax.jit(lambda: init_params(cfg, key))()
    want = {r.uid: r.tokens for r in
            ContinuousEngine(cfg, raw, policy, **kw).serve(reqs)}
    got = {r.uid: r.tokens for r in
           ContinuousEngine(cfg, cast, policy, **kw).serve(reqs)}
    for uid, toks in want.items():
        np.testing.assert_array_equal(got[uid], toks)
