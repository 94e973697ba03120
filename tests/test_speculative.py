"""Self-speculative decoding: draft/verify/commit bitwise oracles (§13).

The ISSUE-8 acceptance gate: greedy speculative serving must be
BIT-IDENTICAL to the non-speculative engine — the accepted prefix plus
the verifier's own argmax successor IS the target chain, so acceptance
only changes how many tokens a round yields, never which tokens.  The
oracles here pin that end to end:

  * ``verify_step`` (one batched forward over k+1 rows) vs k+1
    sequential ``decode_step`` calls: logits AND committed cache trees
    bitwise, including ragged per-slot accept counts — rejected draft
    rows must never be observable in the cache.
  * the speculative ``ContinuousEngine`` vs the plain one across
    dense / SWA-ring / hybrid / ssm families, dense + nxfp4 KV,
    recycled and format drafts, k=1 degenerate, adaptive-k.
  * suspend/resume mid-speculation (snapshots only exist at chunk
    boundaries = fully committed state) and the 2-shard engine.

Also home to the window-aware KV canary fix: wrapped SWA slots stay
armed (unit-level checksum semantics + the engine keeps them armed).
"""
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.models.kvcache import kv_slot_checksum
from repro.models.lm import commit_verify, decode_step, prefill, verify_step
from repro.serving import ContinuousEngine, Request, SpeculativeConfig
from repro.serving.speculative import AdaptiveK, pack_emissions


def _params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, n, t=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (t,)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# verify_step / commit_verify vs sequential decode: the model-layer oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,kv_fmt,wrap", [
    ("llama3_8b", None, False),        # dense cache
    ("llama3_8b", "nxfp4", False),     # packed KV rows
    ("h2o_danube_3_4b", "nxfp4", True),  # SWA ring already wrapped
    ("hymba_1_5b", "nxfp4", False),    # hybrid: ring + SSM carry
    ("falcon_mamba_7b", None, False),  # attention-free
])
def test_verify_matches_sequential_decode(arch, kv_fmt, wrap):
    """One batched verify over Q candidate rows == Q sequential decode
    steps: logits bitwise, and committing n rows (uniform AND ragged
    per slot) reproduces the n-step sequential cache tree bitwise — so
    rejected draft rows are never observable."""
    B, Q = 4, 5
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    rng = np.random.default_rng(1)
    plen = (2 * cfg.sliding_window + 8) if wrap else 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, plen)).astype(np.int32))
    _, cache = jax.jit(functools.partial(
        prefill, cfg, max_len=96, kv_fmt=kv_fmt))(params, {"tokens": toks})
    cands = jnp.asarray(rng.integers(0, cfg.vocab, (B, Q)).astype(np.int32))

    step = jax.jit(functools.partial(decode_step, cfg, kv_fmt=kv_fmt))
    seq_logits, seq_cache, caches_at = [], cache, {}
    for i in range(Q):
        lg, seq_cache = step(params, cands[:, i:i + 1], seq_cache)
        seq_logits.append(lg)
        caches_at[i + 1] = seq_cache
    seq_logits = jnp.stack(seq_logits, 1)                   # (B, Q, V)

    vlogits, pending = jax.jit(functools.partial(
        verify_step, cfg, kv_fmt=kv_fmt))(params, cands, cache)
    np.testing.assert_array_equal(np.asarray(vlogits),
                                  np.asarray(seq_logits))

    commit = jax.jit(functools.partial(commit_verify, cfg, kv_fmt=kv_fmt))
    for n in (1, 3, Q):
        com = commit(cache, pending, jnp.full((B,), n, jnp.int32))
        got = jax.tree_util.tree_flatten_with_path(com)[0]
        ref = jax.tree_util.tree_flatten_with_path(caches_at[n])[0]
        for (path, a), (_, b) in zip(got, ref):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"n={n} leaf={jax.tree_util.keystr(path)}")

    # ragged commit: each slot advances by its own accepted count
    n_rag = jnp.asarray([1, 2, Q, 3], jnp.int32)
    com = commit(cache, pending, n_rag)
    np.testing.assert_array_equal(np.asarray(com["pos"]),
                                  np.asarray(cache["pos"]) + np.asarray(n_rag))
    for b_i, n in enumerate([1, 2, Q, 3]):
        got = jax.tree_util.tree_flatten_with_path(com)[0]
        ref = jax.tree_util.tree_flatten_with_path(caches_at[n])[0]
        for (path, a), (_, r) in zip(got, ref):
            a, r = np.asarray(a), np.asarray(r)
            sl = (slice(None), b_i) if a.ndim > 1 and \
                a.shape[1] == B else (b_i,)
            np.testing.assert_array_equal(
                a[sl], r[sl],
                err_msg=f"slot={b_i} n={n} leaf={jax.tree_util.keystr(path)}")


# ---------------------------------------------------------------------------
# the engine oracle: speculative greedy == non-speculative, bitwise
# ---------------------------------------------------------------------------

def _serve_pair(arch, wfmt, kvfmt, spec, reqs_fn, chunk=4):
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=wfmt, kv_fmt=kvfmt)
    base = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                            chunk=chunk)
    ref = {r.uid: r for r in base.serve(reqs_fn(cfg))}
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=chunk, speculative=spec)
    got = {r.uid: r for r in eng.serve(reqs_fn(cfg))}
    assert got.keys() == ref.keys()
    for uid in ref:
        assert got[uid].n_generated == ref[uid].n_generated, f"uid={uid}"
        np.testing.assert_array_equal(got[uid].tokens, ref[uid].tokens,
                                      err_msg=f"{arch} uid={uid}")
    return eng, ref, got


def _mixed_reqs(cfg):
    return [Request(uid=i, tokens=p, max_new=m)
            for i, (p, m) in enumerate(zip(_prompts(cfg, 5),
                                           [5, 11, 3, 8, 14]))]


@pytest.mark.parametrize("arch,wfmt,kvfmt,draft", [
    ("llama3_8b", "nxfp4", "nxfp4", "recycled"),  # the CPU-winning pairing
    ("llama3_8b", None, None, "nxfp4"),     # format draft, partial accepts
    ("hymba_1_5b", "nxfp4", "nxfp4", "recycled"),   # hybrid ring + carry
    ("falcon_mamba_7b", "nxfp4", None, "recycled"),  # pure recurrent
])
def test_speculative_greedy_matches_plain(arch, wfmt, kvfmt, draft):
    """Staggered admissions, slot reuse, ragged max_new — the speculative
    engine must emit the exact token streams of the plain engine.  The
    format-draft case accepts only part of each window (~70%), so the
    accept-prefix/rollback path is genuinely exercised, not just the
    all-accept fast path."""
    eng, _, _ = _serve_pair(arch, wfmt, kvfmt,
                            SpeculativeConfig(k=4, draft=draft),
                            _mixed_reqs)
    st = eng.spec_stats()
    assert st["offered"] > 0
    if draft == "recycled":
        assert st["accept_rate"] == 1.0   # dequantized copy of the target
    else:
        assert 0.0 < st["accept_rate"] <= 1.0


def test_speculative_k1_degenerate():
    """k=1: draft one, verify one — still bitwise, the smallest window."""
    eng, _, _ = _serve_pair("llama3_8b", "nxfp4", None,
                            SpeculativeConfig(k=1), _mixed_reqs)
    assert eng.spec_stats()["offered"] > 0


def test_speculative_swa_ring_wrap_matches_plain():
    """A request long enough to wrap the SWA ring mid-speculation: the
    batched verify writes candidate rows into the ring, rollback must
    restore the pre-round ring bytes for rejected rows."""
    def reqs(cfg):
        return [Request(uid=0, tokens=_prompts(cfg, 1)[0], max_new=40),
                Request(uid=1, tokens=_prompts(cfg, 1, seed=1)[0], max_new=6),
                Request(uid=2, tokens=_prompts(cfg, 1, seed=2)[0], max_new=6)]
    _serve_pair("h2o_danube_3_4b", "nxfp4", "nxfp4",
                SpeculativeConfig(k=4, draft="nxfp6"), reqs, chunk=8)


def test_speculative_stop_token_and_seeded_sampling():
    """Stop tokens terminate exactly as in the plain engine (greedy rows),
    and seeded sampled requests are self-reproducible run to run —
    residual rejection re-splits keys per ROUND, so sampled streams are
    distribution-equal, not samplewise equal, to the plain engine."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt=None)
    probe = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                             chunk=4).serve(
        [Request(uid=0, tokens=_prompts(cfg, 1)[0], max_new=9)])
    stop = int(probe[0].tokens[3])

    def reqs():
        return [Request(uid=0, tokens=_prompts(cfg, 1)[0], max_new=9,
                        stop_token=stop),
                Request(uid=1, tokens=_prompts(cfg, 1, seed=5)[0], max_new=7,
                        temperature=1.3, seed=17),
                Request(uid=2, tokens=_prompts(cfg, 1, seed=6)[0], max_new=7,
                        temperature=0.8, seed=23)]

    def spec_serve():
        eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                               chunk=4, speculative=SpeculativeConfig(k=4))
        return {r.uid: r for r in eng.serve(reqs())}

    a, b = spec_serve(), spec_serve()
    # greedy stop row: exact plain-engine stream (bitwise oracle)
    plain = {r.uid: r for r in ContinuousEngine(
        cfg, params, policy, n_slots=2, max_len=64, chunk=4).serve(reqs())}
    assert a[0].n_generated == plain[0].n_generated
    np.testing.assert_array_equal(a[0].tokens, plain[0].tokens)
    assert a[0].tokens[-1] == stop
    # sampled rows: self-reproducible, in-vocab, full budget or stopped
    for uid in (1, 2):
        assert a[uid].n_generated == b[uid].n_generated
        np.testing.assert_array_equal(a[uid].tokens, b[uid].tokens)
        assert (np.asarray(a[uid].tokens) >= 0).all()
        assert (np.asarray(a[uid].tokens) < cfg.vocab).all()


def test_speculative_adaptive_k_matches_plain():
    """Adaptive per-slot k (EMA back-off) changes only throughput, never
    tokens: greedy bitwise holds while k adapts, and the controller
    actually moves k on a low-acceptance draft."""
    eng, _, _ = _serve_pair(
        "llama3_8b", "nxfp4", "nxfp4",
        SpeculativeConfig(k=4, adaptive=True), _mixed_reqs)
    assert eng.spec_stats()["accept_rate"] == 1.0


def test_speculative_suspend_resume_matches_plain():
    """Suspend both decoding slots mid-stream of a speculative serve:
    snapshots only exist at chunk boundaries (every round committed),
    so resume continues bitwise — and spec_k rides the snapshot."""
    cfg = get_smoke_config("llama3_8b")
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    reqs = lambda: [Request(uid=i, tokens=p, max_new=m)
                    for i, (p, m) in enumerate(zip(_prompts(cfg, 3),
                                                   [12, 14, 8]))]
    plain = {r.uid: r for r in ContinuousEngine(
        cfg, params, policy, n_slots=2, max_len=64, chunk=4).serve(reqs())}

    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=64,
                           chunk=4,
                           speculative=SpeculativeConfig(k=4, adaptive=True))
    seen = {"n": 0}

    def cb(engine, sched):
        if seen["n"] == 2:
            engine.suspend(0)
            engine.suspend(1)
        seen["n"] += 1

    got = {r.uid: r for r in eng.serve(reqs(), progress_cb=cb)}
    for uid in plain:
        assert got[uid].n_generated == plain[uid].n_generated
        np.testing.assert_array_equal(got[uid].tokens, plain[uid].tokens,
                                      err_msg=f"uid={uid}")


# ---------------------------------------------------------------------------
# construction guards + controller units
# ---------------------------------------------------------------------------

def test_speculative_rejects_moe_family():
    cfg = get_smoke_config("qwen2_moe_a2_7b")
    with pytest.raises(ValueError, match="family"):
        ContinuousEngine(cfg, _params(cfg),
                         QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4"),
                         n_slots=2, max_len=64, chunk=4,
                         speculative=SpeculativeConfig(k=4))


def test_recycled_draft_requires_quantized_target():
    """draft='recycled' dequantizes the cast weights — with a dense
    target there is nothing cheaper to recycle; fail loudly."""
    cfg = get_smoke_config("llama3_8b")
    with pytest.raises(ValueError, match="recycled"):
        ContinuousEngine(cfg, _params(cfg),
                         QuantPolicy(weight_fmt=None, kv_fmt=None),
                         n_slots=2, max_len=64, chunk=4,
                         speculative=SpeculativeConfig(k=4))


def test_adaptive_k_controller_backs_off_and_recovers():
    ctl = AdaptiveK(SpeculativeConfig(k=8, adaptive=True, k_min=1,
                                      ema=0.5, lower=0.35, upper=0.75),
                    n_slots=2)
    live = np.array([True, False])
    assert ctl.round_k(live) == 8
    for _ in range(6):                       # sustained rejection: halve
        ctl.update(live, np.array([0, 0]), np.array([8, 8]))
    assert ctl.k[0] == 1 and ctl.k[1] == 8   # dead slot untouched
    for _ in range(12):                      # sustained acceptance: double
        ctl.update(live, np.array([1, 0]), np.array([1, 0]))
    assert ctl.k[0] == 8                     # capped at spec.k
    ctl.arm(0)                               # re-admission resets
    assert ctl.k[0] == 8 and ctl.ema[0] == 1.0
    ctl.arm(1, k=3)                          # resume restores snapshot k
    assert ctl.k[1] == 3


def test_pack_emissions_left_packs_ragged_rounds():
    toks = jnp.asarray([[[11, 12, 0], [21, 0, 0]],
                        [[13, 0, 0], [22, 23, 24]]], jnp.int32)  # (R=2,B=2,Q=3)
    n = jnp.asarray([[2, 1], [1, 3]], jnp.int32)
    out = np.asarray(pack_emissions(toks, n))
    np.testing.assert_array_equal(out[0, :3], [11, 12, 13])
    np.testing.assert_array_equal(out[1, :4], [21, 22, 23, 24])
    assert (out[0, 3:] == 0).all() and (out[1, 4:] == 0).all()


# ---------------------------------------------------------------------------
# window-aware KV canary: wrapped SWA slots stay armed
# ---------------------------------------------------------------------------

def test_kv_checksum_window_aware_on_wrapped_ring():
    """After the ring wraps, rows >= horizon away from the write pointer
    are still covered: corrupting one changes the canary, corrupting a
    row inside the horizon (legitimately writable) does not.  With
    horizon=None the fold is the exact old prefix behavior."""
    cfg = get_smoke_config("h2o_danube_3_4b")       # sliding_window = 32
    params = _params(cfg)
    w = cfg.sliding_window
    plen = 2 * w + 8                                # pos = 72: wrapped
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, plen)).astype(np.int32))
    _, cache = jax.jit(functools.partial(
        prefill, cfg, max_len=96, kv_fmt="nxfp4"))(params, {"tokens": toks})
    upto = cache["pos"]
    hz = 8

    name = next(n for n in ("k_packed", "k", "v_packed", "v")
                if cache["layers"].get(n) is not None)
    leaf = cache["layers"][name]
    # packed leaves are stored with the context rows on the last axis
    plane = name.endswith("_packed")
    s = leaf.shape[-1] if plane else leaf.shape[2]
    ptr = int(np.asarray(upto)[0]) % s

    base = np.asarray(kv_slot_checksum(cfg, cache, upto, hz))

    def flip(row):
        bad = dict(cache)
        bad["layers"] = dict(cache["layers"])
        zeros = (0,) * (leaf.ndim - 3)
        idx = (0, 0) + (zeros + (row,) if plane else (row,) + zeros)
        bad["layers"][name] = leaf.at[idx].set(leaf[idx] ^ 1 if
                                               leaf.dtype == jnp.uint8
                                               else leaf[idx] + 1)
        return np.asarray(kv_slot_checksum(cfg, bad, upto, hz))

    stable_row = (ptr + hz) % s          # just beyond the write horizon
    writable_row = ptr                   # next row the chunk overwrites
    assert flip(stable_row)[0] != base[0], "wrapped slot must stay armed"
    assert flip(writable_row)[0] == base[0], "horizon rows are excluded"
    assert flip(stable_row)[1] == base[1], "other slots unaffected"

    # unwrapped slot (upto + horizon <= S): the window-aware fold excludes
    # nothing and reduces exactly to the historical prefix fold
    toks2 = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)).astype(np.int32))
    _, c2 = jax.jit(functools.partial(
        prefill, cfg, max_len=96, kv_fmt="nxfp4"))(params, {"tokens": toks2})
    np.testing.assert_array_equal(
        np.asarray(kv_slot_checksum(cfg, c2, c2["pos"], hz)),
        np.asarray(kv_slot_checksum(cfg, c2, c2["pos"])))


def test_wrapped_swa_slot_stays_armed_in_engine():
    """The engine-level fix: pre-fix, a slot about to wrap was disarmed
    for the rest of its life; now only horizon >= window disarms."""
    cfg = get_smoke_config("h2o_danube_3_4b")       # sliding_window = 32
    params = _params(cfg)
    policy = QuantPolicy(weight_fmt=None, kv_fmt="nxfp4")
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=96,
                           chunk=8, kv_integrity=True)
    armed_when_wrapped = {"seen": False}

    def cb(engine, sched):
        pos = np.asarray(jax.device_get(engine.cache["pos"]))
        for s, r in sched.active.items():
            if r.uid == 0 and pos[s] > cfg.sliding_window:
                armed_when_wrapped["seen"] |= bool(engine._kv_armed[s])

    reqs = [Request(uid=0, tokens=_prompts(cfg, 1)[0], max_new=48),
            Request(uid=1, tokens=_prompts(cfg, 1, seed=1)[0], max_new=6)]
    res = {r.uid: r for r in eng.serve(reqs, progress_cb=cb)}
    assert armed_when_wrapped["seen"], \
        "slot past the window must remain canary-armed"
    assert res[0].n_generated == 48                 # and serving still works


# ---------------------------------------------------------------------------
# sharded: 2-shard speculative bitwise + owner-only admission (subprocess)
# ---------------------------------------------------------------------------

_SHARDED_ORACLE = r"""
import numpy as np
import jax
from repro.configs import get_smoke_config
from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.serving import ContinuousEngine, Request, SpeculativeConfig
from repro.serving.sharded import ShardedContinuousEngine
from repro.launch.mesh import make_serving_mesh

cfg = get_smoke_config("llama3_8b")
params = init_params(cfg, jax.random.PRNGKey(0))
policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")

def prompts(lens):
    return [np.random.default_rng(s).integers(0, cfg.vocab, (t,))
            .astype(np.int32) for s, t in enumerate(lens)]

def mk():
    return [Request(uid=i, tokens=p, max_new=m,
                    arrival_time=0.0 if i < 3 else 0.05)
            for i, (p, m) in enumerate(zip(prompts([8, 17, 8, 16, 9, 8]),
                                           [5, 11, 3, 8, 14, 6]))]

kw = dict(n_slots=4, max_len=64, chunk=4)
ref = {r.uid: r.tokens
       for r in ContinuousEngine(cfg, params, policy, **kw).serve(mk())}
mesh = make_serving_mesh(2)

# speculative sharded == plain unsharded, bitwise; per-shard stats sane
eng = ShardedContinuousEngine(cfg, params, policy, mesh,
                              speculative=SpeculativeConfig(k=4), **kw)
got = {r.uid: r.tokens for r in eng.serve(mk())}
assert got.keys() == ref.keys()
for uid in ref:
    np.testing.assert_array_equal(got[uid], ref[uid], err_msg=f"uid={uid}")
per = eng.spec_shard_stats()
assert len(per) == 2 and sum(d["offered"] for d in per) > 0
tot = eng.spec_stats()
assert sum(d["accepted"] for d in per) == tot["accepted"]

# owner-only whole-prompt admission (no speculation): still bitwise
kw2 = dict(n_slots=4, max_len=64, chunk=4, prefill_mode="whole")
ref2 = {r.uid: r.tokens
        for r in ContinuousEngine(cfg, params, policy, **kw2).serve(mk())}
got2 = {r.uid: r.tokens
        for r in ShardedContinuousEngine(cfg, params, policy, mesh,
                                         **kw2).serve(mk())}
for uid in ref2:
    np.testing.assert_array_equal(got2[uid], ref2[uid], err_msg=f"uid={uid}")
print("SUBPROC_OK")
"""


@pytest.mark.slow
def test_sharded_speculative_oracle_2_shards_subprocess():
    """2-shard speculative serving: greedy bit-equality vs the plain
    unsharded engine, per-shard acceptance stats, and the owner-only
    whole-prompt admission path."""
    from conftest import run_subprocess
    flags = (os.environ.get("XLA_FLAGS", "")
             + " --xla_force_host_platform_device_count=2").strip()
    env = {**os.environ, "XLA_FLAGS": flags,
           "PYTHONPATH": os.path.join(
               os.path.dirname(os.path.dirname(__file__)), "src")}
    run_subprocess(["-c", _SHARDED_ORACLE], env)
