"""The decode and lane layer loops update the stacked KV cache in place.

``decode_step`` and ``prefill_chunk`` carry the dense attention leaves
whole through the layer loop and write each layer's rows into the stack
(DESIGN.md §7).  Every case here runs them beside a scan over per-layer
cache slices, written in this file as the layer loop was before, and
holds tokens, logits and every cache byte to bitwise equality; then the
continuous engine, which donates its cache to the lane, finish and reset
programs, serves the same streams as with the per-layer scan and never
reads a donated buffer.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.qtensor import QuantPolicy
from repro.models import init_params, lm
from repro.models.blocks import layer_decode, layer_prefill_chunk
from repro.serving import ContinuousEngine, Request
from repro.serving import engine as serving_engine

# case -> (smoke config, KV format, prompt lengths per slot, live mask)
CASES = {
    "dense": ("llama3_8b", "nxfp4", (9, 5), (True, True)),
    "dense_bf16": ("llama3_8b", None, (9, 5), (True, True)),
    "swa_wrapped": ("h2o_danube_3_4b", "nxfp4", (45, 38), (True, True)),
    "not_live": ("llama3_8b", "nxfp4", (9, 5), (True, False)),
    "hybrid": ("hymba_1_5b", "nxfp4", (20, 16), (True, True)),
}
MAX_LEN = 64


def _scan_decode_step(cfg, params, tokens, cache, kv_fmt, live=None):
    """``decode_step`` as a scan over per-layer cache slices (scan xs/ys)."""
    pos = cache["pos"]
    kind = lm._KIND[cfg.family]

    def body(h, xs):
        lp, lc = xs
        return layer_decode(cfg, lp, h, lc, pos, kind, kv_fmt, live=live)

    x, layers = jax.lax.scan(body, lm._embed(cfg, params, tokens),
                             (params["layers"], cache["layers"]))
    step = 1 if live is None else live.astype(jnp.int32)
    return (lm._head(cfg, params, x)[:, 0],
            {"pos": pos + step, "layers": layers})


def _scan_prefill_chunk(cfg, params, tokens, cache, slot, offset, n_valid,
                        lane, kv_fmt):
    """``prefill_chunk`` as a scan over per-layer cache slices."""
    kind = lm._KIND[cfg.family]
    pch = tokens.shape[1]
    positions = jnp.asarray(offset, jnp.int32) + jnp.arange(pch)
    first = jnp.asarray(offset == 0)

    def body(h, xs):
        lp, lane_l, cache_l = xs
        h, new_lane_l, new_cache_l = layer_prefill_chunk(
            cfg, lp, h, lane_l, cache_l, slot, positions, offset, n_valid,
            kind, kv_fmt, first)
        return h, (new_lane_l, new_cache_l)

    x, (new_lane, layers) = jax.lax.scan(
        body, lm._embed(cfg, params, tokens),
        (params["layers"], lane, cache["layers"]))
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    return (lm._head(cfg, params, last)[:, 0], dict(cache, layers=layers),
            new_lane)


def _same_tree(a, b):
    fa, ta = jax.tree.flatten(a)
    fb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _setup(case):
    name, kv_fmt, lens, live = CASES[case]
    cfg = get_smoke_config(name)
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    cache = lm.init_cache(cfg, len(lens), MAX_LEN, kv_fmt)
    for slot, t in enumerate(lens):
        toks = rng.integers(0, cfg.vocab, (1, t)).astype(np.int32)
        _, cache = jax.jit(functools.partial(
            lm.prefill_into_slot, cfg, max_len=MAX_LEN, kv_fmt=kv_fmt))(
            params, {"tokens": jnp.asarray(toks)}, cache, jnp.int32(slot))
    return cfg, params, kv_fmt, cache, jnp.asarray(live)


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_loop_matches_scan_over_slices(case, monkeypatch):
    cfg, params, kv_fmt, cache, live = _setup(case)
    tok = jnp.asarray([3, 7], jnp.int32)

    def run(c):
        return lm.decode_loop(
            cfg, params, tok, c, 3, kv_fmt,
            lambda lg, _: jnp.argmax(lg, axis=-1), jax.random.PRNGKey(0),
            live=live, probe_fn=lambda lg: lg)

    # the in-place path engages on this cache (and bitwise equality
    # below is not two runs of one program)
    assert lm._stacked_kv(cache["layers"]) is not None
    got = jax.jit(run)(cache)
    monkeypatch.setattr(lm, "decode_step", _scan_decode_step)
    want = jax.jit(run)(cache)
    _same_tree(got, want)                 # tokens, cache bytes, logits
    if not bool(np.asarray(live).all()):
        # a not-live slot keeps every row and its position
        b = int(np.flatnonzero(~np.asarray(live))[0])
        _same_tree(jax.tree.map(lambda x: x[:, b], got[2]["layers"]),
                   jax.tree.map(lambda x: x[:, b], cache["layers"]))
        assert int(got[2]["pos"][b]) == int(cache["pos"][b])


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_chunk_matches_scan_over_slices(case):
    cfg, params, kv_fmt, cache, _ = _setup(case)
    p = 16
    lane = lm.init_lane(cfg, MAX_LEN, p)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, (1, 60)).astype(np.int32)
    step = jax.jit(functools.partial(lm.prefill_chunk, cfg, kv_fmt=kv_fmt))
    ref = jax.jit(functools.partial(_scan_prefill_chunk, cfg, kv_fmt=kv_fmt))
    got_c = want_c = cache
    got_l = want_l = lane
    # slot 1 is prefilled chunk by chunk: rows past the SWA window wrap
    # the ring, the last chunk is ragged, and slot 0 keeps its rows
    for off in range(0, prompt.shape[1], p):
        toks = np.zeros((1, p), np.int32)
        n = min(p, prompt.shape[1] - off)
        toks[0, :n] = prompt[0, off:off + n]
        args = (jnp.asarray(toks), jnp.int32(1), jnp.int32(off),
                jnp.int32(n))
        lg, got_c, got_l = step(params, args[0], got_c, args[1], args[2],
                                args[3], got_l)
        lw, want_c, want_l = ref(params, args[0], want_c, args[1], args[2],
                                 args[3], want_l)
        _same_tree((lg, got_c, got_l), (lw, want_c, want_l))
    _same_tree(jax.tree.map(lambda x: x[:, 0], got_c["layers"]),
               jax.tree.map(lambda x: x[:, 0], cache["layers"]))
    # an idle call (n_valid = 0 and not active, the sharded engine's
    # no-op) changes no cache byte
    _, idle, _ = jax.jit(functools.partial(
        lm.prefill_chunk, cfg, kv_fmt=kv_fmt, active=False))(
        params, jnp.zeros((1, p), jnp.int32), got_c, jnp.int32(0),
        jnp.int32(5), jnp.int32(0), got_l)
    _same_tree(idle["layers"], got_c["layers"])


def _requests(cfg):
    rng = np.random.default_rng(11)
    return [Request(uid=u, tokens=rng.integers(0, cfg.vocab, (t,)).astype(
        np.int32), max_new=n, temperature=temp, seed=u)
        for u, (t, n, temp) in enumerate(
            [(21, 9, 0.0), (7, 12, 0.8), (40, 6, 0.0), (13, 10, 1.1),
             (30, 8, 0.0)])]


def _serve(cfg, params, policy, reqs):
    eng = ContinuousEngine(cfg, params, policy, n_slots=2, max_len=MAX_LEN,
                           chunk=4, prefill_mode="chunked", p_chunk=16)
    return eng, {r.uid: list(r.tokens) for r in eng.serve(reqs)}


@pytest.mark.parametrize("arch", ["h2o_danube_3_4b", "hymba_1_5b"])
def test_engine_streams_unchanged_and_donation_safe(arch, monkeypatch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(4))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    reqs = _requests(cfg)

    eng, got = _serve(cfg, params, policy, reqs)
    # the engine's first cache was donated away; the one it holds is live
    first = eng.cache
    assert not any(x.is_deleted() for x in jax.tree.leaves(first))
    # a caller that drops the lane's result on the engine's own cache (a
    # probe, a timing loop) leaves the engine on a live cache
    toks = jnp.zeros((1, eng.p_chunk), jnp.int32)
    for _ in range(2):
        eng._lane_fn(eng.params, toks, eng.cache, eng.lane, jnp.int32(0),
                     jnp.int32(0), jnp.int32(eng.p_chunk), with_head=False,
                     wrapped=False)
    assert all(x.is_deleted() for x in jax.tree.leaves(first["layers"]))
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.cache))
    again = {r.uid: list(r.tokens) for r in eng.serve(reqs)}
    assert again == got

    # the same streams from the per-layer scan (fresh programs)
    monkeypatch.setattr(lm, "_stacked_kv", lambda layers: None)
    monkeypatch.setattr(serving_engine, "_PROGRAM_CACHE", {})
    _, want = _serve(cfg, params, policy, reqs)
    assert got == want
