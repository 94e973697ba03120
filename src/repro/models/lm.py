"""Model assembly: init / train forward / prefill / decode for all families.

All stacks run under ``lax.scan`` over stacked layer parameters (QTensor
leaves slice correctly — see core.qtensor), keeping the HLO size
depth-independent. The VLM interleave (cross-attention every k-th layer)
scans over *groups* of (k-1 self + 1 cross) layers; whisper runs an encoder
stack followed by a decoder stack with per-layer cross attention.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core.qtensor import QTensor, QuantPolicy, direct_cast_tree
from repro.kernels.ops import quantize_qtensor

from .attention import memory_kv
from .blocks import (init_layer, layer_decode, layer_forward,
                     layer_prefill_chunk, layer_verify)
from .common import (ModelConfig, dense, gated_update_slice, ninit, rmsnorm,
                     split_keys)
from .kvcache import (_KV_LEAVES, slot_rows, slot_store, ssm_cache_init,
                      write_prefill, write_token)

Params = Dict[str, Any]

_KIND = {"dense": "dense", "moe": "moe", "ssm": "ssm", "hybrid": "hybrid"}


def _stack_init(key, cfg: ModelConfig, kind: str, n: int):
    return jax.vmap(lambda k: init_layer(k, cfg, kind))(
        jax.random.split(key, n))


def init_params(cfg: ModelConfig, key) -> Params:
    ks = split_keys(key, ["embed", "head", "layers", "enc", "cross", "pos"])
    p: Params = {
        "tok_embed": ninit(ks["embed"], (cfg.vocab, cfg.d_model)),
        "final_scale": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": ninit(ks["head"], (cfg.d_model, cfg.vocab)),
    }
    fam = cfg.family
    if fam in _KIND:
        p["layers"] = _stack_init(ks["layers"], cfg, _KIND[fam], cfg.n_layers)
    elif fam == "vlm":
        every = cfg.cross_attn_every
        assert cfg.n_layers % every == 0, (cfg.n_layers, every)
        groups = cfg.n_layers // every
        self_stack = _stack_init(ks["layers"], cfg, "dense",
                                 groups * (every - 1))
        p["self_layers"] = jax.tree.map(
            lambda l: l.reshape(groups, every - 1, *l.shape[1:]), self_stack)
        p["cross_layers"] = _stack_init(ks["cross"], cfg, "cross", groups)
    elif fam == "audio":
        p["enc_layers"] = _stack_init(ks["enc"], cfg, "dense",
                                      cfg.n_enc_layers)
        p["layers"] = _stack_init(ks["layers"], cfg, "encdec", cfg.n_layers)
        p["enc_pos_embed"] = ninit(ks["pos"], (cfg.n_audio_frames,
                                               cfg.d_model))
        p["enc_scale"] = jnp.ones((cfg.d_model,), jnp.float32)
    else:
        raise ValueError(fam)
    return p


def init_cast_params(cfg: ModelConfig, key, policy: QuantPolicy,
                     mesh=None) -> Params:
    """``direct_cast_tree(init_params(cfg, key), policy, quantize_qtensor)``,
    built one layer at a time so the full float32 tree never exists.

    Each layer is initialized from its own key of ``init_params``' layer
    split, cast, and written into a preallocated stacked buffer in place;
    the top-level leaves (embeddings, head, norms) come from the same
    stream.  The result is bit-identical to casting the full tree under
    ``jit``, at a peak of the cast tree plus one float32 layer, except
    that a dense token embedding and head are stored in ``cfg.dtype``:
    the model reads them only in that dtype (``_embed``, and the bf16
    operands of ``qmatmul``), so they serve the same values, and the
    step programs keep no converted copies of them (2 GiB at llama3-8b).
    With a ``mesh`` every leaf is replicated over it (the serving
    engines' weight layout) and each device casts its own replica; None
    leaves them on the default device.  Families with one homogeneous
    layer stack only.
    """
    if cfg.family not in _KIND:
        raise ValueError(f"layer-wise cast init supports {sorted(_KIND)}, "
                         f"not family={cfg.family!r}")
    kind = _KIND[cfg.family]
    layer_keys = jax.random.split(
        split_keys(key, ["embed", "head", "layers", "enc", "cross",
                         "pos"])["layers"], cfg.n_layers)

    def cast(tree):
        return direct_cast_tree(tree, policy, quantize_qtensor)

    def top():
        p = init_params(cfg, key)
        p.pop("layers")                 # dead code: XLA never builds it
        p = cast(p)
        for name in ("tok_embed", "lm_head"):
            if not isinstance(p[name], QTensor):
                p[name] = p[name].astype(cfg.dtype)
        return p

    # which stacked leaves the policy casts is decided on the stacked
    # shapes (min_size, ndim), exactly as for the full tree
    ref = jax.eval_shape(lambda: cast(init_params(cfg, key)))["layers"]

    def one_layer(k):
        layer = init_layer(k, cfg, kind)
        return jax.tree.leaves({
            n: (quantize_qtensor(v, policy.weight_fmt, policy.axis)
                if isinstance(ref[n], QTensor) else v)
            for n, v in layer.items()})

    def jit(fn, **kw):
        if mesh is None:
            return jax.jit(fn, **kw)
        # fully manual: a Pallas quantizer cannot be partitioned by XLA,
        # so every device runs the whole (replicated) computation itself
        from jax.sharding import PartitionSpec
        from repro.sharding.shard_map import shard_map_manual
        return jax.jit(shard_map_manual(fn, mesh, in_specs=PartitionSpec(),
                                        out_specs=PartitionSpec()), **kw)

    leaves = jit(lambda: [jnp.zeros(l.shape, l.dtype)
                          for l in jax.tree.leaves(ref)])()
    put = jit(lambda st, lay, i: [a.at[i].set(b) for a, b in zip(st, lay)],
              donate_argnums=0)
    layer_fn = jit(one_layer)
    for i in range(cfg.n_layers):
        leaves = put(leaves, layer_fn(layer_keys[i]), jnp.int32(i))
    params = jit(top)()
    params["layers"] = jax.tree.unflatten(jax.tree.structure(ref), leaves)
    return params


def _embed(cfg: ModelConfig, params: Params, tokens):
    emb = params["tok_embed"]
    if hasattr(emb, "dequantize"):  # QTensor embedding (policy-dependent)
        emb = emb.dequantize(cfg.dtype)
    return jnp.take(emb, tokens, axis=0).astype(cfg.dtype)


def _head(cfg: ModelConfig, params: Params, x):
    x = rmsnorm(x, params["final_scale"], cfg.norm_eps)
    return dense(x, params["lm_head"], out_dtype=jnp.float32)


def _encode_audio(cfg: ModelConfig, params: Params, frames):
    """Stub-frontend encoder: frames (B, S_enc, D) are precomputed embeddings."""
    s = frames.shape[1]
    pos = params["enc_pos_embed"]
    if hasattr(pos, "dequantize"):
        pos = pos.dequantize(jnp.float32)
    x = (frames.astype(jnp.float32) + pos[None, :s]).astype(cfg.dtype)
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(h, lp):
        h, _ = layer_forward(cfg, lp, h, positions, "dense", causal=False)
        return h, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rmsnorm(x, params["enc_scale"], cfg.norm_eps)


def forward_train(cfg: ModelConfig, params: Params, batch: Dict[str, Any]
                  ) -> Tuple[jax.Array, jax.Array]:
    """batch: tokens (B, T) [+ frames / vision]. Returns (logits f32, aux)."""
    tokens = batch["tokens"]
    b, t = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(t, dtype=jnp.int32)
    aux0 = jnp.zeros((), jnp.float32)
    fam = cfg.family

    ckpt = jax.checkpoint if cfg.remat else (lambda f: f)

    if fam in _KIND:
        @ckpt
        def body(carry, lp):
            h, aux = carry
            h, out = layer_forward(cfg, lp, h, positions, _KIND[fam])
            return (h, aux + out.get("moe_aux", 0.0)), None

        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
        return _head(cfg, params, x), aux

    if fam == "vlm":
        vision = batch["vision"]

        @ckpt
        def group(h, lps):
            lp_self, lp_cross = lps

            def inner(hh, lp):
                hh, _ = layer_forward(cfg, lp, hh, positions, "dense")
                return hh, None

            h, _ = jax.lax.scan(inner, h, lp_self)
            mem = memory_kv(cfg, lp_cross, vision.astype(cfg.dtype))
            h, _ = layer_forward(cfg, lp_cross, h, positions, "cross",
                                 mem=mem)
            return h, None

        x, _ = jax.lax.scan(group, x,
                            (params["self_layers"], params["cross_layers"]))
        return _head(cfg, params, x), aux0

    if fam == "audio":
        enc = _encode_audio(cfg, params, batch["frames"])

        @ckpt
        def body(h, lp):
            mem = memory_kv(cfg, lp, enc)
            h, _ = layer_forward(cfg, lp, h, positions, "encdec", mem=mem)
            return h, None

        x, _ = jax.lax.scan(body, x, params["layers"])
        return _head(cfg, params, x), aux0

    raise ValueError(fam)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            aux_weight: float = 0.01):
    logits, aux = forward_train(cfg, params, batch)
    targets = batch["tokens"][:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        loss = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    else:
        loss = jnp.mean(nll)
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            max_len: int, kv_fmt: Optional[str], act_fmt: Optional[str] = None
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the full prompt, build the cache. Returns (last logits (B,V), cache).

    ``act_fmt`` (DESIGN.md §15) quantizes each layer's prefill activations
    for quantized x quantized GEMMs — scanned-stack families only (vlm/
    audio group scans stay dense); None keeps the seed graph bitwise.
    """
    tokens = batch["tokens"]
    b, t = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(t, dtype=jnp.int32)
    fam = cfg.family
    # per-slot position vector: every slot of the decode stack advances
    # independently (DESIGN.md §8) — lockstep prefill just starts them equal
    cache: Dict[str, Any] = {"pos": jnp.full((b,), t, jnp.int32)}

    def attn_entries(out):
        return write_prefill(cfg, out["k"], out["v"], kv_fmt, max_len)

    if fam in _KIND:
        kind = _KIND[fam]

        def body(h, lp):
            h, out = layer_forward(cfg, lp, h, positions, kind,
                                   act_fmt=act_fmt)
            entries = {}
            if "k" in out:
                entries.update(attn_entries(out))
            if "ssm_h" in out:
                entries.update(h=out["ssm_h"], conv=out["ssm_conv"])
            return h, entries

        x, layer_caches = jax.lax.scan(body, x, params["layers"])
        cache["layers"] = layer_caches
    elif fam == "vlm":
        vision = batch["vision"].astype(cfg.dtype)

        def group(h, lps):
            lp_self, lp_cross = lps

            def inner(hh, lp):
                hh, out = layer_forward(cfg, lp, hh, positions, "dense")
                return hh, attn_entries(out)

            h, self_cache = jax.lax.scan(inner, h, lp_self)
            mem_k, mem_v = memory_kv(cfg, lp_cross, vision)
            h, _ = layer_forward(cfg, lp_cross, h, positions, "cross",
                                 mem=(mem_k, mem_v))
            return h, (self_cache, {"mem_k": mem_k, "mem_v": mem_v})

        x, (self_caches, cross_caches) = jax.lax.scan(
            group, x, (params["self_layers"], params["cross_layers"]))
        cache["self_layers"] = self_caches
        cache["cross_layers"] = cross_caches
    elif fam == "audio":
        enc = _encode_audio(cfg, params, batch["frames"])

        def body(h, lp):
            mem_k, mem_v = memory_kv(cfg, lp, enc)
            h, out = layer_forward(cfg, lp, h, positions, "encdec",
                                   mem=(mem_k, mem_v))
            entries = attn_entries(out)
            entries.update(mem_k=mem_k, mem_v=mem_v)
            return h, entries

        x, layer_caches = jax.lax.scan(body, x, params["layers"])
        cache["layers"] = layer_caches
    else:
        raise ValueError(fam)

    logits = _head(cfg, params, x[:, -1:])
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# chunked prefill: resumable fixed-shape partial prefill (the serving lane)
# ---------------------------------------------------------------------------

def _check_p_chunk(cfg: ModelConfig, p_chunk: int) -> None:
    """Static lane-chunk invariants, enforced WHERE they break (not only
    in one engine): a chunk wider than the SWA ring scatters two tokens
    to the same cache row (silent corruption), and a chunk misaligned
    with ``ssm_chunk`` regroups the associative scan — breaking the
    chunked == whole bit-equality contract without an error."""
    assert not cfg.sliding_window or p_chunk <= cfg.sliding_window, \
        (p_chunk, cfg.sliding_window)
    assert cfg.family not in ("ssm", "hybrid") or \
        p_chunk % cfg.ssm_chunk == 0, (p_chunk, cfg.ssm_chunk)


def init_lane(cfg: ModelConfig, max_len: int, p_chunk: int,
              n_lanes: int = 1) -> Dict[str, Any]:
    """Allocate the chunked-prefill lane scratch (batch-1, fixed shapes).

    The lane holds the ONE in-flight prompt's state between chunks:
    a dense natural-order K/V scratch (what the next chunk attends over —
    the same full-precision values the whole-prompt prefill sees, which
    is what makes chunked == whole bit for bit even when the live cache
    is NxFP-packed) plus the SSM/conv recurrent carry.  Stale contents
    need no reset between requests: attention masks beyond-valid rows to
    exact-zero contributions and ``prefill_chunk`` zeroes the recurrent
    carry at ``offset == 0``.

    ``n_lanes`` stacks independent lanes along the batch axis — the
    slot-sharded engine allocates one PER SHARD (batch axis sharded over
    'data'), so each shard's manual shard_map body sees the ordinary
    batch-1 lane while S prompts prefill concurrently.
    """
    assert cfg.family in _KIND, (cfg.family, "chunked prefill serves the "
                                 "scanned-stack families")
    _check_p_chunk(cfg, p_chunk)
    s_p = -(-max_len // p_chunk) * p_chunk
    lane: Dict[str, Any] = {}
    if cfg.family != "ssm":
        z = jnp.zeros((cfg.n_layers, n_lanes, s_p, cfg.n_kv_heads, cfg.hd),
                      cfg.dtype)
        lane.update(k=z, v=z)
    if cfg.family in ("ssm", "hybrid"):
        lane.update(ssm_cache_init(cfg, cfg.n_layers, n_lanes))
    return lane


def prefill_chunk(cfg: ModelConfig, params: Params, tokens, cache, slot,
                  offset, n_valid, lane, kv_fmt: Optional[str],
                  with_head: bool = True, active=None,
                  wrapped: bool = False, act_fmt: Optional[str] = None):
    """Advance the in-flight prefill by ONE fixed-shape (1, P) chunk.

    ``tokens`` holds prompt positions [offset, offset + P) (tail-padded
    past ``n_valid``); K/V lands in slot ``slot`` of the LIVE cache at
    the global offsets (dense or NxFP-packed via the fused quantize
    path), the lane carries the dense attention scratch and SSM state to
    the next chunk, and the returned logits are the hidden state at the
    chunk's LAST VALID row through the head — on the final chunk, bit-
    identical to the whole-prompt ``prefill``'s last-token logits.  The
    shapes are offset-independent: one compiled program serves every
    chunk of every prompt length (the admission-stall bound the serving
    lane exists for — no per-length retraces).

    ``with_head=False`` (static) skips the (D, V) head matmul and
    returns the last-valid HIDDEN row (1, D) instead — only the final
    chunk's logits are ever read, and at real vocab sizes the head is a
    whole layer's worth of FLOPs per chunk.

    ``active`` (traced bool, default live) is the sharded engine's no-op
    form: an inactive call (a shard whose lane is idle while its
    neighbors advance theirs inside one fused dispatch) must leave the
    CACHE untouched — callers pass ``n_valid=0`` so the K/V scatter
    drops every row, and ``active=False`` gates the SSM state writes
    that have no out-of-range row to route to.  Lane scratch may take
    garbage writes either way: the next prompt's chunks overwrite/mask
    every row they read (see ``init_lane``).

    ``wrapped`` (STATIC) selects the ring-lane graph for chunks whose
    global offset has passed the lane's row capacity — how long SWA
    prompts admit through the fixed-size lane (DESIGN.md §9/§14).  It
    must be False for in-capacity chunks: the two graphs index the lane
    differently and only agree on their own offset ranges.

    ``act_fmt`` (STATIC, DESIGN.md §15) quantizes the chunk's per-layer
    activations for quantized x quantized GEMMs; None keeps the graph
    byte-identical to the dense-activation lane.

    Returns (logits (1, V) — or hidden (1, D) — , new_cache, new_lane).
    """
    b, pch = tokens.shape
    assert b == 1, tokens.shape
    _check_p_chunk(cfg, pch)
    fam = cfg.family
    kind = _KIND[fam]
    x = _embed(cfg, params, tokens)
    positions = (jnp.asarray(offset, jnp.int32)
                 + jnp.arange(pch, dtype=jnp.int32))
    first = jnp.asarray(offset == 0)

    def body(h, xs, cache_l):
        lp, lane_l = xs
        return layer_prefill_chunk(
            cfg, lp, h, lane_l, cache_l, slot, positions, offset, n_valid,
            kind, kv_fmt, first, active=active, wrapped=wrapped,
            act_fmt=act_fmt)

    x, new_lane, new_layers = _layer_scan(
        body, x, (params["layers"], lane), cache["layers"])
    # the slot's pos stays parked while PREFILLING (its decode-chunk
    # writes are live-masked); the engine sets pos[slot] at completion
    new_cache = dict(cache, layers=new_layers)
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    if not with_head:
        return last[:, 0], new_cache, new_lane
    logits = _head(cfg, params, last)
    return logits[:, 0], new_cache, new_lane


def _stacked_kv(layers):
    """The dense attention leaves of a stacked layer cache, or None.

    These ride the layer loop's carry whole and each layer writes its
    rows into them in place (``kvcache.write_token`` /
    ``write_prefill_at`` with the ``layer`` entry), so no layer's cache
    slice is ever materialised (DESIGN.md §7).  A paged cache (``block``
    leaf) keeps the scan over per-layer slices, and a cache without
    attention leaves (pure SSM) has nothing to carry.
    """
    if "block" in layers:
        return None
    kv = {n: v for n, v in layers.items() if n in _KV_LEAVES}
    return kv or None


def _layer_scan(body, x, xs, layers, in_place: bool = True):
    """Scan ``body(h, xs_l, layer_cache) -> (h, ys_l, new_layer_cache)``
    over the stacked layers; returns (x, ys, new layers).

    With ``in_place``, the dense attention leaves (``_stacked_kv``) ride
    the carry whole, pinned to the row-major layout the attention kernel
    reads (with no kernel in the loop, XLA lays the lane's stack out in
    rows and relayouts it on entry and exit), and each layer's cache is
    those leaves plus its ``layer`` index; the rest (SSM state) and
    ``xs`` are scanned per layer.
    """
    kv = _stacked_kv(layers) if in_place else None
    if kv is None:
        def step(h, xs_c):
            h, ys, nc = body(h, *xs_c)
            return h, (ys, nc)

        x, (ys, new) = jax.lax.scan(step, x, (xs, layers))
        return x, ys, new

    def step_kv(carry, xs_c):
        h, kv, l = carry
        xs_l, lc = xs_c
        h, ys, nc = body(h, xs_l, dict(lc, layer=l, **kv))
        kv = {n: with_layout_constraint(nc[n], Layout(tuple(range(
            nc[n].ndim)))) for n in kv}
        return (h, kv, l + 1), (ys, {n: nc[n] for n in lc})

    rest = {n: v for n, v in layers.items() if n not in kv}
    (x, kv, _), (ys, rest) = jax.lax.scan(
        step_kv, (x, kv, jnp.int32(0)), (xs, rest))
    return x, ys, dict(rest, **kv)


def decode_step(cfg: ModelConfig, params: Params, tokens, cache,
                kv_fmt: Optional[str], live=None
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens (B, 1); cache from prefill. Returns (logits (B, V), new cache).

    ``cache["pos"]`` is (B,) — slots at ragged positions decode together;
    each ropes/writes/attends at its own offset.  ``live`` (B,) bool
    (continuous engine) freezes not-live slots' cache state — position,
    K/V row writes, SSM integration — so mid-prefill and parked slots
    ride through the fixed-shape batch without clobbering anything; live
    slots are bit-identical to ``live=None``.
    """
    pos = cache["pos"]
    x = _embed(cfg, params, tokens)
    fam = cfg.family
    step = 1 if live is None else live.astype(jnp.int32)
    new_cache: Dict[str, Any] = {"pos": pos + step}

    if fam in _KIND or fam == "audio":
        kind = _KIND.get(fam, "encdec")

        def body(h, lp, lc):
            h, nc = layer_decode(cfg, lp, h, lc, pos, kind, kv_fmt,
                                 live=live)
            return h, None, nc

        # audio keeps the scan over per-layer slices
        x, _, new_cache["layers"] = _layer_scan(
            body, x, params["layers"], cache["layers"],
            in_place=fam in _KIND)
    elif fam == "vlm":
        def group(h, xs):
            (lp_self, lc_self), (lp_cross, lc_cross) = xs

            def inner(hh, ys):
                lp, lc = ys
                hh, nc = layer_decode(cfg, lp, hh, lc, pos, "dense", kv_fmt,
                                      live=live)
                return hh, nc

            h, self_new = jax.lax.scan(inner, h, (lp_self, lc_self))
            h, cross_new = layer_decode(cfg, lp_cross, h, lc_cross, pos,
                                        "cross", kv_fmt, live=live)
            return h, (self_new, cross_new)

        x, (self_caches, cross_caches) = jax.lax.scan(
            group, x, ((params["self_layers"], cache["self_layers"]),
                       (params["cross_layers"], cache["cross_layers"])))
        new_cache["self_layers"] = self_caches
        new_cache["cross_layers"] = cross_caches
    else:
        raise ValueError(fam)

    logits = _head(cfg, params, x)
    return logits[:, 0], new_cache


def decode_loop(cfg: ModelConfig, params: Params, tok, cache, n_steps: int,
                kv_fmt: Optional[str], sample_fn, key,
                split_fn=jax.random.split, live=None, logits_fn=None,
                probe_fn=None):
    """Run ``n_steps`` decode steps as ONE on-device ``lax.scan``.

    The serving hot loop (DESIGN.md §7): the KV cache, logits and sampled
    tokens never leave the device; the host dispatches once per chunk
    instead of once per token.

    ``tok`` (B,) int32 is the token entering the loop (already sampled
    from the previous logits). Each step records it, advances the model,
    and samples the successor with ``sample_fn(logits (B, V) f32, subkey)
    -> (B,) int32``. The PRNG key is split once per step regardless of
    sampler, so the key stream is invariant to chunking AND matches the
    host loop's per-token ``jax.random.split``.

    ``key``/``split_fn`` generalize the sampler state: the continuous
    engine threads PER-SLOT keys ((B, 2) uint32) with a vmapped split so
    each slot's stream matches the solo engine's chain for its seed;
    ``split_fn(key) -> (next_key, subkey)``.

    ``logits_fn`` (optional) rewrites each step's logits before sampling
    — the serving fault-injection hook (an identity-by-default ``where``
    keeps the fault-free path bit-identical).  ``probe_fn`` (optional)
    maps each step's post-``logits_fn`` logits to a per-step auxiliary
    (e.g. a per-slot ``isfinite`` health sentinel); when set, the return
    grows a fifth element with the per-step probes stacked on axis 0.

    Returns ``(tokens (B, n_steps), tok, cache, key[, aux])`` — the
    emitted tokens start with the entering token; the returned ``tok``
    enters the next chunk.
    """
    def step(carry, _):
        t, c, k = carry
        k, sub = split_fn(k)
        logits, c = decode_step(cfg, params, t[:, None], c, kv_fmt,
                                live=live)
        if logits_fn is not None:
            logits = logits_fn(logits)
        out = t if probe_fn is None else (t, probe_fn(logits))
        nxt = sample_fn(logits, sub).astype(jnp.int32)
        return (nxt, c, k), out

    (tok, cache, key), out = jax.lax.scan(
        step, (tok, cache, key), None, length=n_steps)
    if probe_fn is None:
        return out.T, tok, cache, key
    toks, aux = out
    return toks.T, tok, cache, key, aux


# ---------------------------------------------------------------------------
# self-speculative decoding: draft (cheap weights) / verify (target weights)
# ---------------------------------------------------------------------------

def draft_loop(cfg: ModelConfig, draft_params: Params, tok, cache,
               n_steps: int, kv_fmt: Optional[str], sample_fn, key,
               split_fn=jax.random.split, live=None, with_logits=False):
    """Draft ``n_steps`` candidate tokens per slot WITHOUT committing KV.

    Runs the regular ``decode_loop`` scan over the DRAFT weights on a
    functional copy of the cache and simply discards the returned cache —
    JAX immutability makes the rollback free (no rejected draft row ever
    reaches the caller's buffers, including SWA ring writes and SSM state
    integration, which stay internally consistent inside the discarded
    copy).  The returned tokens are the candidates c_1..c_k entering
    ``verify_step``; the caller's cache and ``pos`` are untouched.

    ``with_logits`` additionally returns the per-step draft logits
    ((n_steps, B, V) f32) via the probe hook — residual-rejection
    sampling needs the draft distribution at each candidate.

    Returns ``(cands (B, n_steps), key[, draft_logits])``.
    """
    out = decode_loop(cfg, draft_params, tok, cache, n_steps, kv_fmt,
                      sample_fn, key, split_fn=split_fn, live=live,
                      probe_fn=(lambda lg: lg) if with_logits else None)
    if with_logits:
        toks, last, _, key, logits = out
    else:
        toks, last, _, key = out
    # decode_loop emits the ENTERING token each step; the candidates are
    # the sampled successors: steps 1.. plus the final sampled token
    cands = jnp.concatenate([toks[:, 1:], last[:, None]], axis=1)
    if with_logits:
        return cands, key, logits
    return cands, key


def verify_step(cfg: ModelConfig, params: Params, tokens, cache,
                kv_fmt: Optional[str], live=None):
    """Score Q candidate rows per slot in ONE batched target-width forward.

    ``tokens`` (B, Q) holds rows [c_0, c_1, .., c_{Q-1}] — the last
    committed token followed by the draft candidates — consumed at
    positions ``pos[b] .. pos[b]+Q-1``.  Row i's logits are bit-identical
    to what a sequential ``decode_step`` would produce after committing
    rows < i (the batched weight matmuls are row-stable and the
    write/attend inner loop runs the exact decode ops per row — see
    ``blocks.layer_verify``), so greedy acceptance can only ever emit the
    same tokens the non-speculative engine would.

    The caller's cache is NOT modified: all cache writes land in a
    discarded scratch copy.  Returns ``(logits (B, Q, V) f32, pending)``;
    feed ``pending`` with per-slot accept lengths to ``commit_verify``.
    """
    pos = cache["pos"]
    x = _embed(cfg, params, tokens)
    fam = cfg.family
    if fam not in _KIND:
        raise NotImplementedError(f"speculative verify: family {fam!r}")
    kind = _KIND[fam]

    def body(h, xs):
        lp, lc = xs
        h, scratch, pend = layer_verify(cfg, lp, h, lc, pos, kind, kv_fmt,
                                        live=live)
        return h, (scratch, pend)

    x, (_, pending_layers) = jax.lax.scan(
        body, x, (params["layers"], cache["layers"]))
    logits = _head(cfg, params, x)                               # (B, Q, V)
    return logits, {"layers": pending_layers}


def commit_verify(cfg: ModelConfig, cache, pending, n_commit,
                  kv_fmt: Optional[str], live=None):
    """Land each slot's accepted prefix; rejected rows are never written.

    ``n_commit`` (B,) int32 in [0, Q]: rows [pos, pos + n_commit) per slot
    receive the target-weight K/V from ``pending`` through the same
    value-gated ``write_token`` the sequential decode path uses (same
    per-row quantization — committed bytes are bit-identical to a
    non-speculative run), SSM state jumps to the post-``n_commit`` step
    state, and ``pos`` advances by each slot's own accepted length.
    Slots with ``n_commit == 0`` or ``live == False`` are untouched.
    """
    pos = cache["pos"]
    b = pos.shape[0]
    n_commit = jnp.asarray(n_commit, jnp.int32)
    live_b = (jnp.ones((b,), bool) if live is None
              else jnp.asarray(live, bool))
    commit_any = live_b & (n_commit > 0)

    def body(_, xs):
        lc, pend = xs
        nc = dict(lc)
        if "k" in pend:
            attn = {n: lc[n] for n in lc
                    if not n.startswith(("h", "conv", "mem_"))}
            qn = pend["k"].shape[1]

            def wstep(c, i):
                gate = live_b & (i < n_commit)
                ki = jax.lax.dynamic_slice_in_dim(pend["k"], i, 1, axis=1)
                vi = jax.lax.dynamic_slice_in_dim(pend["v"], i, 1, axis=1)
                return write_token(cfg, c, ki, vi, pos + i, kv_fmt,
                                   live=gate), None

            attn, _ = jax.lax.scan(wstep, attn,
                                   jnp.arange(qn, dtype=jnp.int32))
            nc.update(attn)
        if "h" in pend:
            qn = pend["h"].shape[1]
            idx = jnp.clip(n_commit - 1, 0, qn - 1)

            def sel(stacked, old):
                ix = idx.reshape((b,) + (1,) * (stacked.ndim - 1))
                new = jnp.take_along_axis(stacked, ix, axis=1)[:, 0]
                keep = commit_any.reshape((b,) + (1,) * (old.ndim - 1))
                return jnp.where(keep, new.astype(old.dtype), old)

            nc.update(h=sel(pend["h"], lc["h"]),
                      conv=sel(pend["conv"], lc["conv"]))
        return None, nc

    _, new_layers = jax.lax.scan(body, None,
                                 (cache["layers"], pending["layers"]))
    new_pos = pos + jnp.where(live_b, n_commit, 0)
    return dict(cache, layers=new_layers, pos=new_pos)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               kv_fmt: Optional[str], pos_value: int = 0) -> Dict[str, Any]:
    """Allocate a CONCRETE zeroed cache (the continuous engine's arena).

    Every slot starts empty at ``pos_value``; requests are prefilled into
    slots one at a time via ``prefill_into_slot``. Also the shape source
    for ``init_cache_specs`` (dry-run lowering uses the same builder under
    ``eval_shape``).
    """
    from .kvcache import attn_cache_init

    cache: Dict[str, Any] = {"pos": jnp.full((batch,), pos_value,
                                             jnp.int32)}
    fam, L = cfg.family, cfg.n_layers
    if fam in _KIND:
        entries = {}
        if fam != "ssm":
            entries.update(attn_cache_init(cfg, L, batch, max_len, kv_fmt))
        if fam in ("ssm", "hybrid"):
            entries.update(ssm_cache_init(cfg, L, batch))
        cache["layers"] = entries
    elif fam == "vlm":
        every = cfg.cross_attn_every
        groups = L // every
        self_c = attn_cache_init(cfg, groups * (every - 1), batch,
                                 max_len, kv_fmt)
        cache["self_layers"] = jax.tree.map(
            lambda l: l.reshape(groups, every - 1, *l.shape[1:]), self_c)
        s_vis = cfg.n_vision_tokens
        mem = jnp.zeros((groups, batch, s_vis, cfg.n_kv_heads, cfg.hd),
                        cfg.dtype)
        cache["cross_layers"] = {"mem_k": mem, "mem_v": mem}
    elif fam == "audio":
        entries = attn_cache_init(cfg, L, batch, max_len, kv_fmt)
        s_enc = cfg.n_audio_frames
        mem = jnp.zeros((L, batch, s_enc, cfg.n_kv_heads, cfg.hd),
                        cfg.dtype)
        entries.update(mem_k=mem, mem_v=mem)
        cache["layers"] = entries
    else:
        raise ValueError(fam)
    return cache


def init_cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                     kv_fmt: Optional[str]):
    """Abstract cache (ShapeDtypeStructs) for decode-only dry-run lowering."""
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, kv_fmt, max_len - 1))


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     kv_fmt: Optional[str], n_pages: int, page_size: int,
                     pos_value: int = 0) -> Dict[str, Any]:
    """Allocate the paged-engine arena: pool leaves + per-slot block tables.

    Same pytree contract as ``init_cache`` (``pos`` (B,), scan-stacked
    ``layers``) but attention KV lives in an ``n_pages``-page physical
    pool indexed through each slot's block table (DESIGN.md §14) instead
    of B max_len-sized slabs.  The decode/prefill/verify programs are
    unchanged — ``kvcache``'s write/attend paths dispatch on the
    ``block`` leaf.  SSM recurrent state has no sequence axis and stays
    per-slot dense.  Scanned-stack families only (the paged engine's
    service surface).
    """
    if cfg.family not in _KIND:
        raise ValueError(f"paged cache serves the scanned-stack families, "
                         f"not {cfg.family!r}")
    from .kvcache import paged_attn_cache_init

    cache: Dict[str, Any] = {"pos": jnp.full((batch,), pos_value,
                                             jnp.int32)}
    entries: Dict[str, Any] = {}
    if cfg.family != "ssm":
        entries.update(paged_attn_cache_init(cfg, cfg.n_layers, batch,
                                             max_len, kv_fmt, n_pages,
                                             page_size))
    if cfg.family in ("ssm", "hybrid"):
        entries.update(ssm_cache_init(cfg, cfg.n_layers, batch))
    cache["layers"] = entries
    return cache


# ---------------------------------------------------------------------------
# slot surgery: admit / evict ONE sequence of a live batched cache
# ---------------------------------------------------------------------------

def _batch_axis(name: str) -> int:
    """Batch-axis position inside a cache group's stacked leaves."""
    return 2 if name == "self_layers" else 1  # vlm self stack: (G, k-1, B,…)


def _paged_slot_table(group, slot):
    """One slot's block-table rows (L, P) out of a paged cache group."""
    blk = group["block"]                                     # (L, B, P)
    row = jax.lax.dynamic_slice(
        blk, (jnp.zeros((), jnp.int32), jnp.asarray(slot, jnp.int32),
              jnp.zeros((), jnp.int32)),
        (blk.shape[0], 1, blk.shape[2]))
    return row[:, 0]


def _write_paged_group(group, solo_group, slot, apply):
    """Scatter a DENSE-layout batch-1 group into one paged slot.

    ``solo_group`` carries standard dense leaf names (k/v/k_packed/...,
    shapes (L, 1, S, ...)) — the snapshot interchange layout — and each
    row r routes through the slot's block table to pool[phys, r % page].
    Rows whose table entry is still the null page (beyond the slot's
    reservation: snapshots zero-pad to full capacity) and non-owner
    shards (``apply`` False) route past the pool bound and drop.  SSM
    leaves in the same group take the ordinary gated slice.
    """
    row = _paged_slot_table(group, slot)                     # (L, P)
    pool0 = next(v for n, v in group.items() if n.startswith("pool_"))
    n_pages, page = pool0.shape[1], pool0.shape[2]
    s = row.shape[1] * page
    r = jnp.arange(s, dtype=jnp.int32)
    ro = r % page
    phys = row[:, r // page]                                 # (L, S)
    phys = jnp.where(phys == 0, n_pages, phys)
    if apply is not None:
        phys = jnp.where(jnp.asarray(apply, bool), phys, n_pages)
    out = {"block": group["block"]}
    for name, leaf in group.items():
        if name == "block":
            continue
        if name.startswith("pool_"):
            vals = solo_group[name[len("pool_"):]][:, 0]     # (L, S, ...)
            out[name] = jax.vmap(
                lambda pl, ph, vl: pl.at[ph, ro].set(
                    vl.astype(pl.dtype), mode="drop"))(leaf, phys, vals)
        else:
            idx = [0] * leaf.ndim
            idx[1] = slot
            out[name] = gated_update_slice(
                leaf, solo_group[name].astype(leaf.dtype), tuple(idx),
                apply)
    return out


def _read_paged_group(group, slot):
    """Gather one paged slot back into the DENSE-layout batch-1 group.

    The inverse of ``_write_paged_group``: pool pages gather through the
    slot's block table into (L, 1, S, ...) leaves under their dense
    names — a paged snapshot is indistinguishable from a fixed-slot one
    (same packed-bytes contract, restorable by either engine).
    """
    row = _paged_slot_table(group, slot)                     # (L, P)
    out = {}
    for name, leaf in group.items():
        if name == "block":
            continue
        if name.startswith("pool_"):
            g = jax.vmap(lambda pl, bl: pl[bl])(leaf, row)   # (L,P,page,...)
            out[name[len("pool_"):]] = g.reshape(
                g.shape[0], 1, g.shape[1] * g.shape[2], *g.shape[3:])
        else:
            idx = [jnp.zeros((), jnp.int32)] * leaf.ndim
            idx[1] = jnp.asarray(slot, jnp.int32)
            sizes = list(leaf.shape)
            sizes[1] = 1
            out[name] = jax.lax.dynamic_slice(leaf, idx, sizes)
    return out


def write_cache_slot(cache: Dict[str, Any], solo: Dict[str, Any], slot,
                     apply=None):
    """Merge a batch-1 cache (from a batch-1 ``prefill``) into slot ``slot``.

    Every leaf of ``solo`` is size 1 along the batch axis; a traced-index
    ``dynamic_update_slice`` drops it into the live cache without touching
    neighbor slots — K/V rows, ring meta, SSM state and the slot's ``pos``
    all land atomically (one fused jit).  ``apply`` (traced bool) makes
    the whole merge a value-gated no-op (sharded owner masking — see
    ``common.gated_update_slice``).  ``solo`` is in the row layout
    ``read_cache_slot`` returns (``kvcache.slot_rows``).
    """
    new: Dict[str, Any] = {"pos": gated_update_slice(
        cache["pos"], jnp.asarray(solo["pos"], jnp.int32), (slot,), apply)}
    for name, group in cache.items():
        if name == "pos":
            continue
        if isinstance(group, dict) and "block" in group:
            new[name] = _write_paged_group(group, solo[name], slot, apply)
            continue
        axis = _batch_axis(name)

        def put(leaf, s_leaf):
            idx = [0] * leaf.ndim
            idx[axis] = slot
            return gated_update_slice(leaf, s_leaf.astype(leaf.dtype),
                                      tuple(idx), apply)

        new[name] = jax.tree.map(put, group, slot_store(solo[name], group))
    return new


def read_cache_slot(cache: Dict[str, Any], slot):
    """Slice ONE slot back out as a batch-1 cache (inverse of
    ``write_cache_slot``).

    Every leaf keeps its batch axis at size 1, so the result round-trips
    through ``write_cache_slot`` bit-exactly — packed NxFP bytes, ring
    meta and SSM state are sliced raw, never dequantized.  Shapes are
    slot-independent (one compiled program serves every slot), which is
    what makes live snapshot/migrate/restore cheap on the serving path.
    Packed leaves come back in the row layout (``kvcache.slot_rows``), so
    snapshots of the fixed-slot and the paged cache are alike.
    """
    out: Dict[str, Any] = {"pos": jax.lax.dynamic_slice(
        cache["pos"], (jnp.asarray(slot, jnp.int32),), (1,))}
    for name, group in cache.items():
        if name == "pos":
            continue
        if isinstance(group, dict) and "block" in group:
            out[name] = _read_paged_group(group, slot)
            continue
        axis = _batch_axis(name)

        def take(leaf):
            idx = [jnp.zeros((), jnp.int32)] * leaf.ndim
            idx[axis] = jnp.asarray(slot, jnp.int32)
            sizes = list(leaf.shape)
            sizes[axis] = 1
            return jax.lax.dynamic_slice(leaf, idx, sizes)

        out[name] = slot_rows(jax.tree.map(take, group))
    return out


def prefill_into_slot(cfg: ModelConfig, params: Params,
                      batch: Dict[str, Any], cache: Dict[str, Any], slot,
                      max_len: int, kv_fmt: Optional[str], apply=None,
                      act_fmt: Optional[str] = None):
    """Prefill ONE request (batch-1 inputs) into slot ``slot`` of a live cache.

    The prompt runs through the ordinary batch-1 ``prefill`` (so its K/V
    and logits are bit-identical to serving it alone), then its cache is
    scattered into the slot. Returns (last logits (1, V), new cache).
    ``apply`` (traced bool) gates the scatter only — the sharded engine
    runs this under a per-shard cond (owner-only admission) and lets the
    slot's owner alone commit the merge.  ``act_fmt`` (static) threads
    the quantized-activation prefill format (DESIGN.md §15); None keeps
    the graph byte-identical to the pre-tier engine.
    """
    assert batch["tokens"].shape[0] == 1, batch["tokens"].shape
    logits, solo = prefill(cfg, params, batch, max_len, kv_fmt,
                           act_fmt=act_fmt)
    solo = {n: slot_rows(g) if isinstance(g, dict) else g
            for n, g in solo.items()}
    return logits, write_cache_slot(cache, solo, slot, apply=apply)


def reset_slot(cfg: ModelConfig, cache: Dict[str, Any], slot, apply=None):
    """Park a finished slot: ``pos[slot] -> 0``, recurrent state zeroed.

    K/V rows are left stale on purpose — reads are masked to ``pos`` and
    admission overwrites the whole slot — but the ring pointer must stop
    growing (an unparked drained slot would eventually clamp-write at the
    buffer edge) and SSM state integrates forward unmasked, so both reset.
    ``apply`` (traced bool) owner-masks the park for the sharded engine.
    """
    new = dict(cache)
    new["pos"] = gated_update_slice(cache["pos"], jnp.zeros((1,), jnp.int32),
                                    (slot,), apply)
    layers = cache.get("layers")
    if layers is not None and "h" in layers:
        from .ssm import reset_state_slot
        h, conv = reset_state_slot(layers["h"], layers["conv"], slot,
                                   apply=apply)
        new["layers"] = dict(layers, h=h, conv=conv)
    return new
