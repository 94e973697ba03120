"""Plain float32 reference of a dense decoder: GQA attention with rotary
positions, SwiGLU MLP, RMSNorm, untied embedding and head.

It imports nothing of the program and takes nothing the program made.  It
draws the same random weights from the seed, following the program's
documented initialisation stream (``model_key``, then ``jax.random.split``
into embed / head / layers / ..., one key per layer, normal * 0.02 with the
output projections scaled by ``1 / sqrt(2 * n_layers)``), and casts them to
the formats the configuration states with its own NxFP4 cast
(``reference/nxfp.py``): every layer matrix NxFP4 along its input axis,
embedding and head stored in bfloat16, K and V NxFP4 along the head axis.
All arithmetic is float32 at ``highest`` matmul precision.

Attention follows the served path: prompt rows attend over unquantized K/V
(the prefill lane's scratch), rows of served tokens over the NxFP4 cache.

``widest_gaps`` runs the stack layer by layer over prompts + served tokens
(teacher forcing) and reads, at each position whose next token was served,
how far the served token's logit lies below the reference's best: the
widest such gap and their mean.  Each named stream of ``STREAMS`` runs
beside it: the same reference with every matmul input (and the embedding
and head) rounded to a lower precision -- or, in scope ``all``, every
activation besides: the residual stream, q/k/v, attention probabilities,
the MLP's hidden values -- and reads the same of the token that stream
puts first.  ``control`` takes the configuration's bfloat16 down to
float8 e4m3 (the control the check must fail); ``bf16`` and ``bf16_all``
round to the configuration's own bfloat16 (what rounding alone does).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.nxfp import fake_quant

HI = jax.lax.Precision.HIGHEST
STREAMS = {"control": ("float8_e4m3fn", "matmul"),
           "bf16": ("bfloat16", "matmul"),
           "bf16_all": ("bfloat16", "all")}


def model_key(seed: int):
    """The weights' root key for ``--seed`` (any size of whole number)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def _split(key, names):
    return dict(zip(names, jax.random.split(key, len(names))))


def _dims(m):
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return m["d_model"], m["n_heads"], m["n_kv_heads"], hd, m["d_ff"]


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _weight(key, shape, scale):
    """One (K, N) matrix drawn and cast to NxFP4 along K, a block of
    columns at a time (the cast's candidates need several times the
    matrix in temporaries)."""
    k, n = shape
    cb = math.gcd(n, 512)
    w = jax.random.normal(key, shape, jnp.float32) * scale
    blocks = w.reshape(k, n // cb, cb).transpose(1, 0, 2)
    q = jax.lax.map(lambda b: fake_quant(b, axis=0), blocks)
    return q.transpose(1, 0, 2).reshape(k, n)


def _layer_weights(key, md):
    """One layer's matrices, drawn and cast one at a time (the largest,
    deepseek's 8192 x 22016, is 0.7 GB in float32)."""
    d, h, kvh, hd, ff = _dims(md)
    out = 0.02 / math.sqrt(2 * md["n_layers"])
    k = _split(key, ["attn", "ffn", "ssm", "cross"])
    a = _split(k["attn"], ["q", "k", "v", "o"])
    f = _split(k["ffn"], ["w1", "w3", "w2"])
    return {"wq": _weight(a["q"], (d, h * hd), 0.02),
            "wk": _weight(a["k"], (d, kvh * hd), 0.02),
            "wv": _weight(a["v"], (d, kvh * hd), 0.02),
            "wo": _weight(a["o"], (h * hd, d), out),
            "w1": _weight(f["w1"], (d, ff), 0.02),
            "w3": _weight(f["w3"], (d, ff), 0.02),
            "w2": _weight(f["w2"], (ff, d), out)}


def _lowp(a, dtype):
    """``a`` rounded to ``dtype`` (a dtype name of ``STREAMS``; None
    leaves it float32)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _rounders(lowp):
    """(matmul-input rounding, activation rounding) of a stream's
    ``(dtype, scope)``; None rounds nothing."""
    dtype, scope = lowp or (None, None)
    lp = functools.partial(_lowp, dtype=dtype)
    return lp, (lp if scope == "all" else (lambda a: a))


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "lowp", "rows"))
def _layer(x, w, n_prompt, m, lowp, rows):
    """One layer over (B, S, D) rows; ``n_prompt`` (B,) prompt lengths."""
    md = dict(m)
    d, h, kvh, hd, _ = _dims(md)
    b, s, _ = x.shape
    g = h // kvh
    dot = functools.partial(jnp.matmul, precision=HI)
    lp, act = _rounders(lowp)
    hn = lp(_rms(x, md["norm_eps"]))
    q = act(dot(hn, w["wq"])).reshape(b, s, h, hd)
    k = act(dot(hn, w["wk"])).reshape(b, s, kvh, hd)
    v = act(dot(hn, w["wv"])).reshape(b, s, kvh, hd)
    pos = jnp.arange(s, dtype=jnp.float32)
    inv = 1.0 / (md["rope_theta"] ** (jnp.arange(hd // 2, dtype=jnp.float32)
                                      / (hd // 2)))
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    q = act(_rope(q, cos, sin) / math.sqrt(hd))
    k = act(_rope(k, cos, sin))
    kq, vq = fake_quant(k, axis=-1), fake_quant(v, axis=-1)

    def block(i):
        r0 = i * rows
        qb = jax.lax.dynamic_slice_in_dim(q, r0, rows, axis=1)
        qb = qb.reshape(b, rows, kvh, g, hd)
        qpos = r0 + jnp.arange(rows)
        causal = jnp.arange(s)[None, :] <= qpos[:, None]            # (R, S)
        decode = qpos[None, :] >= n_prompt[:, None]                 # (B, R)

        def attend(kk, vv):
            sc = jnp.einsum("brkgd,bskd->bkgrs", qb, kk, precision=HI)
            sc = jnp.where(causal[None, None, None], sc, -jnp.inf)
            p = act(jax.nn.softmax(sc, axis=-1))
            return jnp.einsum("bkgrs,bskd->brkgd", p, vv, precision=HI)

        o = jnp.where(decode[:, :, None, None, None], attend(kq, vq),
                      attend(k, v))
        return o.reshape(b, rows, h * hd)

    o = jax.lax.map(block, jnp.arange(s // rows))          # (nb, B, R, H*hd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, h * hd)
    x = act(x + dot(lp(o), w["wo"]))

    def mlp(xb):                                    # (R, D) rows at a time
        h2 = lp(_rms(xb, md["norm_eps"]))
        gate = act(jax.nn.silu(act(dot(h2, w["w1"])))
                   * act(dot(h2, w["w3"])))
        return act(xb + dot(lp(gate), w["w2"]))

    xr = x.reshape(-1, rows, d)
    return jax.lax.map(mlp, xr).reshape(b, s, d)


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _logits(x, head, rows_b, rows_t, m, lowp):
    lp, _ = _rounders(lowp)
    hn = _rms(x[rows_b, rows_t], dict(m)["norm_eps"])
    return jnp.matmul(lp(hn), lp(head), precision=HI)


@functools.partial(jax.jit, static_argnames=("m",))
def _top(key, m):
    md = dict(m)
    ks = _split(key, ["embed", "head", "layers", "enc", "cross", "pos"])
    d, v = md["d_model"], md["vocab"]
    emb = (jax.random.normal(ks["embed"], (v, d), jnp.float32) * 0.02
           ).astype(jnp.bfloat16)
    head = (jax.random.normal(ks["head"], (d, v), jnp.float32) * 0.02
            ).astype(jnp.bfloat16)
    return emb, head, jax.random.split(ks["layers"], md["n_layers"])


def widest_gaps(model: dict, seed: int, seqs, seq_len: int, batch: int,
                streams=()) -> dict:
    """``seqs``: list of (prompt, served) int arrays, at most ``batch`` of
    them, each prompt + served at most ``seq_len`` long (fixed shapes, so
    the reference compiles once per cell).  Returns the widest and mean gap
    of the served tokens, with counts, and the same of each stream's first
    choices under ``<stream>_widest_gap`` etc."""
    unknown = set(streams) - set(STREAMS)
    if unknown:
        raise ValueError(f"unknown streams {sorted(unknown)}")
    if not seqs or len(seqs) > batch:
        raise ValueError(f"need 1..{batch} sequences, got {len(seqs)}")
    m = tuple(sorted((k, v) for k, v in model.items()
                     if isinstance(v, (int, float))
                     and not isinstance(v, bool)))
    rows = 128
    s = -(-seq_len // rows) * rows
    toks = np.zeros((batch, s), np.int32)
    n_prompt = np.full((batch,), s, np.int32)
    rows_b, rows_t, served = [], [], []
    for i, (p, o) in enumerate(seqs):
        p, o = np.asarray(p, np.int32), np.asarray(o, np.int32)
        if len(p) + len(o) > s or len(o) < 1:
            raise ValueError(f"sequence {i}: {len(p)} + {len(o)} tokens")
        full = np.concatenate([p, o[:-1]])
        toks[i, :len(full)] = full
        n_prompt[i] = len(p)
        rows_b += [i] * len(o)
        rows_t += list(range(len(p) - 1, len(p) - 1 + len(o)))
        served += list(o)
    emb, head, layer_keys = _top(model_key(seed), m)
    x = emb[jnp.asarray(toks)].astype(jnp.float32)
    xs = {n: _rounders(STREAMS[n])[0](x) for n in streams}
    del emb
    npj = jnp.asarray(n_prompt)
    for lk in layer_keys:
        w = _layer_weights(lk, dict(m))
        x = _layer(x, w, npj, m, None, rows)
        xs = {n: _layer(v, w, npj, m, STREAMS[n], rows)
              for n, v in xs.items()}
        del w
    head = head.astype(jnp.float32)
    rb, rt = jnp.asarray(rows_b), jnp.asarray(rows_t)
    ref = _logits(x, head, rb, rt, m, None)
    best = jnp.max(ref, axis=-1)
    served = jnp.asarray(served)
    gap = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    out = {"widest_gap": float(jnp.max(gap)), "mean_gap": float(jnp.mean(gap)),
           "tokens": int(gap.shape[0]),
           "agree": int(jnp.sum(jnp.argmax(ref, axis=-1) == served))}
    for n, v in xs.items():
        first = jnp.argmax(_logits(v, head, rb, rt, m, STREAMS[n]), axis=-1)
        sgap = best - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
        out.update({f"{n}_widest_gap": float(jnp.max(sgap)),
                    f"{n}_mean_gap": float(jnp.mean(sgap)),
                    f"{n}_agree": int(jnp.sum(sgap == 0))})
    return out
