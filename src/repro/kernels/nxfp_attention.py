"""Flash-decode attention over an NxFP-quantized KV cache (Pallas, TPU).

One new query token attends to a long cached context whose K/V tensors are
stored packed in NxFP (quantization blocks along head_dim, the qk^T
contraction dim). Decode attention at 32k-500k context is *memory-bound*:
wall time ~ KV bytes / HBM bandwidth, so streaming 4.34-bit codes instead of
16-bit values is a direct ~3.7x cut of the dominant roofline term — this
kernel is the paper's "smaller memory footprint" claim turned into serving
bandwidth.

Operands: the cache stacked over the model's layers, in the plane view
the serving cache stores (``cache_planes`` of the rows
``QTensor.quantize(k, fmt, axis=-1)`` emits; DESIGN.md §2.4):
  k_packed/v_packed: (L, B, KVH, Bg, D/P, S) uint8   byte planes
  k_meta/v_meta:     (L, B, KVH, NB, S)      uint16  NB = head_dim/32
  q:                 (B, KVH, G, D)                  G = q_heads / kv_heads
  lengths:           (B,) int32                      valid cache length per seq
  layer:             (1,) int32                      the layer attended

head_dim runs down the sublanes and the context along the lanes, so
every block is lane-dense and the block scale broadcasts down sublanes.
Code plane p holds head_dim indices ``P*j + p``: q is split the same way
for the scores, and the output comes back per plane and is re-interleaved
by the wrapper.  ``lengths`` and ``layer`` ride scalar prefetch (SMEM);
the K/V ``index_map`` reads the layer from there, so the kernel streams
one layer's tiles straight out of the whole stack and no layer slice is
ever materialised (DESIGN.md §7).

Grid: (B, KVH, S/TS); the context axis is sequential with the classic
online-softmax (m, l, acc) VMEM carry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BlockFormat
from .decode_lib import code_group, decode_planes

__all__ = ["nxfp_decode_attention_pallas", "cache_planes", "cache_rows"]

_NEG_INF = -1e30


def cache_planes(packed, meta, bg: int):
    """(..., B, S, KVH, NB, bpb) + (..., B, S, KVH, NB) -> ((..., B, KVH,
    Bg, D/P, S), (..., B, KVH, NB, S)) for groups of ``bg`` bytes: rows
    to the plane view."""
    *lead, b, s, kvh, nb, bpb = packed.shape
    n = len(lead)
    planes = packed.reshape(*lead, b, s, kvh, nb * bpb // bg, bg)
    perm = tuple(range(n)) + tuple(n + i for i in (0, 2, 4, 3, 1))
    return planes.transpose(perm), jnp.moveaxis(meta, n + 1, -1)


def cache_rows(planes, meta):
    """Inverse of ``cache_planes``: the plane view back to rows
    (..., B, S, KVH, NB, bpb) + (..., B, S, KVH, NB)."""
    *lead, b, kvh, bg, r, s = planes.shape
    n = len(lead)
    nb = meta.shape[-2]
    perm = tuple(range(n)) + tuple(n + i for i in (0, 4, 1, 3, 2))
    rows = planes.transpose(perm).reshape(*lead, b, s, kvh, nb,
                                          r * bg // nb)
    return rows, jnp.moveaxis(meta, -1, n + 1)


def _dequant(p_ref, m_ref, fmt: BlockFormat):
    """One (Bg, D/P, TS) packed tile + (NB, TS) meta -> P f32 (D/P, TS)."""
    b = p_ref[0, 0, 0].astype(jnp.int32)
    return decode_planes([b[q] for q in range(b.shape[0])], m_ref[0, 0, 0],
                         fmt)


def _kernel(len_ref, layer_ref, q_ref, kp_ref, km_ref, vp_ref, vm_ref,
            o_ref, m_scr, l_scr, acc_scr, *, fmt: BlockFormat, tile_s: int):
    b_idx, s_idx = pl.program_id(0), pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_planes = _dequant(kp_ref, km_ref, fmt)                # P x (D/P, TS)
    scores = None                                           # (G, TS)
    for p, k in enumerate(k_planes):
        sp = jax.lax.dot(q_ref[0, 0, p], k, preferred_element_type=jnp.float32)
        scores = sp if scores is None else scores + sp

    pos = s_idx * tile_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    valid = pos < len_ref[b_idx]
    scores = jnp.where(valid, scores, _NEG_INF)

    m_old = m_scr[...]                                      # (G, 1)
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    pr = jnp.where(valid, jnp.exp(scores - m_new), 0.0)     # (G, TS)

    for p, v in enumerate(_dequant(vp_ref, vm_ref, fmt)):   # (D/P, TS)
        acc_scr[p] = acc_scr[p] * alpha + jax.lax.dot_general(
            pr, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(s_idx == pl.num_programs(2) - 1)
    def _flush():
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)
        for p in range(acc_scr.shape[0]):
            o_ref[0, 0, p] = (acc_scr[p] * inv).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "tile_s", "interpret"))
def nxfp_decode_attention_pallas(q, k_packed, k_meta, v_packed, v_meta,
                                 lengths, layer, fmt: BlockFormat,
                                 tile_s: int = 512, interpret: bool = False):
    """Returns (B, KVH, G, D) f32 attention output (softmax scale on q)
    over layer ``layer`` (traced int32) of the stacked plane-view leaves."""
    b, kvh, g, d = q.shape
    _, bb, kvh2, bg, dp, s = k_packed.shape
    nb = k_meta.shape[-2]
    p_n = code_group(fmt.bits)[0]
    assert (bb, kvh2, bg) == (b, kvh, code_group(fmt.bits)[1])
    assert nb * fmt.block_size == d and dp * p_n == d
    assert s % tile_s == 0, (s, tile_s)
    # q plane p holds head_dim indices P*j + p, matching the code planes
    qp = q.astype(jnp.float32).reshape(b, kvh, g, dp, p_n) \
        .transpose(0, 1, 4, 2, 3)                           # (B, KVH, P, G, D/P)

    q_spec = pl.BlockSpec((1, 1, p_n, g, dp),
                          lambda i, j, k, lens, lay: (i, j, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, 1, bg, dp, tile_s),
                           lambda i, j, k, lens, lay: (lay[0], i, j, 0, 0, k))
    meta_spec = pl.BlockSpec((1, 1, 1, nb, tile_s),
                             lambda i, j, k, lens, lay: (lay[0], i, j, 0, k))
    out = pl.pallas_call(
        functools.partial(_kernel, fmt=fmt, tile_s=tile_s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kvh, s // tile_s),
            in_specs=[q_spec, kv_spec, meta_spec, kv_spec, meta_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((p_n, g, dp), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, p_n, g, dp), jnp.float32),
        interpret=interpret,
        name="nxfp_decode_attention",
    )(lengths.reshape(b).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qp, k_packed, k_meta,
      v_packed, v_meta)
    # (B, KVH, P, G, D/P) -> (B, KVH, G, D): re-interleave the planes
    return out.transpose(0, 1, 3, 4, 2).reshape(b, kvh, g, d)
