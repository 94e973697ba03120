"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import BlockFormat
from repro.core.pack import unpack_codes
from repro.core.quantize import dequantize_blocks, quantize_blocks

__all__ = ["qmatmul_ref", "qq_matmul_ref", "quantize_ref",
           "decode_attention_ref", "dequant_cache_ref"]


def qmatmul_ref(x, packed, meta, fmt: BlockFormat):
    """x (M, K) @ dequant(Wq) with bf16 operands, f32 accumulation.

    packed (N, KB, bpb) uint8, meta (N, KB) — the QTensor(axis=0) layout.
    """
    from . import ops as _ops
    codes = unpack_codes(packed, fmt.bits, fmt.block_size)
    w = dequantize_blocks(codes, meta, fmt, jnp.float32)    # (N, KB, 32)
    n, kb, b = w.shape
    w = w.reshape(n, kb * b).astype(jnp.bfloat16)           # (N, K)
    return jax.lax.dot_general(
        x.astype(jnp.bfloat16), w, (((1,), (1,)), ((), ())),
        preferred_element_type=getattr(_ops, "PSUM_DTYPE", None)
        or jnp.float32)


def qq_matmul_ref(x_packed, x_meta, x_fmt: BlockFormat,
                  w_packed, w_meta, w_fmt: BlockFormat):
    """dequant(Xq) @ dequant(Wq) — the numerics oracle for the qq kernel.

    x_packed (M, KB, bpb_x) / w_packed (N, KB, bpb_w), both quantized along
    the contraction dim (the activation QTensor's axis=-1 layout and the
    weight QTensor's axis=0 layout coincide after flattening lead dims).
    """
    from . import ops as _ops
    xc = unpack_codes(x_packed, x_fmt.bits, x_fmt.block_size)
    xd = dequantize_blocks(xc, x_meta, x_fmt, jnp.float32)   # (M, KB, B)
    m, kb, b = xd.shape
    xd = xd.reshape(m, kb * b).astype(jnp.bfloat16)          # (M, K)
    wc = unpack_codes(w_packed, w_fmt.bits, w_fmt.block_size)
    wd = dequantize_blocks(wc, w_meta, w_fmt, jnp.float32)   # (N, KB, B)
    n, kbw, bw = wd.shape
    wd = wd.reshape(n, kbw * bw).astype(jnp.bfloat16)        # (N, K)
    return jax.lax.dot_general(
        xd, wd, (((1,), (1,)), ((), ())),
        preferred_element_type=getattr(_ops, "PSUM_DTYPE", None)
        or jnp.float32)


def quantize_ref(xb, fmt: BlockFormat):
    """Blocked quantization oracle — the Algorithm-1 reference itself."""
    return quantize_blocks(xb, fmt)


def dequant_cache_ref(packed, meta, fmt: BlockFormat):
    """(B, S, KVH, NB, bpb) packed -> (B, S, KVH, D) f32."""
    codes = unpack_codes(packed, fmt.bits, fmt.block_size)
    vals = dequantize_blocks(codes, meta, fmt, jnp.float32)
    return vals.reshape(*vals.shape[:-2], vals.shape[-2] * vals.shape[-1])


def decode_attention_ref(q, k_packed, k_meta, v_packed, v_meta, lengths,
                         layer, fmt: BlockFormat):
    """Oracle for nxfp_decode_attention_pallas. q: (B, KVH, G, D) (pre-scaled).

    Layer ``layer`` of the stacked plane-view leaves (L, B, KVH, Bg, D/P,
    S) + (L, B, KVH, NB, S); full dequantization, exact softmax,
    per-sequence length masking.
    """
    from .nxfp_attention import cache_rows
    k = dequant_cache_ref(*cache_rows(k_packed[layer], k_meta[layer]), fmt)
    v = dequant_cache_ref(*cache_rows(v_packed[layer], v_meta[layer]), fmt)
    scores = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32), k,
                        preferred_element_type=jnp.float32)
    s = k.shape[1]
    valid = jnp.arange(s)[None, :] < lengths.reshape(-1, 1)  # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p, v,
                      preferred_element_type=jnp.float32)
