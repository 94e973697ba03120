"""Public jit'd wrappers for the NxFP kernels with an impl switch.

``impl``:
  - "xla":    mathematically identical pure-jnp path (runs everywhere; used
              by the 512-device dry-run and any non-TPU backend).
  - "pallas": the TPU kernels (``interpret=True`` automatically on CPU so
              tests exercise the real kernel bodies).
  - None:     auto — pallas on TPU, xla elsewhere.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BlockFormat, get_format
from repro.core.pack import pack_codes
from repro.core.qtensor import QTensor, fmt_key
from repro.core.quantize import (quantize_blocks, quantize_blocks_arith,
                                 to_blocks)
from . import ref as kref
from .decode_lib import code_group
from .nxfp_attention import cache_planes, nxfp_decode_attention_pallas
from .nxfp_matmul import nxfp_matmul_pallas
from .nxfp_qq_matmul import nxfp_qq_matmul_pallas, qq_planes
from .nxfp_quantize import nxfp_quantize_pack_pallas

__all__ = ["qmatmul", "quantize_qtensor", "decode_attention",
           "decode_attention_planes"]

# Encoder selector for quantize_qtensor (§Perf / DESIGN.md §2.5): "arith"
# (default) = the fused pipeline — Pallas fused encode+pack where eligible,
# else the O(1)-memory exponent/ulp encoder + shift-or pack. "reference"
# = the FULL seed three-pass pipeline (searchsorted+take encode and
# scatter-add repack, never the fused kernel) so perf_iter's
# seed_quant/fused_quant A/B rows compare the real pre-ISSUE-1 baseline.
XLA_QUANT_ENCODER = "arith"

# Weight-stationary serving (§Perf): pin matmul activations replicated so
# GSPMD partial-sums over the weights' FSDP ('data') dim instead of
# all-gathering multi-GB weight shards every decode step. Activations at
# decode are tiny (B x d), weights are not.
REPLICATED_ACT_MATMUL = False

# Dot accumulation/partial-sum dtype (§Perf): bf16 halves the wire bytes of
# every row-parallel all-reduce (the cross-shard sum runs in bf16; each
# shard's MXU accumulation precision is unchanged on TPU). None = f32.
PSUM_DTYPE = None


def _resolve(impl: Optional[str]):
    if impl is not None:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Pallas-eligible element widths (DESIGN.md §2.5): every width whose code
# group (``decode_lib.code_group``) fits a block — 4/8-bit codes sit inside
# one byte, 5/6-bit codes straddle bytes within 5/3-byte groups.
_KERNEL_BITS = (4, 5, 6, 8)


def _arith_ok(fmt: BlockFormat) -> bool:
    """The arithmetic codecs — encoders and the kernels' decode — hard-code
    the default CR remap (DESIGN.md §2.3)."""
    return not fmt.cr or fmt.recycle == "half_smallest"


def _kernel_ok(fmt: BlockFormat) -> bool:
    return fmt.bits in _KERNEL_BITS and _arith_ok(fmt)


# Largest quantized tile a dequant kernel step may decode, in elements
# (weight TK x TN, activation TK x TM, cache D x TS).  A step holds it as
# an int32 byte view plus f32 and bf16 planes, ~8 bytes an element at
# 4-bit, so the largest preferred tile (1024 x 512) takes ~4 MiB of v5e's
# 16 MiB scoped VMEM.  Shapes whose only legal tile is a larger whole dim
# (no preferred tile divides them, e.g. hymba-1.5b's 5504 x 1600 down
# projection) take the XLA path (DESIGN.md §2.5).
_MAX_TILE_ELEMS = 1024 * 512


def _tile(dim: int, prefs) -> int:
    """Largest preferred tile dividing ``dim``, else the whole dim.

    Every preference keeps the kernel blocks (8, 128)-aligned; a whole
    dim is always a legal block, but may exceed ``_MAX_TILE_ELEMS``."""
    return next((t for t in prefs if dim % t == 0), dim)


def _tile_m(m: int) -> int:
    """Row tile: one tile up to 256 rows (block == padded array), else 256."""
    return -(-m // 8) * 8 if m <= 256 else 256


def _tile_k(k: int, p_n: int, lane_dense: bool) -> int:
    """Contraction tile: TK/32 meta rows a multiple of 16 (uint16 tiling)
    and TK/P plane rows a multiple of 32 (uint8 tiling) — or of 128 where
    the activation planes put TK/P on lanes (``lane_dense``)."""
    step = 128 if lane_dense else 32
    return _tile(k, tuple(t for t in (1024, 512) if (t // p_n) % step == 0))


def qmatmul(x, w, impl: Optional[str] = None):
    """x (..., K) @ w, where w is a QTensor (quantized along axis 0 of (K, N))
    or a plain dense array. Returns (..., N) f32.

    ``x`` may itself be a QTensor quantized along axis -1 (an activation
    tensor from ``quantize_qtensor``): with a quantized ``w`` the GEMM runs
    quantized x quantized (fused dual-dequant Pallas kernel where eligible,
    ``qq_matmul_ref`` otherwise); with a dense ``w`` the activation is
    dequantized once and takes the dense dot (the XLA serving tier keeps
    recycled dense weights, so only the activation side is quantized —
    DESIGN.md §15)."""
    if isinstance(x, QTensor):
        return _qact_matmul(x, w, impl)
    if not isinstance(w, QTensor):
        return jax.lax.dot_general(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=PSUM_DTYPE or jnp.float32)
    impl = _resolve(impl)
    # derive dims from the children (aux .shape may be stale after scan
    # slicing of stacked-layer weights); layout is (N, KB, bpb)
    assert w.packed.ndim == 3, w.packed.shape
    n = w.packed.shape[0]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if REPLICATED_ACT_MATMUL:
        # batch dim replicated (so GSPMD partial-sums over the weights'
        # 'data' shards instead of gathering them); feature dim left to the
        # partitioner (keeps the d_ff hidden 'model'-sharded in MLPs).
        from jax.sharding import PartitionSpec as P
        x2 = jax.lax.with_sharding_constraint(
            x2, P(None, P.UNCONSTRAINED))
    kb = w.packed.shape[-2]
    k_pad = kb * w.fmt.block_size
    if x2.shape[-1] < k_pad:  # quantization padded K to a block multiple
        x2 = jnp.pad(x2, ((0, 0), (0, k_pad - x2.shape[-1])))

    tn = _tile(n, (512, 256, 128))
    tk = _tile_k(k_pad, code_group(w.fmt.bits)[0], True)
    if (impl == "pallas" and _kernel_ok(w.fmt)
            and tk * tn <= _MAX_TILE_ELEMS):
        y = nxfp_matmul_pallas(
            x2, w.packed, w.meta, w.fmt, tile_m=_tile_m(x2.shape[0]),
            tile_n=tn, tile_k=tk, interpret=_interpret())
        return y.reshape(*lead, n)
    y = kref.qmatmul_ref(x2, w.packed, w.meta, w.fmt)
    return y.reshape(*lead, n)


def _qact_matmul(xq: QTensor, w, impl: Optional[str]):
    """Quantized-activation GEMM body (x is a QTensor, axis=-1)."""
    assert xq.axis == -1, f"activation QTensor must quantize axis -1: {xq.axis}"
    if not isinstance(w, QTensor):
        # dense-weight tier: decode the activation once (direct-cast error
        # already paid at encode) and ride the ordinary bf16 dot.
        return qmatmul(xq.dequantize(jnp.bfloat16), w, impl)
    impl = _resolve(impl)
    x_fmt, w_fmt = xq.fmt, w.fmt
    assert x_fmt.block_size == w_fmt.block_size, (x_fmt, w_fmt)
    lead = tuple(xq.shape[:-1])
    kb = xq.packed.shape[-2]
    xp = xq.packed.reshape(-1, kb, xq.packed.shape[-1])
    xm = xq.meta.reshape(-1, kb)
    assert w.packed.ndim == 3 and w.packed.shape[-2] == kb, (
        xq.packed.shape, w.packed.shape)
    n = w.packed.shape[0]
    k_pad = kb * x_fmt.block_size

    tn = _tile(n, (512, 256, 128))
    tk = _tile_k(k_pad, qq_planes(x_fmt, w_fmt)[0], False)
    if (impl == "pallas" and _kernel_ok(x_fmt) and _kernel_ok(w_fmt)
            and tk * max(tn, _tile_m(xp.shape[0])) <= _MAX_TILE_ELEMS):
        y = nxfp_qq_matmul_pallas(
            xp, xm, w.packed, w.meta, x_fmt, w_fmt,
            tile_m=_tile_m(xp.shape[0]), tile_n=tn, tile_k=tk,
            interpret=_interpret())
        return y.reshape(*lead, n)
    y = kref.qq_matmul_ref(xp, xm, x_fmt, w.packed, w.meta, w_fmt)
    return y.reshape(*lead, n)


def quantize_qtensor(x, fmt, axis: int = -1,
                     impl: Optional[str] = None) -> QTensor:
    """Quantize a dense array to a QTensor — fused encode+pack hot path.

    ``impl="pallas"`` (4/5/6/8-bit): one fused kernel emits packed uint8 +
    uint16 meta directly — no int32 codes ever reach HBM and no separate
    repack pass runs (§2.4).
    Everything else (non-TPU backends, 3-bit, custom recycle sweeps) takes
    the XLA path: the arithmetic encoder + the gather/scatter-free
    shift-or pack.
    """
    if isinstance(fmt, str):
        fmt = get_format(fmt)
    impl = _resolve(impl)
    axis = axis if axis < 0 else axis - x.ndim
    xb, orig = to_blocks(x, fmt.block_size, axis)
    key = fmt_key(fmt)
    if XLA_QUANT_ENCODER == "reference":
        # faithful seed pipeline for A/B rows: table-driven encode AND the
        # scatter-add repack, bypassing the fused kernel on every backend
        from repro.core.pack import pack_codes_scatter
        codes, meta = quantize_blocks(xb, fmt)
        return QTensor(pack_codes_scatter(codes, fmt.bits), meta, key,
                       tuple(x.shape), axis, orig)
    if impl == "pallas" and _kernel_ok(fmt):
        flat = xb.reshape(-1, fmt.block_size)
        packed, meta = nxfp_quantize_pack_pallas(
            flat.astype(jnp.float32), fmt, interpret=_interpret())
        packed = packed.reshape(*xb.shape[:-1], packed.shape[-1])
        meta = meta.reshape(xb.shape[:-1])
        return QTensor(packed, meta, key, tuple(x.shape), axis, orig)
    if _arith_ok(fmt):
        codes, meta = quantize_blocks_arith(xb, fmt)
    else:  # custom recycle sweeps: table-driven encode, modern pack
        codes, meta = quantize_blocks(xb, fmt)
    return QTensor(pack_codes(codes, fmt.bits), meta, key,
                   tuple(x.shape), axis, orig)


def decode_attention(q, kq: QTensor, vq: QTensor, lengths, n_kv_heads: int,
                     impl: Optional[str] = None):
    """Single-token attention over a quantized KV cache.

    q: (B, H, D) — unscaled query for the new token.
    kq/vq: QTensor of the (B, S, KVH, D) cache, quantized along axis -1
      (its row layout, taken to the plane view here).
    lengths: (B,) int32 valid context lengths.
    Returns (B, H, D) f32.
    """
    bg = code_group(kq.fmt.bits)[1]
    kp, km = cache_planes(kq.packed, kq.meta, bg)
    vp, vm = cache_planes(vq.packed, vq.meta, bg)
    assert km.shape[1] == n_kv_heads, (km.shape, n_kv_heads)
    return decode_attention_planes(q, kp, km, vp, vm, lengths, kq.fmt,
                                   impl=impl)


def decode_attention_planes(q, k_packed, k_meta, v_packed, v_meta, lengths,
                            fmt: BlockFormat, layer=None,
                            impl: Optional[str] = None):
    """``decode_attention`` over the serving cache's stored plane view.

    k_packed/v_packed (L, B, KVH, Bg, D/P, S) uint8 and k_meta/v_meta
    (L, B, KVH, NB, S) uint16 are the whole stack, of which layer
    ``layer`` (traced int32) is attended, read in place by the kernel's
    index map; with ``layer=None`` they are one layer's (no L axis).
    Returns (B, H, D) f32.
    """
    if layer is None:
        k_packed, k_meta, v_packed, v_meta = (
            x[None] for x in (k_packed, k_meta, v_packed, v_meta))
        layer = 0
    impl = _resolve(impl)
    b, h, d = q.shape
    kvh = k_meta.shape[2]
    g = h // kvh
    qg = (q.reshape(b, kvh, g, d).astype(jnp.float32) *
          np.float32(1.0 / np.sqrt(d)))
    lengths2 = lengths.reshape(b, 1).astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    # quantization pads head_dim to a block multiple; pad q to match (the
    # padded K dims dequantize to 0 so scores are unchanged) & slice out.
    d_pad = k_meta.shape[-2] * fmt.block_size
    if d_pad != d:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, d_pad - d)))
    ts = _tile(k_meta.shape[-1], (512, 256, 128))
    leaves = (k_packed, k_meta, v_packed, v_meta)
    if impl == "pallas" and _kernel_ok(fmt) and d_pad * ts <= _MAX_TILE_ELEMS:
        out = nxfp_decode_attention_pallas(
            qg, *leaves, lengths2, layer, fmt, tile_s=ts,
            interpret=_interpret())
    else:
        out = kref.decode_attention_ref(qg, *leaves, lengths2, layer, fmt)
    return out[..., :d].reshape(b, h, d)
