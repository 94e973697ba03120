"""Fused quantized x quantized GEMM (Pallas, TPU target) — DESIGN.md §15.

Computes ``y = dequant(Xq) @ dequant(Wq)`` where BOTH operands stream
*packed* HBM -> VMEM: the activation tensor is quantized along its feature
(contraction) axis by the fused quantizer (``nxfp_quantize.py``, AMXFP/ox
activation formats), the weight along axis 0 of its (K, N) layout as in
``nxfp_matmul.py``. Each grid step decodes one activation tile and one
weight tile arithmetically on the VPU (dual decode tile) and feeds the MAC
on the MXU — prefill GEMM HBM traffic drops to ``(bits_x + bits_w)/32`` of
the bf16 baseline and the separate dequant->matmul round trip for
activations disappears.

Storage (both produced by ``quantize_qtensor``):

  x packed: (M, KB, bpb_x) uint8   blocks along the contraction dim
  x meta:   (M, KB) uint16/uint32  (int32 in-kernel; asym meta is 26 bits)
  w packed: (N, KB, bpb_w) uint8
  w meta:   (N, KB) uint16

Kernel view: both operands in the plane layout of ``nxfp_matmul.py`` (K
down the sublanes), split into the SAME P code planes — P is the lcm of
the two widths' group sizes (``code_group(bits, p_min)``) — so plane p of
each operand holds K indices ``P*j + p`` and the product is the sum over
planes of ``dequant(x_p)^T @ dequant(w_p)``.

Tiling: grid (M/TM, N/TN, K/TK), K innermost; TM/TN multiples of 128 (or
the whole dim), TK/P a multiple of 8 and TK/32 of 16 (or K).  Zero-padded
packed rows decode to exact zeros (meta 0 keeps the ox substitution gate
off), so M padding is free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BlockFormat
from .decode_lib import code_group, decode_planes
from .nxfp_matmul import weight_planes

__all__ = ["nxfp_qq_matmul_pallas", "qq_planes"]


def qq_planes(x_fmt: BlockFormat, w_fmt: BlockFormat):
    """(P, Bg_x, Bg_w): the shared plane count and each operand's group."""
    p_n = code_group(w_fmt.bits, code_group(x_fmt.bits)[0])[0]
    return p_n, code_group(x_fmt.bits, p_n)[1], code_group(w_fmt.bits, p_n)[1]


def _dequant(p_ref, m_ref, fmt: BlockFormat):
    b = p_ref[...].astype(jnp.int32)                         # (Bg, TK/P, T)
    return decode_planes([b[q] for q in range(b.shape[0])], m_ref[...], fmt)


def _kernel(xp_ref, xm_ref, wp_ref, wm_ref, o_ref, acc_ref, *,
            x_fmt: BlockFormat, w_fmt: BlockFormat):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc = acc_ref[...]
    for xt, wt in zip(_dequant(xp_ref, xm_ref, x_fmt),      # (TK/P, TM)
                      _dequant(wp_ref, wm_ref, w_fmt)):     # (TK/P, TN)
        acc += jax.lax.dot_general(
            xt.astype(jnp.bfloat16), wt.astype(jnp.bfloat16),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("x_fmt", "w_fmt", "tile_m", "tile_n", "tile_k",
                     "interpret", "out_dtype"))
def nxfp_qq_matmul_pallas(x_packed, x_meta, w_packed, w_meta,
                          x_fmt: BlockFormat, w_fmt: BlockFormat,
                          tile_m: int = 128, tile_n: int = 128,
                          tile_k: int = 512, interpret: bool = False,
                          out_dtype=jnp.float32):
    """Both operands packed; returns (M, N) ``out_dtype``.

    M is padded internally (zero meta rows decode to zeros); K and N must
    be multiples of the chosen tiles (wrapper in ops.py adapts tile sizes).
    """
    assert x_fmt.block_size == w_fmt.block_size, (x_fmt, w_fmt)
    m, kb, bpb_x = x_packed.shape
    n, kb_w, bpb_w = w_packed.shape
    assert kb == kb_w, (x_packed.shape, w_packed.shape)
    assert bpb_x == x_fmt.bytes_per_block and bpb_w == w_fmt.bytes_per_block

    k_dim = kb * x_fmt.block_size
    pad_m = (-m) % tile_m
    if pad_m:
        x_packed = jnp.pad(x_packed, ((0, pad_m), (0, 0), (0, 0)))
        x_meta = jnp.pad(x_meta, ((0, pad_m), (0, 0)))
    assert k_dim % tile_k == 0 and n % tile_n == 0, (k_dim, n, tile_k, tile_n)
    p_n, bg_x, bg_w = qq_planes(x_fmt, w_fmt)
    xp, xm = weight_planes(x_packed, x_meta.astype(jnp.int32), bg_x)
    wp, wm = weight_planes(w_packed, w_meta, bg_w)
    tkp, kb_t = tile_k // p_n, tile_k // x_fmt.block_size

    grid = ((m + pad_m) // tile_m, n // tile_n, k_dim // tile_k)
    out = pl.pallas_call(
        functools.partial(_kernel, x_fmt=x_fmt, w_fmt=w_fmt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bg_x, tkp, tile_m), lambda i, j, k: (0, k, i)),
            pl.BlockSpec((kb_t, tile_m), lambda i, j, k: (k, i)),
            pl.BlockSpec((bg_w, tkp, tile_n), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((kb_t, tile_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        interpret=interpret,
        name="nxfp_qq_matmul",
    )(xp, xm, wp, wm)
    return out[:m]
