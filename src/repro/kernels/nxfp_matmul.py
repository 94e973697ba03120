"""Fused on-the-fly NxFP dequantization GEMM (Pallas, TPU target).

Computes ``y = x @ dequant(Wq)`` where ``Wq`` is an NxFP/MxFP/BFP-quantized
weight stored *packed* in HBM. This is the paper's deployment kernel
(Fig. 7): compressed codes stream HBM -> VMEM, fields are sliced and decoded
arithmetically on the VPU, the NanoMantissa/shared-exponent scale is applied,
the tile is cast to bf16, and the MAC runs on the MXU — so HBM traffic for
weights is ~bits/16 of the bf16 baseline.

Storage (``QTensor.quantize(w, fmt, axis=0)`` for a (K, N) weight):

  packed: (N, KB, bpb) uint8   KB = K/32 blocks along the contraction dim
  meta:   (N, KB) uint16

Kernel view (``weight_planes``; DESIGN.md §2.4): K runs down the sublanes
and N along the lanes, split into code planes — byte plane q of the group
``(Bg, K/P, N)`` and meta ``(KB, N)``.  Code plane p holds K indices
``P*j + p``, so the activation is split the same way (``act_planes``,
``(P, M, K/P)``) and the GEMM is the sum over planes of
``x_p @ dequant(w_p)``.  Every block is lane-dense, the block scale
broadcasts down sublanes, and nothing in the body reshapes across lanes.

Tiling: grid (M/TM, N/TN, K/TK), K innermost and sequential with an f32
VMEM accumulator.  ``ops.qmatmul`` picks tiles that Mosaic accepts: TN a
multiple of 128 (or N), TK/P a multiple of 128 and TK/32 of 16 (or K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BlockFormat
from .decode_lib import code_group, decode_planes

__all__ = ["nxfp_matmul_pallas", "weight_planes", "act_planes"]

def weight_planes(packed, meta, bg: int):
    """(N, KB, bpb) packed + (N, KB) meta -> ((Bg, K/P, N), (KB, N)) for
    groups of ``bg`` bytes."""
    n, kb, bpb = packed.shape
    return packed.reshape(n, kb * bpb // bg, bg).transpose(2, 1, 0), meta.T


def act_planes(x, p_n: int):
    """(M, K) -> (P, M, K/P): plane p holds columns P*j + p."""
    m, k = x.shape
    return x.reshape(m, k // p_n, p_n).transpose(2, 0, 1)


def _kernel(x_ref, w_ref, m_ref, o_ref, acc_ref, *, fmt: BlockFormat):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = w_ref[...].astype(jnp.int32)                      # (Bg, TK/P, TN)
    planes = decode_planes([b[q] for q in range(b.shape[0])], m_ref[...],
                           fmt)
    acc = acc_ref[...]
    for p, w in enumerate(planes):                        # (TK/P, TN) each
        acc += jax.lax.dot(x_ref[p], w.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "tile_m", "tile_n", "tile_k", "interpret",
                     "out_dtype"))
def nxfp_matmul_pallas(x, packed, meta, fmt: BlockFormat,
                       tile_m: int = 128, tile_n: int = 128,
                       tile_k: int = 512, interpret: bool = False,
                       out_dtype=jnp.float32):
    """x: (M, K) bf16/f32; packed: (N, KB, bpb) uint8; meta: (N, KB) u16.

    Returns (M, N) ``out_dtype``. M is padded internally; K and N must be
    multiples of the chosen tiles (wrapper in ops.py adapts tile sizes).
    """
    m, k_dim = x.shape
    n, kb, bpb = packed.shape
    assert kb * fmt.block_size == k_dim, (packed.shape, x.shape)
    assert bpb == fmt.bytes_per_block
    p_n, bg = code_group(fmt.bits)
    assert k_dim % tile_k == 0 and n % tile_n == 0, (x.shape, n, tile_k, tile_n)
    assert tile_k % fmt.block_size == 0, (tile_k, fmt.block_size)

    pad_m = (-m) % tile_m
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    xp = act_planes(x.astype(jnp.bfloat16), p_n)
    wp, mt = weight_planes(packed, meta, bg)
    tkp, kb_t = tile_k // p_n, tile_k // fmt.block_size

    grid = ((m + pad_m) // tile_m, n // tile_n, k_dim // tile_k)
    out = pl.pallas_call(
        functools.partial(_kernel, fmt=fmt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p_n, tile_m, tkp), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((bg, tkp, tile_n), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((kb_t, tile_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        interpret=interpret,
        name="nxfp_matmul",
    )(xp, wp, mt)
    return out[:m]
