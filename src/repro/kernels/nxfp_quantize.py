"""Pallas fused block quantizer — Algorithm 1 (MSE search) + bit-pack.

Single-pass encode+pack on the VPU: per-block max, shared-exponent
extraction from float32 exponent bits, NanoMantissa rounding, and a
per-candidate (element format x nano) *arithmetic* grid snap — the kernel
body runs ``repro.core.quantize.arith_encode_blocks``, the exact code
behind ``quantize_blocks_arith``, so kernel/XLA bit-identity holds by
construction (same ops, same candidate order, same strict-less argmin).

Versus the seed three-pass pipeline (one-hot grid snap -> int32 codes to
HBM -> separate XLA repack), this kernel eliminates:

  * the one-hot matvec against VMEM-resident level tables, which
    materialized a (rows, block, levels) intermediate — up to ~256x the
    tile bytes for 8-bit formats — per candidate;
  * the int32 HBM round-trip: codes are packed to sub-byte lanes INSIDE
    the kernel (shift + constant 0/1-routing matmul over the 32-element
    block axis, exact in f32 — same layout as ``repro.core.pack``), so
    the kernel writes ``bits/8`` bytes per element instead of 4, an 8x/4x
    HBM write reduction at 4/8 bit before even counting the repack pass
    it replaces.

Element widths 4/5/6/8. 4/8-bit codes pack with a single routing matmul
(never straddle a byte); 5/6-bit codes straddle, so they add the spill
route — same layout, still scatter-free (DESIGN.md §2.4). 3-bit and
custom-recycle sweeps take the XLA arithmetic fallback in ops.py. Used on TPU for runtime casts that sit on the critical path:
per-step KV cache quantization and NxFP gradient compression before the
pod-axis all-reduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import BlockFormat
from repro.core.pack import bytes_per_block
from repro.core.quantize import arith_encode_blocks
from .decode_lib import byte_routes

__all__ = ["nxfp_quantize_pack_pallas"]


def _kernel(x_ref, packed_ref, meta_ref, *, fmt: BlockFormat):
    xb = x_ref[...].astype(jnp.float32)                     # (R, B)
    best_codes, best_meta = arith_encode_blocks(xb, fmt)

    bits, block_size = fmt.bits, fmt.block_size
    bpb = block_size * bits // 8
    if bits == 8:
        packed = best_codes
    else:
        # in-kernel sub-byte pack: shift each code to its in-byte offset,
        # then route to byte slots with constant (B, bpb) 0/1 matmuls —
        # disjoint bit-fields, so the f32 sums are exact bitwise ORs. A
        # straddling code (5/6-bit) sends its high bits to the next byte
        # through the spill route (core.pack.pack_layout).
        off = (jax.lax.broadcasted_iota(jnp.int32, xb.shape, 1) * bits) % 8
        shifted = best_codes << off
        lo_route, hi_route = byte_routes(block_size, bits, bpb)
        packed = jax.lax.dot((shifted & 0xFF).astype(jnp.float32), lo_route,
                             preferred_element_type=jnp.float32)
        if 8 % bits:
            packed += jax.lax.dot((shifted >> 8).astype(jnp.float32),
                                  hi_route,
                                  preferred_element_type=jnp.float32)
        packed = packed.astype(jnp.int32)
    packed_ref[...] = packed.astype(jnp.uint8)
    meta_ref[...] = best_meta[:, None]


@functools.partial(jax.jit, static_argnames=("fmt", "tile_rows", "interpret"))
def nxfp_quantize_pack_pallas(xb, fmt: BlockFormat, tile_rows: int = 256,
                              interpret: bool = False):
    """xb: (T, block_size) f32 blocks -> (packed uint8 (T, bpb), meta
    ``fmt.meta_dtype`` (T,)) — fused Algorithm-1 encode + bit-pack, one HBM
    write of ``bits/8`` bytes/element. Activation-side formats (asym/ox)
    ride the same body: ``arith_encode_blocks`` branches on the format and
    the extended meta word (26 bits max) still fits the int32 output. The
    wrapper in ops.py handles arbitrary shapes/axes.
    """
    t, b = xb.shape
    assert b == fmt.block_size
    assert fmt.bits in (4, 5, 6, 8), fmt
    assert not fmt.cr or fmt.recycle == "half_smallest", fmt
    bpb = bytes_per_block(b, fmt.bits)
    pad = (-t) % tile_rows
    if pad:
        xb = jnp.pad(xb, ((0, pad), (0, 0)))
    grid = ((t + pad) // tile_rows,)
    packed, meta = pl.pallas_call(
        functools.partial(_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_rows, b), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tile_rows, bpb), lambda i: (i, 0)),
            pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t + pad, bpb), jnp.uint8),
            jax.ShapeDtypeStruct((t + pad, 1), jnp.int32),
        ],
        interpret=interpret,
        name="nxfp_quantize",
    )(xb.astype(jnp.float32))
    return packed[:t], meta[:t, 0].astype(jnp.dtype(fmt.meta_dtype))
