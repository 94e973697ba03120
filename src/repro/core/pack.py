"""Sub-byte bit-packing of element codes into per-block byte buffers.

Codes are packed *per quantization block* so a block of 32 k-bit codes is
exactly ``4*k`` bytes and no code ever straddles a block (hence never a
device-shard) boundary. Within a block, codes are laid out little-endian at
bit offsets ``i*k``; a code can straddle at most two bytes (k <= 8).

Implementation (DESIGN.md §2.4): pack and unpack are *gather- AND
scatter-free* shift-or reductions. Each code contributes
``(code << s) & 0xFF`` to its low byte and ``(code << s) >> 8`` to its
high byte; routing contributions to byte slots is a pair of tiny constant
0/1 matmuls over the block axis. The routed bit-fields are disjoint, so
the float32 sums are exact bitwise-ORs (every byte < 256, integer-exact in
f32). This lowers to vector shifts plus one small dot on every backend —
no scatter-add (which serializes and lowers poorly in XLA) and no gather
(which the SPMD partitioner rejects inside the pod-axis shard_map of the
gradient-compression wire path). The same layout constants drive the
in-kernel pack of the fused Pallas quantizer.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["pack_codes", "unpack_codes", "bytes_per_block", "byte_fold"]


def bytes_per_block(block_size: int, bits: int) -> int:
    total = block_size * bits
    assert total % 8 == 0, (block_size, bits)
    return total // 8


@lru_cache(maxsize=None)
def pack_layout(block_size: int, bits: int):
    """Static shift-or layout for (block_size, bits).

    Returns (off, lo_route, hi_route, bpb):
      off:      (B,) int32 — bit offset of code i within its low byte.
      lo_route: (B, bpb) f32 0/1 — code i's low-byte slot.
      hi_route: (B, bpb) f32 0/1 — code i's spill-byte slot (clamped to the
                last byte when there is no spill; the spill contribution is
                0 there, identically to the old scatter layout).
    """
    p = np.arange(block_size) * bits
    lo = p // 8
    off = (p % 8).astype(np.int32)
    bpb = bytes_per_block(block_size, bits)
    hi = np.minimum(lo + 1, bpb - 1)
    lo_route = np.zeros((block_size, bpb), np.float32)
    hi_route = np.zeros((block_size, bpb), np.float32)
    lo_route[np.arange(block_size), lo] = 1.0
    hi_route[np.arange(block_size), hi] = 1.0
    return off, lo_route, hi_route, bpb


def pack_codes(codes, bits: int):
    """(..., nb, B) uint8 codes -> (..., nb, B*bits//8) uint8 bytes."""
    if bits == 8:  # bytes ARE the codes; skip the identity routing matmul
        return codes.astype(jnp.uint8)
    B = codes.shape[-1]
    off, lo_route, hi_route, _ = pack_layout(B, bits)
    shifted = codes.astype(jnp.int32) << jnp.asarray(off)
    lo_part = (shifted & 0xFF).astype(jnp.float32)
    hi_part = (shifted >> 8).astype(jnp.float32)
    out = lo_part @ jnp.asarray(lo_route) + hi_part @ jnp.asarray(hi_route)
    return out.astype(jnp.int32).astype(jnp.uint8)


def unpack_codes(packed, bits: int, block_size: int):
    """(..., nb, bpb) uint8 bytes -> (..., nb, block_size) uint8 codes."""
    if bits == 8:
        assert packed.shape[-1] == block_size, (packed.shape, block_size)
        return packed.astype(jnp.uint8)
    off, lo_route, hi_route, bpb = pack_layout(block_size, bits)
    assert packed.shape[-1] == bpb, (packed.shape, bpb)
    b = packed.astype(jnp.float32)
    # byte selection as the transposed routing matmuls (gather-free); the
    # clamped no-spill hi byte contributes only bits >= 8 - off + bits,
    # which the final mask drops — same math as indexed selection.
    lo_b = (b @ jnp.asarray(lo_route.T)).astype(jnp.int32)
    hi_b = (b @ jnp.asarray(hi_route.T)).astype(jnp.int32)
    word = lo_b | (hi_b << 8)
    mask = (1 << bits) - 1
    return ((word >> jnp.asarray(off)) & mask).astype(jnp.uint8)


def byte_fold(x, keep_dims: int):
    """Position-weighted integrity fold: uint32 canary over trailing dims.

    Flattens every axis after the first ``keep_dims`` and reduces it to
    one uint32 per leading index: ``sum_j x[j] * (2j + 1) mod 2^32``.
    The weights are odd, so a single corrupted element changes the fold
    by ``delta * odd != 0 (mod 2^32)`` — any one-element flip (and any
    single byte flip of a packed buffer) is always detected, and the
    positional weighting catches value swaps a plain sum would miss.
    Floats are bitcast to same-width unsigned ints first, so the fold is
    a statement about BITS, not values (NaN-safe, -0.0 != +0.0).

    This is the checksum half of the round-trip canaries the codec tests
    run (``_validateCode`` spirit): cheap enough to sit on a serving
    chunk boundary, exact enough to make corruption loud.
    """
    lead = x.shape[:keep_dims]
    flat = x.reshape(lead + (-1,))
    if jnp.issubdtype(flat.dtype, jnp.floating):
        bits = {2: jnp.uint16, 4: jnp.uint32}[flat.dtype.itemsize]
        flat = jax.lax.bitcast_convert_type(flat, bits)
    flat = flat.astype(jnp.uint32)
    w = 2 * jnp.arange(flat.shape[-1], dtype=jnp.uint32) + 1
    return jnp.sum(flat * w, axis=-1, dtype=jnp.uint32)


def pack_codes_scatter(codes, bits: int):
    """Seed (PR-0) scatter-add pack — kept as the oracle for equivalence
    tests and the "seed pipeline" row of benchmarks/kernels_bench.py."""
    B = codes.shape[-1]
    off, _, _, bpb = pack_layout(B, bits)
    p = np.arange(B) * bits
    lo = p // 8
    hi = np.minimum(lo + 1, bpb - 1)
    c = codes.astype(jnp.int32)
    shifted = c << jnp.asarray(off)
    out = jnp.zeros((*codes.shape[:-1], bpb), jnp.int32)
    out = out.at[..., jnp.asarray(lo)].add(shifted & 0xFF)
    out = out.at[..., jnp.asarray(hi)].add(shifted >> 8)
    return out.astype(jnp.uint8)
