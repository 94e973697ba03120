"""Arithmetic (LUT-free) NxFP field decode — shared by the Pallas kernels.

TPU adaptation of the paper's Fig. 7 dequantization flow: GPU kernels would
use a shared-memory lookup table; TPU gathers are slow on the VPU, so we
decode sign/microexponent/mantissa fields with vector integer ops and build
powers of two by assembling float32 exponent bits directly (exact, no
transcendentals).

Kernel tiles use the *plane* layout (DESIGN.md §2.4): k-bit codes are
grouped ``P`` codes to ``Bg`` bytes (``code_group``), byte plane ``q``
holds byte ``q`` of every group and code plane ``p`` holds code ``p`` of
every group, so a tile unpacks with whole-vector shifts and masks — no
lane interleave, no gather.  A group never straddles a quantization
block, so the per-block scale expands to a plane's rows as a plain
sublane repeat (``expand_rows``).

All functions are pure jnp and usable both inside ``pl.pallas_call`` bodies
and in plain XLA code.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.formats import BlockFormat, ELEMENT_FORMATS
from repro.core.levels import level_table
from repro.core.quantize import pow2i  # canonical definition (re-export)

__all__ = ["pow2i", "decode_elem", "decode_values", "decode_block_values",
           "byte_routes", "code_group", "unpack_planes", "expand_rows",
           "decode_planes"]


def decode_elem(codes, elem_name: str, cr: bool):
    """Decode k-bit element codes (int32) to float32 values in scaled units.

    Implements Fig. 7 steps 1-3 arithmetically: slice fields, remap the
    recycled code (10...0 -> -(smallest)/2, one right-shift of the smallest
    level), reconstruct the mantissa/exponent product.
    """
    fmt = ELEMENT_FORMATS[elem_name]
    bits, ebits, mbits, bias = fmt.bits, fmt.ebits, fmt.mbits, fmt.bias
    c = codes.astype(jnp.int32)
    sign = (c >> (bits - 1)) & 1
    mag = c & ((1 << (bits - 1)) - 1)
    if fmt.is_bfp:
        val = mag.astype(jnp.float32)
        smallest = 1.0
    else:
        e = mag >> mbits
        m = (mag & ((1 << mbits) - 1)).astype(jnp.float32) * (0.5 ** mbits)
        sub = m * (2.0 ** (1 - bias))                       # e == 0: subnormal
        nrm = (1.0 + m) * pow2i(e - bias)                   # e >= 1: normal
        val = jnp.where(e == 0, sub, nrm)
        if ebits == 4 and mbits == 3:  # e4m3 NaN code -> 0 (matches ref LUT)
            val = jnp.where(mag == 127, 0.0, val)
        smallest = 0.5 ** mbits * 2.0 ** (1 - bias)
    val = jnp.where(sign == 1, -val, val)
    if cr:  # code recycling: 10...0 would be -0; remap to -(smallest)/2
        val = jnp.where(c == (1 << (bits - 1)),
                        jnp.float32(-0.5 * smallest), val)
    return val


def _scale(m, shift: int):
    """(1 + nano/4) * 2**E of the scale field at ``shift`` in meta ``m``."""
    e = ((m >> shift) & 0xFF) - 128
    nano = (m >> (shift + 8)) & 0x3
    return (1.0 + nano.astype(jnp.float32) * 0.25) * pow2i(e)


def decode_values(codes, meta, pos, fmt: BlockFormat):
    """Per-element decode: codes, meta and pos all share one shape.

    ``meta`` is each element's block meta word (int32; uint32 semantics for
    asymmetric formats, whose 26 meta bits fit losslessly) and ``pos`` its
    index inside the block (read only by ``ox`` formats).  The caller
    broadcasts the per-block meta to elements — ``decode_block_values`` in
    XLA, ``expand_rows`` inside the kernels — so no op here reshapes.

    Mirrors ``repro.core.quantize.dequantize_blocks`` bit-exactly: level
    values and scales are exact in f32 on both paths.
    """
    m = meta.astype(jnp.int32)
    c = codes.astype(jnp.int32)
    fmt_bit = (m >> 10) & 0x1
    vals = None
    for fb, elem in fmt.elem_formats:
        v = decode_elem(c, elem.name, fmt.cr)
        vals = v if vals is None else jnp.where(fmt_bit == fb, v, vals)
    scale_p = _scale(m, 0)
    if fmt.asym:
        out = vals * jnp.where(vals < 0, _scale(m, 16), scale_p)
    else:
        out = vals * scale_p
    if fmt.ox:
        # the block max's slot holds sign | bits-1 mantissa bits of the max
        # itself, decoded absolutely off its sign's shared exponent
        elem = fmt.elem_formats[0][1]
        emax = level_table(elem.name, False, fmt.recycle).emax
        mb = fmt.bits - 1
        sign = (c >> mb) & 1
        mag = c & ((1 << mb) - 1)
        e_p = (m & 0xFF) - 128
        e_used = jnp.where(sign == 1, ((m >> 16) & 0xFF) - 128, e_p) \
            if fmt.asym else e_p
        vox = (1.0 + mag.astype(jnp.float32) * (0.5 ** mb)) \
            * pow2i(e_used + emax)
        vox = jnp.where(sign == 1, -vox, vox)
        sub = (pos == ((m >> 11) & 0x1F)) & ((m & 0xFF) != 0)
        out = jnp.where(sub, vox, out)
    return out


def decode_block_values(codes, meta, fmt: BlockFormat):
    """codes (..., nb, B) int-like, meta (..., nb) -> f32 values (original
    units) — the XLA-side entry of ``decode_values``."""
    c = codes.astype(jnp.int32)
    m = jnp.broadcast_to(meta.astype(jnp.int32)[..., None], c.shape)
    pos = jax.lax.broadcasted_iota(jnp.int32, c.shape, c.ndim - 1)
    return decode_values(c, m, pos, fmt)


def byte_routes(n_codes: int, bits: int, n_bytes: int):
    """Iota-built (n_codes, n_bytes) 0/1 lo/spill byte-routing constants
    (the ``core.pack`` layout) for the in-kernel pack.

    (Pallas kernels cannot capture array constants, so the routes are
    rebuilt from ``broadcasted_iota`` comparisons — XLA folds them.)  The
    lo route selects code i's low byte, the spill route its high byte,
    clamped to the last byte when there is no spill (the clamped byte's
    contribution is zero, as in ``core.pack``).
    """
    shape = (n_codes, n_bytes)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    b = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lo = (i * bits) // 8
    hi = jnp.minimum(lo + 1, n_bytes - 1)
    return (b == lo).astype(jnp.float32), (b == hi).astype(jnp.float32)


def code_group(bits: int, p_min: int = 1):
    """(P codes, Bg bytes): the smallest whole-byte run of k-bit codes
    holding a multiple of ``p_min`` codes.

    4-bit (2, 1), 8-bit (1, 1), 6-bit (4, 3), 5-bit (8, 5).  ``P`` divides
    every block size in use (32, 16), so groups never straddle a block.
    ``p_min`` lets two operands of one GEMM split K into the same planes.
    """
    p = math.lcm(8 // math.gcd(8, bits), p_min)
    return p, p * bits // 8


def unpack_planes(byte_planes, bits: int):
    """Bg int32 byte planes -> P = 8*Bg/bits int32 code planes.

    Code ``p`` of a group sits at bit ``p*bits`` of the group's
    little-endian bytes (the ``core.pack`` layout), so each code plane is
    a shift of one byte plane, OR'd with the next plane's low bits where
    the code straddles a byte.
    """
    mask = (1 << bits) - 1
    out = []
    for p in range(len(byte_planes) * 8 // bits):
        i, s = divmod(p * bits, 8)
        c = byte_planes[i] >> s
        if s + bits > 8:
            c = c | (byte_planes[i + 1] << (8 - s))
        out.append(c & mask)
    return out


def expand_rows(a, rows: int):
    """(kb, n) -> (kb*rows, n): repeat every row ``rows`` times (sublane
    broadcast; each block's meta covers ``rows`` consecutive plane rows)."""
    kb, n = a.shape
    return jnp.broadcast_to(a[:, None, :], (kb, rows, n)).reshape(kb * rows, n)


def decode_planes(byte_planes, meta, fmt: BlockFormat):
    """Dequantize one plane-layout tile: Bg int32 byte planes (R, n) and
    the tile's per-block meta (R*P/block_size, n) -> P f32 (R, n) planes.

    Row r of code plane p is element ``(r % rows) * P + p`` of block
    ``r // rows`` (``rows = block_size / P``).
    """
    p_n = len(byte_planes) * 8 // fmt.bits
    rows = fmt.block_size // p_n
    m = expand_rows(meta.astype(jnp.int32), rows)
    r_in = (jax.lax.broadcasted_iota(jnp.int32, m.shape, 0) & (rows - 1)
            if fmt.ox else None)
    return [decode_values(c, m, None if r_in is None else r_in * p_n + p, fmt)
            for p, c in enumerate(unpack_planes(byte_planes, fmt.bits))]
