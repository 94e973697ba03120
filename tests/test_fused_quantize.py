"""Fused encode+pack quantize pipeline: bit-exactness, edge cases, packing.

Covers the ISSUE-1 acceptance criteria: the fused Pallas kernel (interpret
mode — the real kernel body executes on CPU) is bit-identical to
``quantize_blocks_arith`` and decode-compatible with ``dequantize_blocks``
for every format in the registry; the XLA fallback widths (5/6-bit) take
the arithmetic encoder + shift-or pack and agree with the searchsorted
reference; zero blocks, NaN/Inf inputs and midpoint ties behave as
documented in ``quantize_blocks_arith``'s docstring.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (QTensor, get_format, pack_codes, unpack_codes,
                        quantize_blocks, quantize_blocks_arith,
                        quantize_blocks_gatherfree, dequantize_blocks,
                        meta_fields)
from repro.core.pack import pack_codes_scatter
from repro.kernels.nxfp_quantize import nxfp_quantize_pack_pallas
from repro.kernels.ops import quantize_qtensor

# every registered format family x width this repo exercises; 4/5/6/8-bit
# run the fused Pallas kernel per block, 3-bit the XLA arithmetic fallback
REGISTRY = ["bfp4", "bfp4_cr", "mxfp4", "mxfp4_cr", "nxfp4", "nxfp4_nm",
            "nxfp4_nm_am", "nxfp4_bs16", "nxfp8", "mxfp8", "bfp8",
            "mxfp3", "nxfp5", "mxfp5", "nxfp6", "mxfp6", "mxfp6_e3m2"]
KERNEL_FMTS = [f for f in REGISTRY if get_format(f).bits in (4, 5, 6, 8)]
FALLBACK_FMTS = [f for f in REGISTRY if get_format(f).bits not in (4, 5, 6, 8)]


def _edge_blocks(rng, fmt):
    """Random exponent-spread blocks + zero / NaN / Inf / huge rows."""
    b = fmt.block_size
    xb = (rng.standard_normal((257, b)) *
          np.exp(rng.normal(0, 4, size=(257, 1)))).astype(np.float32)
    xb[0] = 0.0                                   # all-zero block
    xb[1, :4] = [np.nan, np.inf, -np.inf, 0.0]    # non-finite inputs
    xb[2] = 1e30                                  # MSE overflows f32 to inf
    xb[3, ::2] = 0.0                              # half-zero block
    return xb


@pytest.mark.parametrize("fname", KERNEL_FMTS)
def test_fused_kernel_bit_identical_to_arith(rng, fname):
    fmt = get_format(fname)
    xb = _edge_blocks(rng, fmt)
    ac, am = quantize_blocks_arith(jnp.asarray(xb), fmt)
    kp, km = nxfp_quantize_pack_pallas(jnp.asarray(xb), fmt, tile_rows=64,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(pack_codes(ac, fmt.bits)),
                                  np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(am), np.asarray(km))
    assert kp.dtype == jnp.uint8 and km.dtype == jnp.uint16


@pytest.mark.parametrize("fname", KERNEL_FMTS)
def test_fused_kernel_decode_compatible(rng, fname):
    """unpack+dequantize of the kernel's packed output == the reference
    decode of the arithmetic encoder's codes (same grid, same metadata)."""
    fmt = get_format(fname)
    xb = _edge_blocks(rng, fmt)
    kp, km = nxfp_quantize_pack_pallas(jnp.asarray(xb), fmt, tile_rows=64,
                                       interpret=True)
    codes = unpack_codes(kp, fmt.bits, fmt.block_size)
    deq = dequantize_blocks(codes, km, fmt)
    ac, am = quantize_blocks_arith(jnp.asarray(xb), fmt)
    ref = dequantize_blocks(ac, am, fmt)
    np.testing.assert_array_equal(np.asarray(deq), np.asarray(ref))
    assert np.isfinite(np.asarray(deq)).all()


@pytest.mark.parametrize("fname", REGISTRY)
def test_arith_matches_searchsorted_reference(rng, fname):
    """Off-midpoint, the arithmetic encoder is bit-identical to the
    table-driven reference for EVERY registered format (random continuous
    inputs hit exact grid midpoints with probability ~0)."""
    fmt = get_format(fname)
    xb = _edge_blocks(rng, fmt)
    ac, am = quantize_blocks_arith(jnp.asarray(xb), fmt)
    qc, qm = quantize_blocks(jnp.asarray(xb), fmt)
    np.testing.assert_array_equal(np.asarray(qc), np.asarray(ac))
    np.testing.assert_array_equal(np.asarray(qm), np.asarray(am))


@pytest.mark.parametrize("fname", FALLBACK_FMTS)
def test_xla_fallback_widths_roundtrip(rng, fname):
    """Widths outside the kernel set (3-bit) fall back to arith encode +
    shift-or pack, exactly."""
    fmt = get_format(fname)
    x = (rng.standard_normal((64, 96)) * 3).astype(np.float32)
    qt = quantize_qtensor(jnp.asarray(x), fname, axis=-1, impl="pallas")
    ac, am = quantize_blocks_arith(
        jnp.asarray(x).reshape(64, -1, fmt.block_size), fmt)
    np.testing.assert_array_equal(np.asarray(qt.packed),
                                  np.asarray(pack_codes(ac, fmt.bits)))
    np.testing.assert_array_equal(np.asarray(qt.meta), np.asarray(am))


def test_zero_blocks_encode_to_zero_codes():
    for fname in ["nxfp4", "nxfp8", "mxfp4", "bfp4"]:
        fmt = get_format(fname)
        xb = np.zeros((8, fmt.block_size), np.float32)
        kp, km = nxfp_quantize_pack_pallas(jnp.asarray(xb), fmt,
                                           tile_rows=8, interpret=True)
        assert (np.asarray(kp) == 0).all(), fname
        e_shared = np.asarray(meta_fields(km)[0])
        assert (e_shared == -126).all(), fname   # tiny-clamp floor
        deq = dequantize_blocks(unpack_codes(kp, fmt.bits, fmt.block_size),
                                km, fmt)
        assert (np.asarray(deq) == 0.0).all(), fname


def test_nonfinite_inputs_sanitized_like_reference():
    """NaN -> 0, +/-Inf -> +/-1e30 before encode (reference semantics); the
    first-candidate-wins rule keeps inf-MSE blocks encoded rather than
    silently zeroed (seed running-argmin bug)."""
    fmt = get_format("mxfp4")
    xb = np.zeros((1, 32), np.float32)
    xb[0, :4] = [np.nan, np.inf, -np.inf, 5.0]
    kp, km = nxfp_quantize_pack_pallas(jnp.asarray(xb), fmt, tile_rows=8,
                                       interpret=True)
    codes = np.asarray(unpack_codes(kp, fmt.bits, fmt.block_size))[0]
    assert codes[0] == 0                       # NaN -> 0
    assert codes[1] == 7 and codes[2] == 15    # +/-inf -> clamped max level
    e_shared = np.asarray(meta_fields(km)[0])[0]
    assert e_shared == 97                      # floor(log2 1e30) - emax(=2)


def test_negative_zero_canonicalization():
    """Negatives snapping to zero must emit the canonical +0 code — the
    10...0 code is a wasted -0 duplicate without CR, and MEANS -smallest/2
    with CR."""
    for fname in ["mxfp4", "bfp4", "nxfp8"]:
        fmt = get_format(fname)
        xb = np.zeros((1, fmt.block_size), np.float32)
        xb[0, 0] = 4.0            # sets the scale
        xb[0, 1] = -1e-6          # snaps to zero from below
        ac, _ = quantize_blocks_arith(jnp.asarray(xb), fmt)
        qc, _ = quantize_blocks(jnp.asarray(xb), fmt)
        assert np.asarray(ac)[0, 1] == 0, fname
        assert np.asarray(qc)[0, 1] == 0, fname


def test_midpoint_ties_round_to_even():
    """Documented divergence: the arithmetic encoder rounds half-to-even in
    ulp units; the searchsorted reference resolves the same tie downward.
    BFP magnitudes 1.5 / 2.5 (scale 1) sit exactly between integer levels:
    round-even gives 2 / 2, ties-down gives 1 / 2."""
    fmt = get_format("bfp4")
    xb = np.zeros((1, 32), np.float32)
    xb[0, 0] = 7.0   # pins e_shared so the grid is the integers
    xb[0, 1] = 1.5
    xb[0, 2] = 2.5
    xb[0, 3] = -1.5
    ac, am = quantize_blocks_arith(jnp.asarray(xb), fmt)
    qc, qm = quantize_blocks(jnp.asarray(xb), fmt)
    ac, qc = np.asarray(ac), np.asarray(qc)
    assert ac[0, 1] == 2 and ac[0, 2] == 2          # round-to-nearest-EVEN
    assert ac[0, 3] == (8 | 2)
    assert qc[0, 1] == 1 and qc[0, 2] == 2          # reference: ties-down
    # both are nearest-level rounds: decode error identical at midpoints
    da = dequantize_blocks(jnp.asarray(ac), am, fmt)
    dq = dequantize_blocks(jnp.asarray(qc), qm, fmt)
    np.testing.assert_allclose(np.abs(np.asarray(da)[0, 1] - 1.5), 0.5)
    np.testing.assert_allclose(np.abs(np.asarray(dq)[0, 1] - 1.5), 0.5)


def test_huge_blocks_not_zeroed_by_inf_mse(rng):
    """Blocks whose per-candidate MSE overflows f32 must still encode (the
    seed running-argmin emitted all-zero codes; argmin semantics pick the
    first candidate)."""
    for fname in ["nxfp4", "nxfp8", "nxfp4_nm_am"]:
        fmt = get_format(fname)
        xb = (rng.standard_normal((4, fmt.block_size)) * 1e30) \
            .astype(np.float32)
        for enc in (quantize_blocks_arith, quantize_blocks_gatherfree,
                    quantize_blocks):
            c, m = enc(jnp.asarray(xb), fmt)
            assert np.abs(np.asarray(
                dequantize_blocks(c, m, fmt))).max() > 1e29, (fname, enc)


def test_pack_matches_scatter_oracle_all_widths(rng):
    for bits in range(2, 9):
        codes = rng.integers(0, 2 ** bits, size=(3, 11, 32)).astype(np.uint8)
        new = pack_codes(jnp.asarray(codes), bits)
        old = pack_codes_scatter(jnp.asarray(codes), bits)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        out = unpack_codes(new, bits, 32)
        np.testing.assert_array_equal(np.asarray(out), codes)


def test_qtensor_roundtrip_through_fused_path(rng):
    """End-to-end: fused-path QTensor dequantizes identically to the
    XLA-path QTensor (packed layout and semantics unchanged)."""
    x = rng.standard_normal((40, 130)).astype(np.float32)  # pads to blocks
    for fname in ["nxfp4", "nxfp5", "nxfp6", "nxfp8"]:
        a = quantize_qtensor(jnp.asarray(x), fname, axis=-1, impl="pallas")
        b = quantize_qtensor(jnp.asarray(x), fname, axis=-1, impl="xla")
        np.testing.assert_array_equal(np.asarray(a.packed),
                                      np.asarray(b.packed))
        np.testing.assert_array_equal(np.asarray(a.meta), np.asarray(b.meta))
        np.testing.assert_array_equal(np.asarray(a.dequantize(jnp.float32)),
                                      np.asarray(b.dequantize(jnp.float32)))


def test_custom_recycle_sweeps_fall_back_to_reference():
    """Fig.-11 style custom recycle values can't use the arithmetic
    encoder (its CR window is hard-coded to half_smallest) — the wrapper
    must route them to the table-driven reference, and the arith encoder
    must refuse them loudly."""
    base = get_format("nxfp4")
    fmt = dataclasses.replace(base, recycle=-0.17, name="nxfp4_r17")
    x = jnp.asarray(np.linspace(-4, 4, 64, dtype=np.float32).reshape(2, 32))
    qt = quantize_qtensor(x, fmt, axis=-1, impl="pallas")  # no assert trip
    codes, meta = quantize_blocks(x.reshape(2, 1, 32), fmt)
    np.testing.assert_array_equal(np.asarray(qt.packed),
                                  np.asarray(pack_codes(codes, fmt.bits)))
    assert qt.fmt == fmt                       # ad-hoc fmt stored intact
    with pytest.raises(AssertionError):
        quantize_blocks_arith(x.reshape(2, 1, 32), fmt)
