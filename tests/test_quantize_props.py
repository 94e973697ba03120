"""Property-based tests (hypothesis) for the Algorithm-1 quantizer."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (get_format, pack_codes, unpack_codes,
                        quantize_blocks, dequantize_blocks, meta_fields)

FMTS = ["bfp4", "mxfp4", "nxfp4", "nxfp4_nm", "nxfp4_nm_am", "nxfp5",
        "nxfp8", "mxfp6", "mxfp6_e3m2"]

# domain: normal f32 magnitudes (no subnormals/inf/nan — direct-cast domain)
_BOUND = float(np.float32(1e20))
finite = st.floats(min_value=-_BOUND, max_value=_BOUND, allow_nan=False,
                   allow_infinity=False, allow_subnormal=False, width=32)


def blocks(draw, nblocks=4):
    data = draw(st.lists(finite, min_size=nblocks * 32,
                         max_size=nblocks * 32))
    x = np.array(data, np.float32).reshape(nblocks, 32)
    # direct-cast domain: magnitudes below ~1e-30 flush to zero (dequant
    # values within 2**7 of the f32 subnormal floor cannot re-encode
    # identically once E_shared clamps at -126 — a codec boundary, not a
    # property violation)
    return np.where(np.abs(x) < 1e-30, 0.0, x)


@st.composite
def block_arrays(draw):
    return blocks(draw)


@given(block_arrays(), st.sampled_from(FMTS))
@settings(max_examples=60, deadline=None)
def test_chosen_candidate_is_mse_argmin(xb, fname):
    """Algorithm 1 invariant: the emitted encoding achieves min-MSE among
    all (element format x nano) candidates it evaluated."""
    fmt = get_format(fname)
    codes, meta, deq, mses = quantize_blocks(jnp.asarray(xb), fmt,
                                             return_debug=True)
    got = np.mean((np.asarray(deq) - xb) ** 2, -1)
    best = np.min(np.asarray(mses), axis=0)
    np.testing.assert_allclose(got, best, rtol=1e-6, atol=1e-30)


@given(block_arrays())
@settings(max_examples=40, deadline=None)
def test_decode_of_encode_matches_debug(xb):
    fmt = get_format("nxfp4")
    codes, meta, deq, _ = quantize_blocks(jnp.asarray(xb), fmt,
                                          return_debug=True)
    d2 = dequantize_blocks(codes, meta, fmt)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(deq))


@given(block_arrays())
@settings(max_examples=30, deadline=None)
def test_idempotence_non_nano(xb):
    """Grid points are fixed points of the codec — exactly true for
    formats whose candidate set is closed under dequantization (no
    NanoMantissa, or exhaustive nano search)."""
    for fname in ["mxfp4", "bfp4_cr", "mxfp6"]:
        fmt = get_format(fname)
        c1, m1 = quantize_blocks(jnp.asarray(xb), fmt)
        d1 = dequantize_blocks(c1, m1, fmt)
        c2, m2 = quantize_blocks(d1, fmt)
        d2 = dequantize_blocks(c2, m2, fmt)
        np.testing.assert_allclose(np.asarray(d2), np.asarray(d1),
                                   rtol=1e-6, atol=1e-30)


@given(block_arrays())
@settings(max_examples=30, deadline=None)
def test_nano_orbit_stabilizes(xb):
    """Property discovered by this suite: the paper's Algorithm-1 nano
    candidate set {round(vmax ratio), 0} is NOT closed under its own
    dequantization — re-encoding a nano=1 block yields ratio ~1.07 which
    rounds to nano=0, i.e. quantize∘dequantize is not idempotent in one
    step. It must, however, stabilize by the second application (the
    nano=0 grid IS closed), and exhaustive nano search is idempotent
    immediately."""
    fmt = get_format("nxfp4")
    c1, m1 = quantize_blocks(jnp.asarray(xb), fmt)
    d1 = dequantize_blocks(c1, m1, fmt)
    c2, m2 = quantize_blocks(d1, fmt)
    d2 = dequantize_blocks(c2, m2, fmt)
    c3, m3 = quantize_blocks(d2, fmt)
    d3 = dequantize_blocks(c3, m3, fmt)
    np.testing.assert_allclose(np.asarray(d3), np.asarray(d2),
                               rtol=1e-6, atol=1e-30)
    # exhaustive nano search: one-step idempotent
    import dataclasses
    fx = dataclasses.replace(fmt, nano_search="exhaustive", name="nxfp4_ex")
    c1, m1 = quantize_blocks(jnp.asarray(xb), fx)
    d1 = dequantize_blocks(c1, m1, fx)
    c2, m2 = quantize_blocks(d1, fx)
    d2 = dequantize_blocks(c2, m2, fx)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d1),
                               rtol=1e-6, atol=1e-30)


@given(block_arrays())
@settings(max_examples=30, deadline=None)
def test_technique_dominance(xb):
    """Each added NxFP technique can only improve (or tie) block MSE,
    because each technique strictly enlarges the candidate set:
    nxfp4_nm >= mxfp4; nxfp4_nm_am >= nxfp4_nm; nxfp4 >= mxfp4_cr."""
    x = jnp.asarray(xb)

    def mse(fname):
        fmt = get_format(fname)
        c, m = quantize_blocks(x, fmt)
        d = dequantize_blocks(c, m, fmt)
        return float(jnp.mean(jnp.square(d - x)))

    assert mse("nxfp4_nm") <= mse("mxfp4") * (1 + 1e-6)
    assert mse("nxfp4_nm_am") <= mse("nxfp4_nm") * (1 + 1e-6)
    assert mse("nxfp4") <= mse("mxfp4_cr") * (1 + 1e-6)
    assert mse("nxfp4") <= mse("bfp4_cr") * (1 + 1e-6)


@given(block_arrays(), st.integers(min_value=-20, max_value=20))
@settings(max_examples=30, deadline=None)
def test_scale_equivariance(xb, e):
    """Quantization commutes with power-of-two scaling (pure exponent
    shift; codes identical, shared exponent offset by e) — as long as the
    scaled values stay far from the f32/clamp boundaries."""
    fmt = get_format("nxfp4")
    vmax = np.abs(xb).max(-1)
    ok = (vmax > 1e-10) & (vmax < 1e10)   # no clamp/overflow interaction
    c1, m1 = quantize_blocks(jnp.asarray(xb), fmt)
    c2, m2 = quantize_blocks(jnp.asarray(xb * np.float32(2.0 ** e)), fmt)
    np.testing.assert_array_equal(np.asarray(c1)[ok], np.asarray(c2)[ok])
    e1 = np.asarray(meta_fields(m1)[0])
    e2 = np.asarray(meta_fields(m2)[0])
    np.testing.assert_array_equal(e2[ok], e1[ok] + e)


@given(block_arrays())
@settings(max_examples=30, deadline=None)
def test_sign_symmetry_without_cr(xb):
    """Sign-magnitude formats are odd-symmetric — until CR breaks the tie
    (the recycled level exists only at -smallest/2, the paper's point)."""
    fmt = get_format("mxfp4")
    c1, m1 = quantize_blocks(jnp.asarray(xb), fmt)
    c2, m2 = quantize_blocks(jnp.asarray(-xb), fmt)
    d1 = dequantize_blocks(c1, m1, fmt)
    d2 = dequantize_blocks(c2, m2, fmt)
    np.testing.assert_allclose(np.asarray(d2), -np.asarray(d1),
                               rtol=1e-6, atol=1e-30)


@given(st.integers(min_value=3, max_value=8),
       st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=60, deadline=None)
def test_pack_roundtrip(bits, nblocks, seed):
    r = np.random.default_rng(seed)
    codes = r.integers(0, 2 ** bits, size=(nblocks, 32)).astype(np.uint8)
    # 32 * bits always divisible by 8
    packed = pack_codes(jnp.asarray(codes), bits)
    assert packed.shape == (nblocks, 4 * bits)
    out = unpack_codes(packed, bits, 32)
    np.testing.assert_array_equal(np.asarray(out), codes)


@given(st.sampled_from([5, 6]), st.integers(min_value=1, max_value=4),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=60, deadline=None)
def test_two_block_tile_pack_roundtrip(bits, npairs, seed):
    """The 5/6-bit kernel layout, against the seed scatter oracle.

    (a) shift-or pack == scatter pack, (b) the kernels' plane unpack
    (``decode_lib.unpack_planes`` over the packed bytes split into byte
    planes, ``Bg`` bytes to a code group) inverts both, (c) a two-block
    run of codes packs to exactly its blocks' bytes concatenated — groups
    and kernel tiles are a granularity choice, never a layout migration.
    """
    from repro.core.pack import pack_codes_scatter
    from repro.kernels.decode_lib import code_group, unpack_planes
    r = np.random.default_rng(seed)
    nb = 2 * npairs
    codes = r.integers(0, 2 ** bits, size=(3, nb, 32)).astype(np.uint8)
    packed = pack_codes(jnp.asarray(codes), bits)
    np.testing.assert_array_equal(
        np.asarray(packed),
        np.asarray(pack_codes_scatter(jnp.asarray(codes), bits)))
    p_n, bg = code_group(bits)
    groups = np.asarray(packed).astype(np.int32).reshape(3, -1, bg)
    planes = unpack_planes([jnp.asarray(groups[..., q]) for q in range(bg)],
                           bits)
    assert len(planes) == p_n
    out = np.stack([np.asarray(c) for c in planes], -1).reshape(3, nb, 32)
    np.testing.assert_array_equal(out, codes.astype(np.int32))
    tiled = pack_codes(jnp.asarray(codes.reshape(3, npairs, 64)), bits)
    np.testing.assert_array_equal(
        np.asarray(tiled).reshape(3, nb, 4 * bits), np.asarray(packed))


def test_outlier_tracking_fig4():
    """The paper's Fig. 4 worked example, end to end."""
    x = np.zeros((1, 32), np.float32)
    x[0, 0] = -7.4
    x[0, 1:] = np.linspace(-2, 2, 31)
    fmt4 = get_format("mxfp4")
    fmtn = get_format("nxfp4_nm")
    c, m = quantize_blocks(jnp.asarray(x), fmt4)
    d4 = dequantize_blocks(c, m, fmt4)
    c, m = quantize_blocks(jnp.asarray(x), fmtn)
    dn = dequantize_blocks(c, m, fmtn)
    assert abs(float(d4[0, 0]) - (-6.0)) < 1e-6       # clamped
    assert abs(float(dn[0, 0]) - (-7.5)) < 1e-6       # nano=1.25 tracks it
