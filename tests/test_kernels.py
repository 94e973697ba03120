"""Per-kernel validation: shape/dtype/format sweeps vs the ref.py oracles,
all in interpret mode (the kernel bodies execute on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QTensor, get_format, pack_codes
from repro.core.quantize import quantize_blocks
from repro.kernels import decode_attention, qmatmul, quantize_qtensor
from repro.kernels.nxfp_matmul import nxfp_matmul_pallas
from repro.kernels.nxfp_quantize import nxfp_quantize_pack_pallas
from repro.kernels.ref import qmatmul_ref, decode_attention_ref


@pytest.mark.parametrize("fname", ["nxfp4", "mxfp4", "bfp4", "nxfp8"])
@pytest.mark.parametrize("mkn", [(32, 256, 128), (64, 512, 256),
                                 (17, 256, 128)])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_sweep(rng, fname, mkn, xdtype):
    m, k, n = mkn
    fmt = get_format(fname)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = (rng.standard_normal((k, n)) * 0.05).astype(np.float32)
    qt = QTensor.quantize(jnp.asarray(w), fmt, axis=0)
    ref = qmatmul_ref(jnp.asarray(x, xdtype), qt.packed, qt.meta, fmt)
    y = nxfp_matmul_pallas(jnp.asarray(x, xdtype), qt.packed, qt.meta, fmt,
                           tile_m=32, tile_n=64, tile_k=128, interpret=True)
    scale = np.max(np.abs(np.asarray(ref))) + 1e-9
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(ref) / scale, atol=1e-5)


@pytest.mark.parametrize("fname", ["nxfp4", "mxfp4", "bfp4", "nxfp8",
                                   "nxfp4_nm", "nxfp4_nm_am", "mxfp4_cr",
                                   "bfp4_cr"])
def test_quantize_kernel_exact(rng, fname):
    """Fused encode+pack kernel == reference encode + reference pack.

    (Random continuous inputs never hit grid midpoints, so the kernel's
    round-to-even and the reference's ties-down agree bit-for-bit; the
    midpoint carve-out itself is covered in test_fused_quantize.py.)
    """
    fmt = get_format(fname)
    xb = (rng.standard_normal((513, 32)) *
          np.exp(rng.normal(0, 4, size=(513, 1)))).astype(np.float32)
    xb[0] = 0.0
    ref_c, ref_m = quantize_blocks(jnp.asarray(xb), fmt)
    ref_p = pack_codes(ref_c, fmt.bits)
    kp, km = nxfp_quantize_pack_pallas(jnp.asarray(xb), fmt, tile_rows=128,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_p), np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(ref_m), np.asarray(km))


@pytest.mark.parametrize("fname", ["nxfp4", "nxfp8"])
@pytest.mark.parametrize("bshkd", [(2, 256, 8, 4, 64), (1, 128, 4, 1, 128),
                                   (3, 64, 6, 2, 32)])
def test_decode_attention_sweep(rng, fname, bshkd):
    b, s, h, kvh, d = bshkd
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k = (rng.standard_normal((b, s, kvh, d)) * 0.3).astype(np.float32)
    v = (rng.standard_normal((b, s, kvh, d)) * 0.3).astype(np.float32)
    lengths = rng.integers(1, s + 1, size=(b,)).astype(np.int32)
    kq = quantize_qtensor(jnp.asarray(k), fname, axis=-1, impl="xla")
    vq = quantize_qtensor(jnp.asarray(v), fname, axis=-1, impl="xla")
    o_pl = decode_attention(jnp.asarray(q), kq, vq, jnp.asarray(lengths),
                            kvh, impl="pallas")
    o_ref = decode_attention(jnp.asarray(q), kq, vq, jnp.asarray(lengths),
                             kvh, impl="xla")
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fname", ["nxfp5", "mxfp5", "nxfp6", "mxfp6_e3m2"])
def test_matmul_kernel_two_block_widths(rng, fname):
    """5/6-bit weights route through the fused dequant GEMM: codes
    straddle bytes within 5/3-byte groups, one code plane per group
    position."""
    fmt = get_format(fname)
    x = rng.standard_normal((17, 256)).astype(np.float32)
    w = (rng.standard_normal((256, 128)) * 0.05).astype(np.float32)
    qt = QTensor.quantize(jnp.asarray(w), fmt, axis=0)
    ref = qmatmul_ref(jnp.asarray(x), qt.packed, qt.meta, fmt)
    y = nxfp_matmul_pallas(jnp.asarray(x), qt.packed, qt.meta, fmt,
                           tile_m=32, tile_n=64, tile_k=128, interpret=True)
    scale = np.max(np.abs(np.asarray(ref))) + 1e-9
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(ref) / scale, atol=1e-5)


@pytest.mark.parametrize("fname", ["nxfp5", "nxfp6"])
def test_decode_attention_two_block_widths(rng, fname):
    """5/6-bit KV caches hit the Pallas decode-attention kernel."""
    b, s, h, kvh, d = 2, 64, 8, 4, 64
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k = (rng.standard_normal((b, s, kvh, d)) * 0.3).astype(np.float32)
    v = (rng.standard_normal((b, s, kvh, d)) * 0.3).astype(np.float32)
    lengths = np.array([64, 30], np.int32)
    kq = quantize_qtensor(jnp.asarray(k), fname, axis=-1, impl="xla")
    vq = quantize_qtensor(jnp.asarray(v), fname, axis=-1, impl="xla")
    o_pl = decode_attention(jnp.asarray(q), kq, vq, jnp.asarray(lengths),
                            kvh, impl="pallas")
    o_ref = decode_attention(jnp.asarray(q), kq, vq, jnp.asarray(lengths),
                             kvh, impl="xla")
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-5)


def test_two_block_widths_odd_block_count_falls_back(rng):
    """An odd number of 32-blocks (no longer special: code groups never
    straddle a block) stays exact through the Pallas wrappers."""
    x = rng.standard_normal((8, 96)).astype(np.float32)   # 3 blocks
    w = (rng.standard_normal((96, 64)) * 0.1).astype(np.float32)
    qt = QTensor.quantize(jnp.asarray(w), "nxfp5", axis=0)
    y = qmatmul(jnp.asarray(x), qt, impl="pallas")
    ref = x @ np.asarray(qt.dequantize(jnp.float32))[:96]
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-2, atol=2e-2)
    # head_dim 96 -> 3 blocks along the quantized axis
    q = rng.standard_normal((2, 4, 96)).astype(np.float32)
    k = (rng.standard_normal((2, 32, 2, 96)) * 0.2).astype(np.float32)
    kq = quantize_qtensor(jnp.asarray(k), "nxfp5", axis=-1, impl="xla")
    lengths = np.array([32, 16], np.int32)
    o_pl = decode_attention(jnp.asarray(q), kq, kq, jnp.asarray(lengths),
                            2, impl="pallas")
    o_ref = decode_attention(jnp.asarray(q), kq, kq, jnp.asarray(lengths),
                             2, impl="xla")
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-5)


def test_quantize_wrapper_impls_agree(rng):
    x = rng.standard_normal((96, 80)).astype(np.float32)
    a = quantize_qtensor(jnp.asarray(x), "nxfp4", axis=0, impl="pallas")
    b = quantize_qtensor(jnp.asarray(x), "nxfp4", axis=0, impl="xla")
    np.testing.assert_array_equal(np.asarray(a.packed), np.asarray(b.packed))
    np.testing.assert_array_equal(np.asarray(a.meta), np.asarray(b.meta))


def test_qmatmul_handles_padded_k(rng):
    """K=80 pads to 96 (3 blocks); x is zero-padded to match."""
    x = rng.standard_normal((8, 80)).astype(np.float32)
    w = (rng.standard_normal((80, 64)) * 0.1).astype(np.float32)
    qt = QTensor.quantize(jnp.asarray(w), "nxfp4", axis=0)
    y = qmatmul(jnp.asarray(x), qt, impl="xla")
    ref = x @ np.asarray(qt.dequantize(jnp.float32))[:80]
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-2, atol=2e-2)


def test_decode_attention_head_dim_padding(rng):
    """head_dim=120 (danube) pads to 128 inside the cache codec."""
    b, s, h, kvh, d = 2, 64, 4, 2, 120
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k = (rng.standard_normal((b, s, kvh, d)) * 0.2).astype(np.float32)
    v = (rng.standard_normal((b, s, kvh, d)) * 0.2).astype(np.float32)
    lengths = np.array([64, 30], np.int32)
    kq = quantize_qtensor(jnp.asarray(k), "nxfp4", axis=-1, impl="xla")
    vq = quantize_qtensor(jnp.asarray(v), "nxfp4", axis=-1, impl="xla")
    o_pl = decode_attention(jnp.asarray(q), kq, vq, jnp.asarray(lengths),
                            kvh, impl="pallas")
    o_ref = decode_attention(jnp.asarray(q), kq, vq, jnp.asarray(lengths),
                             kvh, impl="xla")
    assert o_pl.shape == (b, h, d)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-5)
