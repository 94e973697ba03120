"""The Pallas kernels compile for a TPU v5e at real widths (no chip needed).

Each case lowers the kernel through its ``ops`` wrapper — the tiles and
plane views the serving path uses — and compiles it for one chip of a
described ``v5e:2x2`` topology with the TPU compiler installed here.
Mosaic refuses misaligned blocks, unsupported in-kernel reshapes/casts
and VMEM overruns at this step, which interpret-mode tests cannot see.
The topology is described inside a fixture (never at import), so every
xdist worker collects the same tests and only the one running this file
loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import QTensor, get_format
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to check here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the real kernels, not their interpret-mode emulation."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *args, kernels: bool = True):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert (hlo.count("tpu_custom_call") >= 1) == kernels
    return hlo


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weight(sharding, fname, k, n):
    f = get_format(fname)
    nb = k // f.block_size
    return QTensor(_sds(sharding, (n, nb, f.bytes_per_block), jnp.uint8),
                   _sds(sharding, (n, nb), jnp.uint16), fname, (k, n), -2,
                   k)


@pytest.mark.parametrize("fname,m,k,n", [
    ("nxfp4", 8, 4096, 14336),      # decode: gate/up projection
    ("nxfp4", 8, 4096, 1024),       # decode: k/v projection
    ("nxfp4", 256, 14336, 4096),    # prefill chunk: down projection
    ("nxfp5", 8, 4096, 4096),
    ("nxfp6", 8, 4096, 4096),
    ("nxfp8", 8, 4096, 4096),
    ("nxfp4", 8, 1600, 5504),       # hymba-1.5b up projection: whole-K tile
])
def test_nxfp_matmul_compiles(one_chip, mosaic, fname, m, k, n):
    _compile(lambda x, w: ops.qmatmul(x, w, impl="pallas"),
             _sds(one_chip, (m, k), jnp.bfloat16),
             _weight(one_chip, fname, k, n))


def test_untileable_width_takes_xla_path(one_chip, mosaic):
    """hymba-1.5b's down projection (K=5504, N=1600): no preferred tile
    divides either dim, the whole-dim tile is over ``_MAX_TILE_ELEMS``,
    and the GEMM compiles on the XLA path with no kernel in it."""
    k, n = 5504, 1600
    assert (ops._tile_k(k, 2, True) * ops._tile(n, (512, 256, 128))
            > ops._MAX_TILE_ELEMS)
    _compile(lambda x, w: ops.qmatmul(x, w, impl="pallas"),
             _sds(one_chip, (8, k), jnp.bfloat16),
             _weight(one_chip, "nxfp4", k, n), kernels=False)


@pytest.mark.parametrize("fname", ["nxfp4", "nxfp6"])
def test_nxfp_decode_attention_compiles(one_chip, mosaic, fname):
    b, s, kvh, g, d = 8, 4096, 8, 4, 128
    f = get_format(fname)
    nb = d // f.block_size
    cache = QTensor(_sds(one_chip, (b, s, kvh, nb, f.bytes_per_block),
                         jnp.uint8),
                    _sds(one_chip, (b, s, kvh, nb), jnp.uint16), fname,
                    (b, s, kvh, d), -1, d)
    _compile(lambda q, kq, vq, lens: ops.decode_attention(
        q, kq, vq, lens, kvh, impl="pallas"),
        _sds(one_chip, (b, kvh * g, d), jnp.float32), cache, cache,
        _sds(one_chip, (b,), jnp.int32))


@pytest.mark.parametrize("fname", ["nxfp4", "nxfp6"])
def test_nxfp_quantize_pack_compiles(one_chip, mosaic, fname):
    # one decode step's K rows: (B, 1, KVH, D) quantized along head_dim
    _compile(lambda x: ops.quantize_qtensor(x, fname, axis=-1,
                                            impl="pallas"),
             _sds(one_chip, (8, 1, 8, 128), jnp.float32))


def test_nxfp_qq_matmul_compiles(one_chip, mosaic):
    _compile(lambda x, w: ops.qmatmul(
        ops.quantize_qtensor(x, "amxfp4", axis=-1, impl="pallas"), w,
        impl="pallas"),
        _sds(one_chip, (256, 4096), jnp.bfloat16),
        _weight(one_chip, "nxfp4", 4096, 14336))
