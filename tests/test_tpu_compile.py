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
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
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


# ---------------------------------------------------------------------------
# the continuous engine's programs update the stacked KV cache in place
# ---------------------------------------------------------------------------

# danube3-4b (the chat cell's model) at its own depth, 32 slots, a
# 4096-row SWA ring, chunks of 4 and lane chunks of 2048
_DANUBE = dict(family="dense", n_layers=24, d_model=3840, n_heads=32,
               n_kv_heads=8, head_dim=120, d_ff=10240, vocab=32000,
               rope_theta=500000.0, norm_eps=1e-5, sliding_window=4096)
_SLOTS, _MAX_LEN, _CHUNK, _P_CHUNK = 32, 2048, 4, 2048
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* (\S+)\(")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
             "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}


def _hlo_buffers(hlo):
    """name -> (bytes, shape, opcode, operand names) of each array-valued
    instruction of a compiled module's text."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dtype, dims, op = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        nbytes = int(np.prod(shape, dtype=np.int64)) * _ITEMSIZE.get(dtype, 0)
        args = line[m.end():].split("),")[0]
        out[name] = (nbytes, shape, op, re.findall(r"%([\w.\-]+)", args))
    return out


@pytest.mark.parametrize("program", ["decode_chunk", "lane_chunk"])
def test_serving_program_updates_cache_in_place(one_chip, mosaic,
                                                monkeypatch, program):
    """The decode chunk and the lane chunk, cache donated, compiled for
    a v5e at danube3-4b widths: the cache aliases the output, no copy or
    slice of a cache-shaped array is a layer's slice or more, an in-place
    update writes less than that, and no temporary is cache-sized."""
    from repro.core.qtensor import QuantPolicy, direct_cast_tree
    from repro.models import lm
    from repro.models.common import ModelConfig
    from repro.serving.scheduler import ContinuousEngine

    monkeypatch.setattr(ops, "_resolve",
                        lambda impl: "pallas" if impl is None else impl)
    cfg = ModelConfig(name="danube3-4b", **_DANUBE)
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")

    def put(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = put(jax.eval_shape(lambda: direct_cast_tree(
        lm.init_params(cfg, jax.random.PRNGKey(0)), policy,
        ops.quantize_qtensor)))
    cache = put(lm.init_cache_specs(cfg, _SLOTS, _MAX_LEN, "nxfp4"))
    vec = functools.partial(_sds, one_chip, (_SLOTS,))
    if program == "decode_chunk":
        fn = functools.partial(ContinuousEngine._chunk_fn, cfg=cfg,
                               kv_fmt="nxfp4", n_steps=_CHUNK, greedy=True)
        args = (params, vec(jnp.int32), cache,
                _sds(one_chip, (_SLOTS, 2), jnp.uint32), vec(bool),
                vec(jnp.int32), vec(jnp.int32), vec(jnp.float32),
                vec(jnp.int32), vec(bool), vec(bool))
    else:
        fn = functools.partial(ContinuousEngine._lane_chunk_fn, cfg=cfg,
                               kv_fmt="nxfp4", with_head=True)
        lane = put(jax.eval_shape(
            lambda: lm.init_lane(cfg, _MAX_LEN, _P_CHUNK)))
        scalar = _sds(one_chip, (), jnp.int32)
        args = (params, _sds(one_chip, (1, _P_CHUNK), jnp.int32), cache,
                lane, scalar, scalar, scalar)
    compiled = jax.jit(fn, donate_argnums=2).lower(*args).compile()
    mem = compiled.memory_analysis()

    leaves = jax.tree.leaves(cache["layers"])
    cache_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    leaf_bytes = max(x.size * x.dtype.itemsize for x in leaves)
    # the smallest leaf's one-layer slice: one layer of the uint16 meta
    slice_bytes = min(x.size * x.dtype.itemsize for x in leaves) \
        // cfg.n_layers
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < leaf_bytes

    def cache_shaped(shape):    # slots and ring rows among its dims
        return _SLOTS in shape and cfg.sliding_window in shape

    bufs = _hlo_buffers(compiled.as_text())
    for name, (nbytes, shape, op, operands) in bufs.items():
        if not cache_shaped(shape):
            continue
        if "update" in name or op == "dynamic-update-slice":
            # in place: the updated buffer is the cache, what is written
            # into it is small
            for arg in operands[1:]:
                assert bufs.get(arg, (0,))[0] < slice_bytes, (name, arg)
        elif "copy" in name or "slice" in name or op in (
                "copy", "dynamic-slice", "transpose"):
            assert nbytes < slice_bytes, (name, shape)
