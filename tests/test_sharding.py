"""Sharding rules unit tests (no devices needed) + an 8-device subprocess
lowering test of the real dry-run machinery."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.sharding import fit_spec, shard_friendly_config
from repro.sharding.rules import _dense_spec, _qtensor_specs


class FakeMesh:
    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_rule_table():
    assert _dense_spec("layers/wq", 3) == P(None, None, "model")
    assert _dense_spec("layers/wo", 3) == P(None, "model", None)
    assert _dense_spec("layers/experts_w1", 4) == P(None, "model", None, None)
    assert _dense_spec("layers/ln1_scale", 2) == P()
    # embeddings shard d_model, never vocab (gather partitioner crashes —
    # DESIGN.md sharding lessons); small tables are replicated at the
    # params_specs level on top of this rule
    assert _dense_spec("tok_embed", 2) == P(None, "model")
    assert _dense_spec("layers/router", 3) == P(None, None, None)


def test_fit_spec_drops_indivisible():
    mesh = FakeMesh(data=16, model=16)
    # hymba: 25 heads * 64 = 1600 divides, but whisper 6*64=384 / 16 = 24 ok;
    # a dim of 25 must fall back to replication
    assert fit_spec((32, 25), P(None, "model"), mesh) == P(None, None)
    assert fit_spec((32, 1600), P(None, "model"), mesh) == P(None, "model")
    assert fit_spec((8,), P(("pod", "data")), FakeMesh(pod=2, data=16)) \
        == P(None)


def test_qtensor_spec_derivation():
    # dense (L, K, N) sharded (None, 'data', 'model'), quant axis -2 (K):
    # packed (L, N, nb, bpb) -> (None, 'model', 'data', None)
    sub = _qtensor_specs(((4, 128, 8, 16), (4, 128, 8)),
                         P(None, "data", "model"), -2)
    assert sub["packed"] == P(None, "model", "data", None)
    assert sub["meta"] == P(None, "model", "data")


def test_shard_friendly_kv_replication():
    cfg = get_config("llama3_405b")          # kv=8, tp=16 -> replicate x2
    out = shard_friendly_config(cfg, 16)
    assert out.n_kv_heads == 16
    cfg = get_config("hymba_1_5b")           # kv=5: no clean replication
    assert shard_friendly_config(cfg, 16).n_kv_heads == 5
    cfg = get_config("qwen2_moe_a2_7b")      # 60 experts -> pad to 64
    assert shard_friendly_config(cfg, 16).n_experts_padded == 64
    assert shard_friendly_config(cfg, 16).n_experts == 60


_SUBPROC = r"""
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
r = lower_cell("llama3_8b", "decode_32k", mesh)
assert r["cost"].get("flops", 0) > 0
colls = {k: v["count"] for k, v in r["collectives"].items() if v["count"]}
assert colls, "expected collectives in a TP-sharded decode"
print("SUBPROC_OK", colls)
"""


@pytest.mark.slow
def test_multidevice_lowering_subprocess():
    """Real mesh lowering in a subprocess with 8 host devices (keeps this
    pytest process at 1 device, as required)."""
    import os
    from conftest import run_subprocess
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.path.join(
               os.path.dirname(os.path.dirname(__file__)), "src")}
    run_subprocess(["-c", _SUBPROC], env)


def test_single_device_visible_here():
    # conftest must NOT leak the 512-device flag into tests
    assert len(jax.devices()) == 1
