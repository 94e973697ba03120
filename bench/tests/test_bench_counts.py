"""Count functions and the peaks table, against hand counts at the
danube3-4b and deepseek-67b-s8 shapes."""
import json
from pathlib import Path

import pytest

from bench import counts, peaks

BENCH = Path(__file__).resolve().parents[1]


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())[
        "model"]


def test_nxfp4_bytes_per_value():
    # 4-bit codes + one uint16 meta word per 32 values = 4.5 bits
    assert counts.nxfp_bytes_per_value() == 0.5 + 2 / 32 == 4.5 / 8


@pytest.mark.parametrize("m,k,n,flops,nbytes", [
    # danube3-4b w2 at 32 decode slots: 32 x 10240 @ 10240 x 3840
    (32, 10240, 3840, 2 * 32 * 10240 * 3840,
     32 * 10240 * 2 + 10240 * 3840 * 0.5625 + 32 * 3840 * 4),
    # deepseek-67b-s8 w1 over a 512-token lane chunk: 512 x 8192 @ 8192 x 22016
    (512, 8192, 22016, 2 * 512 * 8192 * 22016,
     512 * 8192 * 2 + 8192 * 22016 * 0.5625 + 512 * 22016 * 4),
])
def test_matmul_call(m, k, n, flops, nbytes):
    assert counts.matmul_call(m, k, n) == (flops, nbytes)


def test_decode_attention_counts_valid_rows_only():
    # danube: 32 heads over 8 KV heads, head_dim 120 stored as 128;
    # two slots at 1000 and 3000 valid rows
    f, b = counts.decode_attention_call([1000, 3000], 32, 8, 120)
    assert f == 4 * 4000 * 32 * 120
    assert b == 2 * 4000 * 8 * 128 * 0.5625 + 2 * 2 * 32 * 128 * 4
    # more valid rows -> proportionally more K/V bytes; max_len plays no part
    f2, b2 = counts.decode_attention_call([2000, 6000], 32, 8, 120)
    assert f2 == 2 * f and b2 - b == 2 * 4000 * 8 * 128 * 0.5625


def test_layer_params_match_published_sizes():
    # per-layer parameter counts of the issue: 154.8M (danube), 692.1M
    # (deepseek), norms aside
    assert counts.layer_matmul_params(model("danube3-4b")) == 154_828_800
    assert counts.layer_matmul_params(model("deepseek-67b-s8")) \
        == 692_060_160


def test_model_flops_per_token_hand_count():
    m = model("danube3-4b")
    per_layer = 2 * 154_828_800 + 4 * 32 * 120 * 1000
    assert counts.model_flops_per_token(m, 1000) == \
        24 * per_layer + 2 * 3840 * 32000
    assert counts.model_flops_per_token(m, 1000, head=False) == \
        24 * per_layer


def test_roofline_share_picks_the_binding_bound():
    pk = peaks.peaks("TPU v5 lite")
    # 197 GFLOP in 1 s of a 197 TFLOP/s chip: 0.1% of the compute bound
    share, bound = counts.roofline_share(197e9, 1.0, 1.0, pk)
    assert bound == "compute" and share == pytest.approx(0.1)
    # 819 MB in 10 ms at 819 GB/s: 10% of the memory bound
    share, bound = counts.roofline_share(1.0, 819e6, 0.01, pk)
    assert bound == "memory" and share == pytest.approx(10.0)


def test_peaks_table_is_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
