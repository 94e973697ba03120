"""Operations and bytes of each kernel call, and model FLOPs per token.

Everything here is counted from what a call computes -- its operand shapes
and, for attention, the valid context lengths the harness set -- never from
the program's choice of kernel, tile or padded cache length.  A bytes count
charges each stored NxFP value ``bits / 8 + meta_bytes / block_size``
(the packed codes plus the uint16 per-block meta word).
"""
from __future__ import annotations

from typing import Sequence

NXFP4 = {"bits": 4, "block_size": 32, "meta_bytes": 2}


def nxfp_bytes_per_value(fmt: dict = NXFP4) -> float:
    return fmt["bits"] / 8 + fmt["meta_bytes"] / fmt["block_size"]


def matmul_call(m: int, k: int, n: int, x_bytes: int = 2, out_bytes: int = 4,
                fmt: dict = NXFP4):
    """(flops, bytes) of ``x (m, k) @ W (k, n)`` with W stored in ``fmt``:
    the activations read once, the packed weight read once, f32 out."""
    flops = 2 * m * k * n
    nbytes = m * k * x_bytes + k * n * nxfp_bytes_per_value(fmt) \
        + m * n * out_bytes
    return flops, nbytes


def decode_attention_call(lengths: Sequence[int], n_heads: int,
                          n_kv_heads: int, head_dim: int,
                          fmt: dict = NXFP4):
    """(flops, bytes) of one single-token attention call over an NxFP KV
    cache: each slot reads its packed K and V rows up to its valid length
    (head_dim padded to whole blocks, as stored), plus q in and out (f32).
    QK^T and PV are 2 * head_dim flops per head and row each."""
    bs = fmt["block_size"]
    d_stored = -(-head_dim // bs) * bs
    rows = sum(int(t) for t in lengths)
    kv = 2 * rows * n_kv_heads * d_stored * nxfp_bytes_per_value(fmt)
    qo = 2 * len(lengths) * n_heads * d_stored * 4
    flops = 4 * rows * n_heads * head_dim
    return flops, kv + qo


def layer_matmul_params(m: dict) -> int:
    """Weights a token multiplies by in one dense GQA + SwiGLU layer."""
    d, h, kvh, ff = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"]
    hd = m.get("head_dim") or d // h
    return d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * ff


def model_flops_per_token(m: dict, context: float, head: bool = True
                          ) -> float:
    """Forward FLOPs of one token at ``context`` attended positions: two
    per weight of every layer (and of the head), plus attention's QK^T and
    PV over the context.  The embedding lookup is a gather, not FLOPs."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    per_layer = 2 * layer_matmul_params(m) + 4 * m["n_heads"] * hd * context
    return m["n_layers"] * per_layer + (2 * m["d_model"] * m["vocab"]
                                        if head else 0)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict):
    """(share of the roofline in %, bound) for work done in ``seconds``:
    the least time the chip could take -- the larger of flops over peak
    FLOP/s and bytes over peak bytes/s -- over the time taken."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
