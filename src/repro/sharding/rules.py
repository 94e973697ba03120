"""Sharding rules: logical parameter axes -> mesh PartitionSpecs.

Mesh axes: ('data', 'model') single pod, ('pod', 'data', 'model') multi-pod.
  - 'model': tensor parallel (column/row parallel projections, expert
    parallel on the expert axis, SSM channel parallel, KV-head parallel).
  - 'data' (+ 'pod'): batch data parallel; optionally FSDP (weights shard a
    big non-TP dim over 'data' and all-gather at use) and ZeRO-1 (optimizer
    moments always FSDP-sharded).

Every rule passes through ``fit_spec`` which drops any mesh axis that does
not evenly divide the corresponding dim — small models (whisper-tiny 6
heads, hymba 25 heads) gracefully fall back to replication instead of
failing to lower, exactly what a production launcher must do.

QTensor leaves get derived specs: the packed/meta layouts are the dense
layout with the quantized axis moved last and split into (blocks, bytes),
so their specs are a permutation of the dense spec (block-dim sharding
follows the contraction-dim sharding; bytes dim never sharded).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.qtensor import QTensor
from repro.models.common import ModelConfig

# rule table: regex on the parameter leaf name -> per-dim logical axes for
# the LAST `n` dims (leading stacked dims are always None). 'tp' = model.
_RULES = [
    # embedding gather table: shard d_model, NOT vocab — a vocab-sharded
    # gather makes GSPMD replicate the whole table per lookup ("involuntary
    # full rematerialization") and CHECK-crashes XLA inside pod subgroups.
    (r"tok_embed$", (None, "tp")),
    (r"lm_head$", (None, "tp")),
    (r"enc_pos_embed$", (None, None)),
    (r"(wq|wk|wv)$", (None, "tp")),          # column parallel
    (r"wo$", ("tp", None)),                  # row parallel
    (r"(mlp_w1|mlp_w3|shared_w1|shared_w3)$", (None, "tp")),
    (r"(mlp_w2|shared_w2)$", ("tp", None)),
    (r"router$", (None, None)),
    (r"experts_w[13]$", ("ep", None, None)),  # expert parallel
    (r"experts_w2$", ("ep", None, None)),
    (r"ssm_in_w$", (None, "tp")),
    (r"ssm_conv_w$", ("tp", None)),
    (r"ssm_conv_b$", ("tp",)),
    (r"ssm_x_w$", ("tp", None)),
    (r"ssm_dt_w$", (None, "tp")),
    (r"ssm_dt_bias$", ("tp",)),
    (r"ssm_a_log$", ("tp", None)),
    (r"ssm_d_skip$", ("tp",)),
    (r"ssm_out_w$", ("tp", None)),
    (r"(scale|bias)$", None),                # norms etc: replicated
]

_AXIS_MAP = {"tp": "model", "ep": "model"}


def _mesh_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return mesh.shape[name]


def fit_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop axes that don't divide their dim (graceful replication)."""
    out = []
    for d, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        out.append(ax if ax and d % _mesh_size(mesh, ax) == 0 else None)
    return P(*out)


def _dense_spec(name: str, ndim: int) -> P:
    for pat, axes in _RULES:
        if re.search(pat, name):
            if axes is None:
                return P()
            mapped = tuple(_AXIS_MAP.get(a, a) if a else None for a in axes)
            lead = (None,) * (ndim - len(mapped))
            return P(*(lead + mapped))
    return P()


def _apply_fsdp(shape, spec: P, mesh: Mesh, min_size: int = 1 << 20) -> P:
    """Shard the largest replicated dim over 'data' (FSDP weight sharding)."""
    if int(np.prod(shape)) < min_size or "data" not in mesh.shape:
        return spec
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    cands = [i for i, (d, ax) in enumerate(zip(shape, entries))
             if ax is None and d % mesh.shape["data"] == 0 and d > 1]
    if not cands:
        return spec
    best = max(cands, key=lambda i: shape[i])
    entries[best] = "data"
    return P(*entries)


def _qtensor_specs(qt_shapes, dense_spec: P, axis: int) -> Dict[str, P]:
    """Derive packed/meta specs from the dense spec.

    dense dims D; quantized axis a (negative). packed = moveaxis(a, -1) then
    split last into (nb, bpb); meta = moveaxis(a, -1) with last dim nb.
    """
    packed_shape, meta_shape = qt_shapes
    nd = len(meta_shape)                      # == dense ndim (block dim last)
    entries = list(tuple(dense_spec) + (None,) * (nd - len(dense_spec)))
    a = axis % nd
    moved = [e for i, e in enumerate(entries) if i != a] + [entries[a]]
    return {"packed": P(*(moved + [None])), "meta": P(*moved)}


def params_specs(cfg: ModelConfig, params, mesh: Mesh, fsdp: bool = False):
    """Pytree of PartitionSpecs matching ``params`` (dense or QTensor leaves).

    ``params`` may be real arrays or ShapeDtypeStructs (dry-run).
    """

    def leaf_path_spec(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        # small embedding tables are replicated: the gather partitions
        # trivially (XLA's gather partitioner mis-lowers sharded-operand
        # gathers under multi-level device groups — DESIGN.md lessons);
        # tables >3.5 GB (405B/VLM-90B class) stay d_model-sharded + FSDP.
        if name.endswith("tok_embed") and not isinstance(leaf, QTensor):
            import numpy as _np
            if int(_np.prod(leaf.shape)) * 4 < 3.5e9:
                return P()
        if isinstance(leaf, QTensor):
            nd_dense = len(leaf.shape)
            spec = _dense_spec(name, nd_dense)
            spec = fit_spec(leaf.shape, spec, mesh)
            if fsdp:
                spec = _apply_fsdp(leaf.shape, spec, mesh)
            sub = _qtensor_specs((leaf.packed.shape, leaf.meta.shape),
                                 spec, leaf.axis)
            sub = {"packed": fit_spec(leaf.packed.shape, sub["packed"], mesh),
                   "meta": fit_spec(leaf.meta.shape, sub["meta"], mesh)}
            return QTensor(sub["packed"], sub["meta"], leaf.fmt_name,
                           leaf.shape, leaf.axis, leaf.orig_len)
        spec = _dense_spec(name, leaf.ndim)
        spec = fit_spec(leaf.shape, spec, mesh)
        if fsdp:
            spec = _apply_fsdp(leaf.shape, spec, mesh)
            spec = fit_spec(leaf.shape, spec, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(
        leaf_path_spec, params, is_leaf=lambda l: isinstance(l, QTensor))


_BATCH = ("pod", "data")


def _dp_axes(mesh: Mesh):
    return tuple(a for a in _BATCH if a in mesh.shape) or None


def batch_specs(mesh: Mesh, batch_shapes) -> Any:
    """Inputs: batch dim over ('pod','data'); everything else replicated."""
    dp = _dp_axes(mesh)

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        b = leaf.shape[0]
        if dp and b % _mesh_size(mesh, dp) == 0:
            return P(dp)
        if dp and b % mesh.shape["data"] == 0:
            return P("data")
        return P(*((None,) * leaf.ndim))

    return jax.tree.map(spec, batch_shapes)


_CACHE_DIMS = {
    # leaf-name -> (batch dim, model-sharded dim), offsets from the END,
    # so stacked (L, ...) and VLM-grouped (G, k-1, ...) leaves both work.
    "k": (-4, -2), "v": (-4, -2),                  # (..,B,S,KVH,hd)
    "mem_k": (-4, -2), "mem_v": (-4, -2),
    "k_packed": (-5, -4), "v_packed": (-5, -4),    # (..,B,KVH,Bg,D/P,S)
    "k_meta": (-4, -3), "v_meta": (-4, -3),        # (..,B,KVH,nb,S)
    "h": (-3, -2),                                 # (..,B,di,N)
    "conv": (-3, -1),                              # (..,B,cw-1,di)
}


def cache_specs(mesh: Mesh, cache_shapes) -> Any:
    """Serving cache: batch over DP axes; KV-head/channel dims over 'model'."""
    dp = _dp_axes(mesh)
    has_tp = "model" in mesh.shape   # serving meshes may be data-only
    tp = mesh.shape.get("model", 1)

    def spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1])) if path else ""
        if leaf.ndim == 0 or name not in _CACHE_DIMS:
            return P(*((None,) * leaf.ndim))
        bdim, mdim = _CACHE_DIMS[name]
        e: list = [None] * leaf.ndim
        b = leaf.shape[bdim]
        if dp and b % _mesh_size(mesh, dp) == 0:
            e[bdim % leaf.ndim] = dp
        elif dp and b % mesh.shape["data"] == 0:
            e[bdim % leaf.ndim] = "data"
        if has_tp and leaf.shape[mdim] % tp == 0 and leaf.shape[mdim] >= tp:
            e[mdim % leaf.ndim] = "model"
        return P(*e)

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)


def slot_cache_specs(cache: Any) -> Dict[str, P]:
    """Slot-sharded serving specs: the SLOT axis over 'data', nothing else.

    The continuous engine's cache pytree groups leaves by top-level name
    ("pos" is (B,); every other group stacks layers ahead of the batch
    axis), and within a group every leaf carries its batch dim at the
    same position — so one PartitionSpec *prefix* per group is exact.
    This is the layout contract of ``serving.sharded``: each shard owns
    ``n_slots / S`` whole slots (K/V rows, ring meta, SSM state, pos),
    weights stay replicated, and the fully-manual shard_map body sees the
    plain per-shard continuous-batching problem.  The same dict serves as
    shard_map in_specs/out_specs (prefix semantics) and, leaf-mapped to
    NamedShardings, as the device_put layout.
    """
    from repro.models.lm import _batch_axis

    specs: Dict[str, P] = {}
    for name in cache:
        if name == "pos":
            specs[name] = P("data")
        else:
            specs[name] = P(*((None,) * _batch_axis(name)), "data")
    return specs


def to_shardings(mesh: Mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree (recurses into QTensor)."""
    def conv(s):
        return NamedSharding(mesh, s) if isinstance(s, P) else s
    if isinstance(specs, P):
        return conv(specs)
    return jax.tree.map(conv, specs)


def shard_friendly_config(cfg: ModelConfig, tp: int) -> ModelConfig:
    """Math-preserving config transform for a TP degree.

    - GQA KV-head replication: if tp %% kvh == 0, replicate each KV head
      tp/kvh times (attention output is IDENTICAL — the same K/V rows serve
      the same query heads, only the grouping changes). Standard practice
      (MaxText); costs (tp/kvh)x on the tiny KV projections/cache rows in
      exchange for clean head-parallel attention.
    - MoE expert padding: pad expert TABLES up to a multiple of tp with dead
      experts (the router still scores only the real experts, so routing is
      unchanged); enables expert parallelism for e.g. 60 experts on tp=16.
    """
    changes = {}
    kvh, h = cfg.n_kv_heads, cfg.n_heads
    if 0 < kvh < tp and tp % kvh == 0 and h % tp == 0:
        changes["n_kv_heads"] = tp
    if cfg.n_experts and cfg.n_experts % tp:
        changes["n_experts_padded"] = -(-cfg.n_experts // tp) * tp
    return dataclasses.replace(cfg, **changes) if changes else cfg
