"""QTensor: a quantized-tensor pytree + direct-cast of parameter pytrees."""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .formats import BlockFormat, get_format
from .pack import pack_codes, unpack_codes
from .quantize import (dequantize_blocks, from_blocks, quantize_blocks,
                       to_blocks)

__all__ = ["QTensor", "QuantPolicy", "direct_cast_tree", "fmt_key",
           "tree_footprint_bytes"]


def fmt_key(fmt: BlockFormat):
    """QTensor.fmt_name for a BlockFormat: the registry name when it
    round-trips (checkpoint-serializable), else the BlockFormat itself
    (ad-hoc formats, e.g. custom recycle values in the Fig. 11 sweep)."""
    try:
        return fmt.name if get_format(fmt.name) == fmt else fmt
    except ValueError:
        return fmt


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """A direct-cast NxFP/MxFP/BFP tensor.

    ``packed``: (..., nb, bytes_per_block) uint8 — block axis moved last.
    ``meta``:   (..., nb) uint16 — shared exponent / nano / fmt bits.
    Static aux: format name, logical shape, block axis, original axis length.
    """

    packed: Any
    meta: Any
    fmt_name: str
    shape: Tuple[int, ...]
    axis: int   # ALWAYS negative (offset from the last dim) so that leading
                # axes may be sliced away (e.g. scan over stacked layers)
    orig_len: int

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.packed, self.meta), (self.fmt_name, self.shape,
                                          self.axis, self.orig_len)

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, meta = children
        return cls(packed, meta, *aux)

    # -- codec ---------------------------------------------------------------
    @property
    def fmt(self) -> BlockFormat:
        # fmt_name is usually a registry name; ad-hoc formats (e.g. custom
        # recycle values in the Fig. 11 sweep) store the BlockFormat itself.
        if isinstance(self.fmt_name, BlockFormat):
            return self.fmt_name
        return get_format(self.fmt_name)

    @property
    def dtype(self):
        return jnp.float32

    @property
    def ndim(self):
        return len(self.shape)

    @classmethod
    def quantize(cls, x, fmt, axis: int = -1) -> "QTensor":
        if isinstance(fmt, str):
            fmt = get_format(fmt)
        axis = axis if axis < 0 else axis - x.ndim
        xb, n = to_blocks(x, fmt.block_size, axis)
        codes, meta = quantize_blocks(xb, fmt)
        return cls(pack_codes(codes, fmt.bits), meta, fmt_key(fmt),
                   tuple(x.shape), axis, n)

    def dequantize(self, dtype=jnp.bfloat16):
        fmt = self.fmt
        codes = unpack_codes(self.packed, fmt.bits, fmt.block_size)
        deq = dequantize_blocks(codes, self.meta, fmt, jnp.float32)
        return from_blocks(deq, self.orig_len, self.axis).astype(dtype)

    # -- accounting ----------------------------------------------------------
    def nbytes(self) -> int:
        import numpy as np
        meta_itemsize = self.meta.dtype.itemsize  # uint16, uint32 for asym
        return (int(np.prod(self.packed.shape))
                + meta_itemsize * int(np.prod(self.meta.shape)))

    def bits_per_value(self) -> float:
        import numpy as np
        return self.nbytes() * 8.0 / float(np.prod(self.shape))


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Which parameter leaves get direct-cast, and how.

    ``weight_fmt``: format for matmul/embedding weights (None = keep dense).
    ``kv_fmt``:     format for the serving KV cache (None = bf16 cache).
    ``pattern``:    leaves whose path matches are quantized (ndim >= 2 only).
    ``skip``:       overriding skip pattern (norms, biases, scales).
    ``axis``:       block axis for weights: -2 = contraction dim of
                    (..., K, N) matmul weights (robust to stacked layers).
    """

    weight_fmt: Optional[str] = "nxfp4"
    kv_fmt: Optional[str] = "nxfp4"
    state_fmt: Optional[str] = None      # SSM recurrent-state cache format
    pattern: str = r"(w|kernel|embed|weight)"
    skip: str = r"(norm|scale|bias|gamma|beta|dt_bias|a_log|conv|tok_embed|pos_embed|router)"
    axis: int = -2
    min_size: int = 1024

    def matches(self, path: str, leaf) -> bool:
        if self.weight_fmt is None:
            return False
        if getattr(leaf, "ndim", 0) < 2:
            return False
        import numpy as np
        if int(np.prod(leaf.shape)) < self.min_size:
            return False
        p = path.lower()
        if re.search(self.skip, p):
            return False
        return re.search(self.pattern, p) is not None


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def direct_cast_tree(params, policy: QuantPolicy, quantize_fn=None):
    """Direct-cast a parameter pytree: matching leaves become QTensor.

    ``quantize_fn(leaf, fmt, axis) -> QTensor`` overrides the encoder;
    default is the reference-oracle ``QTensor.quantize``. The serving
    engine passes ``repro.kernels.ops.quantize_qtensor`` so load-time
    weight casts ride the fused encode+pack kernel (core cannot import
    kernels itself — that would be a circular import).  Leaves that are
    already ``QTensor`` stay as they are, so casting a cast tree is the
    identity.
    """
    qfn = quantize_fn or (
        lambda leaf, fmt, axis: QTensor.quantize(leaf, fmt, axis=axis))

    def cast(path, leaf):
        p = _path_str(path)
        if not isinstance(leaf, QTensor) and policy.matches(p, leaf):
            return qfn(leaf, policy.weight_fmt, policy.axis)
        return leaf

    return jax.tree_util.tree_map_with_path(
        cast, params, is_leaf=lambda l: isinstance(l, QTensor))


def dense_like(qparams):
    """Dequantize every QTensor leaf back to bf16 (for paper-style eval)."""
    return jax.tree.map(
        lambda l: l.dequantize() if isinstance(l, QTensor) else l,
        qparams, is_leaf=lambda l: isinstance(l, QTensor))


def tree_footprint_bytes(params) -> int:
    """Measured footprint: packed bytes for QTensor, nbytes for dense leaves."""
    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda l: isinstance(l, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes()
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total
