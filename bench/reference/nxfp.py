"""NxFP4 direct cast, written from the format's definition (plain jnp).

The reference quantizes the weights and the KV rows it makes itself, so it
takes no table, scale or code from the program.  NxFP4 as the configuration
states it (``weight_fmt`` / ``kv_fmt`` "nxfp4"): blocks of 32 values along
one axis share a power-of-two exponent ``E = floor(log2 max|v|) - 2``
(the block maximum lands in the element grid's top octave); NanoMantissa
scales it by ``1 + nano / 4`` with ``nano`` in 0..3; each block picks, by
least squared error, one of two element grids (Adaptive Microexponent):
sign-magnitude integers 0..7 (BFP4) or E2M1 (0, .5, 1, 1.5, 2, 3, 4, 6);
Code Recycling turns each grid's unused -0 code into minus half its
smallest positive level.  NanoMantissa candidates per grid are the rounded
``max / (top level * 2**E) - 1`` in quarters, and 0.  Values round to the
nearest level; a value half way between two levels takes the lower.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

BLOCK = 32
_INT4 = [float(m) for m in range(8)]
_E2M1 = [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0]


def _grid(pos):
    neg = [-v for v in pos if v > 0] + [-pos[1] / 2]    # recycled -0 code
    return np.array(sorted(set(pos) | set(neg)), np.float32)


GRIDS = [_grid(_INT4), _grid(_E2M1)]     # ties between grids: BFP4 first


def _nearest(v, grid):
    """The grid level nearest ``v``, the lower one at a tie: the lowest
    level plus each step up whose midpoint ``v`` lies strictly above.
    Elementwise only (a table lookup is a gather, slow on the TPU); the
    sums are exact, the levels being small dyadic numbers."""
    out = jnp.full(v.shape, grid[0], jnp.float32)
    for lo, hi in zip(grid[:-1], grid[1:]):
        out = out + jnp.where(v > (lo + hi) / 2, np.float32(hi - lo), 0.0)
    return out


def fake_quant(x, axis: int = -1):
    """``x`` cast to NxFP4 along ``axis`` and back to float32 (blocks of 32,
    the axis zero-padded to whole blocks and cut back after)."""
    x = jnp.moveaxis(jnp.asarray(x, jnp.float32), axis, -1)
    n = x.shape[-1]
    pad = -n % BLOCK
    xb = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xb.reshape(*xb.shape[:-1], -1, BLOCK)
    vmax = jnp.max(jnp.abs(xb), axis=-1)
    _, e = jnp.frexp(jnp.maximum(vmax, np.float32(np.finfo(np.float32).tiny)))
    e = jnp.clip(e - 1 - 2, -126, 127)
    scale0 = jnp.ldexp(jnp.float32(1.0), e)
    best = best_err = None
    for grid in GRIDS:
        r = vmax / (scale0 * grid[-1])
        for nano in (jnp.clip(jnp.round((r - 1.0) * 4.0), 0, 3), 0.0):
            scale = (scale0 * (1.0 + nano * 0.25))[..., None]
            deq = _nearest(xb / scale, grid) * scale
            err = jnp.mean(jnp.square(deq - xb), axis=-1)
            if best is None:
                best, best_err = deq, err
            else:
                take = (err < best_err)[..., None]
                best = jnp.where(take, deq, best)
                best_err = jnp.minimum(err, best_err)
    out = best.reshape(*x.shape[:-1], -1)[..., :n]
    return jnp.moveaxis(out, -1, axis)
