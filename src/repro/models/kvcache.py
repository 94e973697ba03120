"""KV / SSM-state caches: dense bf16 or NxFP-packed, with SWA ring buffers.

The quantized cache is the paper's "weights AND KV cache" configuration
(§7.1): K/V rows are direct-cast per token (blocks along head_dim) into
packed byte buffers; decode attention dequantizes tiles on the fly
(Pallas kernel on TPU, identical jnp path elsewhere).

The cast sits on the decode critical path (it re-runs EVERY token), so it
rides the fused encode+pack quantize pipeline: on TPU one Pallas kernel
writes packed uint8 + uint16 meta straight into the cache layout below —
no int32 code intermediate, no separate repack pass (DESIGN.md §2).

Cache pytrees hold a leading stacked-layer axis.  The decode and lane
layer loops carry the dense attention leaves whole and write each
layer's rows in place (the ``layer`` entry of a layer cache, DESIGN.md
§7); SSM state and the paged cache ride lax.scan per layer.

Packed leaves are STORED in the attention kernel's plane view, context
minor (DESIGN.md §2.4): ``k_packed`` (L, B, KVH, Bg, D/P, S) uint8 and
``k_meta`` (L, B, KVH, NB, S) uint16, so the kernel reads them as they
lie and a token's write is one column per slot.  Slot surgery and
snapshots speak the ROW layout — ``(L, 1, S, KVH, NB, bpb)`` /
``(L, 1, S, KVH, NB)``, what ``quantize_qtensor`` emits — through
``slot_rows`` / ``slot_store``; dense bf16 leaves are (L, B, S, KVH, hd)
in both.

Positions are PER SLOT: ``pos`` is a (B,) int32 vector, one ring pointer
per batch slot, so slots advance independently — the invariant continuous
batching needs (a finished slot can be re-prefilled while its neighbors
keep decoding; see DESIGN.md §8). ``write_token`` scatters each slot's
K/V row at its own ring slot (``pos[b] % window``), and ``attend_decode``
masks each slot to its own valid length.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.formats import get_format
from repro.core.pack import byte_fold, bytes_per_block
from repro.kernels.decode_lib import code_group
from repro.kernels.nxfp_attention import cache_planes, cache_rows
from repro.kernels.ops import decode_attention_planes, quantize_qtensor
from .common import ModelConfig

# the attention-cache leaves covered by the per-slot integrity canary
# (dense bf16 or NxFP packed+meta; SSM state is excluded — it integrates
# every step, so it has no immutable prefix to checksum)
_KV_LEAVES = ("k", "v", "k_packed", "k_meta", "v_packed", "v_meta")
# the packed (codes, meta) pairs, stored in the plane view
_PLANE_PAIRS = (("k_packed", "k_meta"), ("v_packed", "v_meta"))
_PLANE_LEAVES = frozenset(n for pair in _PLANE_PAIRS for n in pair)

# Paged-cache leaf naming (DESIGN.md §14): each dense leaf <name> has a
# physical-page pool twin "pool_<name>" of shape (L, NP, page, ...tail),
# indexed through the per-slot "block" table (L, B, P) — replicated
# across L so the layer scan hands every layer an identical (B, P)
# table with zero plumbing changes.  Logical row r of slot b lives at
# pool[block[b, r // page], r % page].  Physical page 0 is the reserved
# null page (never allocated; unreserved table entries point there and
# writes headed for it are routed out of range and dropped).
_POOL_PREFIX = "pool_"


def attn_cache_init(cfg: ModelConfig, n_layers: int, batch: int,
                    max_len: int, kv_fmt: Optional[str]):
    """Allocate a stacked (L-leading) attention cache."""
    kvh, hd = cfg.n_kv_heads, cfg.hd
    # windowed caches are always window-sized rings (slot = pos % window)
    s = cfg.sliding_window if cfg.sliding_window else max_len
    # every leaf its own buffer: the serving programs donate the cache,
    # and one buffer cannot be donated twice
    if kv_fmt is None:
        shape = (n_layers, batch, s, kvh, hd)
        return {n: jnp.zeros(shape, cfg.dtype) for n in ("k", "v")}
    fmt = get_format(kv_fmt)
    nb = -(-hd // fmt.block_size)
    bpb = bytes_per_block(fmt.block_size, fmt.bits)
    bg = _group_bytes(kv_fmt)
    codes = (n_layers, batch, kvh, bg, nb * bpb // bg, s)
    meta = (n_layers, batch, kvh, nb, s)
    return {"k_packed": jnp.zeros(codes, jnp.uint8),
            "k_meta": jnp.zeros(meta, jnp.uint16),
            "v_packed": jnp.zeros(codes, jnp.uint8),
            "v_meta": jnp.zeros(meta, jnp.uint16)}


def _group_bytes(kv_fmt: str) -> int:
    """Bytes per code group (Bg) of the plane view for ``kv_fmt``."""
    return code_group(get_format(kv_fmt).bits)[1]


def slot_rows(layers):
    """A cache group's packed pairs from the stored plane view to the row
    layout (``cache_rows``); other leaves pass through.  The interchange
    layout of ``lm.read_cache_slot`` and of slot snapshots."""
    out = dict(layers)
    for pk, mk in _PLANE_PAIRS:
        if pk in layers:
            out[pk], out[mk] = cache_rows(layers[pk], layers[mk])
    return out


def slot_store(rows, like):
    """Inverse of ``slot_rows``: row-layout packed pairs into the plane
    view of the stored group ``like`` (whose leaves give Bg)."""
    if "k_packed" not in like:
        return rows
    return _to_planes(rows, like["k_packed"].shape[-3])


def _to_planes(rows, bg: int):
    """Row-layout packed pairs of a group -> the plane view (groups of
    ``bg`` bytes); other leaves pass through."""
    out = dict(rows)
    for pk, mk in _PLANE_PAIRS:
        if pk in rows:
            out[pk], out[mk] = cache_planes(rows[pk], rows[mk], bg)
    return out


def row_capacity(layers) -> Optional[int]:
    """Rows per slot (window or max_len) of a dense stored cache group's
    attention leaves, None without any."""
    for name in _KV_LEAVES:
        if name in layers:
            leaf = layers[name]
            return int(leaf.shape[-1] if name in _PLANE_LEAVES
                       else leaf.shape[2])
    return None


def paged_attn_cache_init(cfg: ModelConfig, n_layers: int, batch: int,
                          max_len: int, kv_fmt: Optional[str],
                          n_pages: int, page_size: int):
    """Allocate a paged attention cache: pool leaves + block table.

    The per-slot logical row space is the same as the dense layout's
    (window-sized ring for SWA, max_len otherwise) so every downstream
    shape and reduction order is preserved bit-for-bit — but physical
    storage is ``n_pages`` pages of ``page_size`` rows, mapped through
    the (L, B, P) block table.  Requires the logical row capacity to be
    a whole number of pages.
    """
    kvh, hd = cfg.n_kv_heads, cfg.hd
    s = cfg.sliding_window if cfg.sliding_window else max_len
    if s % page_size:
        raise ValueError(
            f"page_size {page_size} must divide the slot row capacity {s} "
            f"(sliding window or max_len)")
    block = jnp.zeros((n_layers, batch, s // page_size), jnp.int32)
    rows = (n_layers, n_pages, page_size, kvh)
    if kv_fmt is None:                  # one buffer per leaf (donated)
        return {"block": block,
                **{n: jnp.zeros(rows + (hd,), cfg.dtype)
                   for n in ("pool_k", "pool_v")}}
    fmt = get_format(kv_fmt)
    nb = -(-hd // fmt.block_size)
    bpb = bytes_per_block(fmt.block_size, fmt.bits)
    return {"block": block,
            "pool_k_packed": jnp.zeros(rows + (nb, bpb), jnp.uint8),
            "pool_k_meta": jnp.zeros(rows + (nb,), jnp.uint16),
            "pool_v_packed": jnp.zeros(rows + (nb, bpb), jnp.uint8),
            "pool_v_meta": jnp.zeros(rows + (nb,), jnp.uint16)}


def paged_layer_view(layer_cache):
    """Gather one layer's paged pool into the dense (B, S, ...) layout.

    ``pool[block]`` reshaped to (B, P*page, ...) is EXACTLY the dense
    cache leaf shape, so attention downstream of the view is the same
    program as the fixed-slot engine — identical shapes, identical
    reduction order, bitwise-identical output.  Rows mapped through the
    null page (or stale pages) surface garbage bytes, but only at
    positions attention masks to an exact-zero contribution.
    """
    blk = layer_cache["block"]                              # (B, P)
    out = {}
    for name in _KV_LEAVES:
        pool = layer_cache.get(_POOL_PREFIX + name)
        if pool is None:
            continue
        g = pool[blk]                                       # (B, P, page, ...)
        out[name] = g.reshape(g.shape[0], g.shape[1] * g.shape[2],
                              *g.shape[3:])
    return out


def _pool_dims(layer_cache):
    """(block_table, n_pages, page_size) of one layer's paged cache."""
    pool0 = next(v for n, v in layer_cache.items()
                 if n.startswith(_POOL_PREFIX))
    return layer_cache["block"], pool0.shape[0], pool0.shape[1]


def ssm_cache_init(cfg: ModelConfig, n_layers: int, batch: int):
    di, n, cw = cfg.dinner, cfg.ssm_state, cfg.conv_width
    # conv tail is carried in activation dtype (prefill emits it that way;
    # the decode scan requires a fixed-point carry dtype)
    return {"h": jnp.zeros((n_layers, batch, di, n), jnp.float32),
            "conv": jnp.zeros((n_layers, batch, cw - 1, di), cfg.dtype)}


def _quantize_kv(x, kv_fmt: str):
    """(B, T, KVH, hd) -> (packed, meta) along head_dim blocks.

    quantize_qtensor's fused path emits exactly the (..., nb, bpb) uint8 +
    (..., nb) uint16 buffers the cache stores — the QTensor here is a
    zero-copy view, not a repack.
    """
    qt = quantize_qtensor(x, kv_fmt, axis=-1)
    return qt.packed, qt.meta


def _quantize_planes(x, kv_fmt: str):
    """(B, T, KVH, hd) -> plane-view (codes (B, KVH, Bg, D/P, T), meta
    (B, KVH, NB, T)): the stored layout's columns for T rows."""
    return cache_planes(*_quantize_kv(x, kv_fmt), _group_bytes(kv_fmt))


def _ring_place(x, window: int, t: int):
    """Store the last `window` of x (B, T, ...) at ring slots (pos % window)."""
    if t <= window:
        pad = window - t
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    tail = jax.lax.dynamic_slice_in_dim(x, t - window, window, axis=1)
    return jnp.roll(tail, t % window, axis=1)


def write_prefill(cfg: ModelConfig, k, v, kv_fmt: Optional[str],
                  max_len: int):
    """Build one layer's cache from full prefill K/V (B, T, KVH, hd)."""
    t = k.shape[1]
    w = cfg.sliding_window
    s_total = w if w else max_len

    def place(x):
        if w:
            return _ring_place(x, w, t)
        pad = s_total - x.shape[1]
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    if kv_fmt is None:
        return {"k": place(k.astype(cfg.dtype)), "v": place(v.astype(cfg.dtype))}
    bg = _group_bytes(kv_fmt)
    kp, km = cache_planes(*map(place, _quantize_kv(k, kv_fmt)), bg)
    vp, vm = cache_planes(*map(place, _quantize_kv(v, kv_fmt)), bg)
    return {"k_packed": kp, "k_meta": km, "v_packed": vp, "v_meta": vm}


def write_prefill_at(cfg: ModelConfig, layer_cache, k, v, slot, offset,
                     n_valid, kv_fmt: Optional[str]):
    """Scatter one prefill chunk's K/V (1, P, KVH, hd) into a LIVE slot.

    The chunked-prefill lane's cache write: chunk row i lands at global
    position ``offset + i`` of slot ``slot`` — row ``(offset+i) % window``
    for SWA rings, row ``offset+i`` otherwise — quantized per token when
    ``kv_fmt`` is set (blocks run along head_dim, entirely inside one
    row, so the packed bytes are bit-identical to a whole-prompt cast).
    Rows >= ``n_valid`` (the padded tail of a fixed-shape partial chunk)
    are not written (the packed columns' window writes back what it
    read; dense bf16 and paged rows are routed out of range and dropped
    by a scatter), so a ragged final chunk never touches rows it doesn't
    own.  Requires P <= window for ring caches (distinct in-chunk rows;
    the engine asserts it).  With a ``layer`` entry the rows land in
    that layer of the stacked cache, in place (DESIGN.md §7).

    ``n_valid = 0`` writes no row — the whole call becomes a cache
    no-op, which is how an idle shard rides the sharded engine's fused
    lane dispatch (DESIGN.md §10).  Under the slot-sharded manual
    shard_map the slot axis of ``layer_cache`` is a local shard slice,
    so the write stays a single-device op per shard.
    """
    w = cfg.sliding_window
    pch = k.shape[1]
    assert not w or pch <= w, (pch, w)   # duplicate ring rows corrupt
    gpos = offset + jnp.arange(pch, dtype=jnp.int32)
    row = (gpos % w) if w else gpos

    if "block" in layer_cache:
        # paged: route each chunk row through the slot's block table to
        # its physical page.  Padded-tail rows and rows whose table
        # entry is still the null page go past the pool bound (dropped)
        # — the scattered bytes are the same per-row quantized values as
        # the dense branch, so chunked writes stay bit-identical to a
        # whole-prompt cast.
        blk, n_pages, page = _pool_dims(layer_cache)
        phys = jnp.take(blk, slot, axis=0)[row // page]     # (pch,)
        phys = jnp.where(phys == 0, n_pages, phys)          # null -> dropped
        phys = jnp.where(jnp.arange(pch) < n_valid, phys, n_pages)
        ro = row % page

        def put(buf, val):
            return buf.at[phys, ro].set(val[0].astype(buf.dtype),
                                        mode="drop")

        if kv_fmt is None:
            return {"block": blk, "pool_k": put(layer_cache["pool_k"], k),
                    "pool_v": put(layer_cache["pool_v"], v)}
        kp, km = _quantize_kv(k, kv_fmt)
        vp, vm = _quantize_kv(v, kv_fmt)
        return {"block": blk,
                "pool_k_packed": put(layer_cache["pool_k_packed"], kp),
                "pool_k_meta": put(layer_cache["pool_k_meta"], km),
                "pool_v_packed": put(layer_cache["pool_v_packed"], vp),
                "pool_v_meta": put(layer_cache["pool_v_meta"], vm)}

    at = _layer_at(layer_cache)
    if kv_fmt is None:
        s = layer_cache["k"].shape[len(at) + 1]
        row = jnp.where(jnp.arange(pch) < n_valid, row, s)  # OOB: dropped

        def put(buf, val):
            return buf.at[at + (slot, row)].set(val[0].astype(buf.dtype),
                                                mode="drop")

        return dict(layer_cache, k=put(layer_cache["k"], k),
                    v=put(layer_cache["v"], v))

    def put_cols(buf, val):
        return _put_columns(buf, val[0], at + (slot,), offset, n_valid,
                            ring=bool(w))

    kp, km = _quantize_planes(k, kv_fmt)
    vp, vm = _quantize_planes(v, kv_fmt)
    return dict(layer_cache,
                k_packed=put_cols(layer_cache["k_packed"], kp),
                k_meta=put_cols(layer_cache["k_meta"], km),
                v_packed=put_cols(layer_cache["v_packed"], vp),
                v_meta=put_cols(layer_cache["v_meta"], vm))


def _put_columns(buf, val, lead, offset, n_valid, ring: bool):
    """Write chunk columns ``val[..., i]``, i < ``n_valid``, at context
    rows ``offset + i`` (mod S for a ring) of the plane-view leaf ``buf``
    at index ``lead`` (layer and slot, or slot).

    Each write is a read-modify-write of one P-wide column window (two
    for a ring, whose rows may wrap), a ``dynamic_update_slice`` that
    XLA performs in place; the window's columns outside the chunk's
    valid rows write back what they held, so padded rows and an idle
    call (``n_valid = 0``) change nothing.
    """
    s, p = buf.shape[-1], val.shape[-1]
    wd = min(p, s)
    # ext[i] is chunk row i (zero past the chunk), doubled so that any
    # window start reads the rows of a ring rotation as one slice
    ext = (val[..., :s] if p >= s else jnp.concatenate(
        [val, jnp.zeros(val.shape[:-1] + (s - p,), val.dtype)], axis=-1))
    ext = jnp.concatenate([ext, ext], axis=-1).astype(buf.dtype)
    start = jnp.asarray(offset, jnp.int32) % s
    zeros = (0,) * (buf.ndim - len(lead) - 1)
    shape = (1,) * len(lead) + val.shape[:-1] + (wd,)
    j = jnp.arange(wd, dtype=jnp.int32)
    starts = [jnp.minimum(start, s - wd)] + ([jnp.int32(0)] if ring else [])
    for a in starts:
        c = (a - start) % s                     # window column 0's chunk row
        new = jax.lax.dynamic_slice_in_dim(ext, c, wd, axis=-1)
        idx = tuple(lead) + zeros + (a,)
        old = jax.lax.dynamic_slice(buf, idx, shape)
        keep = (c + j) % s < n_valid
        buf = jax.lax.dynamic_update_slice(
            buf, jnp.where(keep, new.reshape(shape), old), idx)
    return buf


def _layer_at(layer_cache):
    """Leading index of the rows a layer owns: ``(layer,)`` for one layer
    of a stacked cache (the ``layer`` entry, DESIGN.md §7), else ()."""
    layer = layer_cache.get("layer")
    return () if layer is None else (layer,)


def _per_slot(pos, b: int):
    """Normalize a traced position to a per-slot (B,) int32 vector."""
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(pos, (b,)) if pos.ndim == 0 else pos


def write_token(cfg: ModelConfig, layer_cache, k1, v1, pos,
                kv_fmt: Optional[str], live=None):
    """Insert one token's K/V (B, 1, KVH, hd) at per-slot positions.

    ``pos`` is (B,) int32 (a scalar broadcasts): each batch slot writes at
    its OWN ring slot (``pos[b] % window``), so ragged slots never touch a
    neighbor's rows — one ``dynamic_update_slice`` per sequence.  With a
    ``layer`` entry (one layer of the stacked cache, DESIGN.md §7) the
    rows land at ``(layer, b, ...)`` of the whole stack, in place.

    ``live`` (B,) bool, when given, SUPPRESSES slot b's write for
    ``live[b] == False`` (the row keeps its old value).  The continuous
    engine marks mid-prefill and parked slots not-live: they still step
    through the decode scan (fixed batch shape) but must not clobber
    rows the chunked-prefill lane owns — a ring slot's garbage write
    would land on already-prefilled rows.  Live slots see bit-identical
    writes, so ``live=None`` callers (solo engine) are unchanged.
    """
    w = cfg.sliding_window
    pos = _per_slot(pos, k1.shape[0])
    slot = (pos % w) if w else pos

    if "block" in layer_cache:
        # paged: each batch slot's ring row maps through ITS block-table
        # row to a physical page — a batched (page, in-page-row) scatter
        # instead of the per-slot dynamic_update_slice.  Distinct slots
        # own distinct physical pages (shared pages are COW-broken by
        # the engine before any divergent write reaches them), so the
        # scatter never sees colliding indices; not-live slots and rows
        # mapped to the null page route past the pool bound and drop.
        blk, n_pages, page = _pool_dims(layer_cache)
        pg, ro = slot // page, slot % page                  # (B,) each
        phys = jnp.take_along_axis(blk, pg[:, None], axis=1)[:, 0]
        phys = jnp.where(phys == 0, n_pages, phys)
        if live is not None:
            phys = jnp.where(live, phys, n_pages)

        def updp(buf, val):
            return buf.at[phys, ro].set(val[:, 0].astype(buf.dtype),
                                        mode="drop")

        if kv_fmt is None:
            return {"block": blk,
                    "pool_k": updp(layer_cache["pool_k"], k1),
                    "pool_v": updp(layer_cache["pool_v"], v1)}
        kp, km = _quantize_kv(k1, kv_fmt)
        vp, vm = _quantize_kv(v1, kv_fmt)
        return {"block": blk,
                "pool_k_packed": updp(layer_cache["pool_k_packed"], kp),
                "pool_k_meta": updp(layer_cache["pool_k_meta"], km),
                "pool_v_packed": updp(layer_cache["pool_v_packed"], vp),
                "pool_v_meta": updp(layer_cache["pool_v_meta"], vm)}

    at = _layer_at(layer_cache)

    def upd(buf, val, plane: bool):
        # one dynamic_update_slice per slot, unrolled: XLA performs each
        # in place in the layout the attention kernel reads, where a
        # scatter (what a vmapped update becomes) or a loop of updates
        # makes it relayout the whole buffer.  A not-live slot writes
        # its old row back (row-level gating: no full-buffer select).
        for b in range(val.shape[0]):
            v = val[b:b + 1].astype(buf.dtype)[(None,) * len(at)]
            idx = at + (b,) + _row_index(v.ndim - len(at) - 1, slot[b],
                                         plane)
            if live is not None:
                v = jnp.where(live[b], v,
                              jax.lax.dynamic_slice(buf, idx, v.shape))
            buf = jax.lax.dynamic_update_slice(buf, v, idx)
        return buf

    if kv_fmt is None:
        return dict(layer_cache, k=upd(layer_cache["k"], k1, False),
                    v=upd(layer_cache["v"], v1, False))
    kp, km = _quantize_planes(k1, kv_fmt)
    vp, vm = _quantize_planes(v1, kv_fmt)
    return dict(layer_cache,
                k_packed=upd(layer_cache["k_packed"], kp, True),
                k_meta=upd(layer_cache["k_meta"], km, True),
                v_packed=upd(layer_cache["v_packed"], vp, True),
                v_meta=upd(layer_cache["v_meta"], vm, True))


def _row_index(ndim: int, row, plane: bool):
    """Start index, after the slot, of one context row of a slot's
    ``ndim``-dim leaf: the last axis of a plane view, else the first."""
    zeros = (0,) * (ndim - 1)
    return zeros + (row,) if plane else (row,) + zeros


def kv_slot_checksum(cfg: ModelConfig, cache, upto, horizon=None):
    """(B,) uint32 canary over each slot's LIVE, about-to-be-stable KV rows.

    The failure-containment primitive (DESIGN.md §11): decode APPENDS at
    ``pos``, so the rows a chunk does NOT write are immutable across it —
    a checksum computed before the chunk must match after it, or the
    slot's cache was corrupted.  The fold is ``core.pack.byte_fold`` per
    (layer, slot, row) — bit-exact over packed uint8/uint16 buffers and
    bitcast bf16 alike — combined with odd per-row weights, so a flipped
    byte OR two swapped rows both change the canary.

    ``upto`` is (B,) int32 (each slot's ``pos``); slots with
    ``upto[b] == 0`` contribute the trivially stable 0 (mid-prefill and
    parked slots).  With ``horizon=None`` the fold covers the append-only
    prefix ``[0, upto)`` — correct until an SWA ring wraps, at which
    point the "prefix" is no longer immutable.  ``horizon`` (scalar or
    (B,), the max rows the next chunk may write per slot) makes the fold
    WINDOW-AWARE: it covers the occupied rows (``row < min(upto, S)`` —
    the whole ring once wrapped) MINUS the rows within ``horizon`` of
    the write pointer in ring distance (``(row - upto) mod S``), i.e.
    exactly the rows a healthy chunk cannot touch.  Unwrapped slots with
    ``upto + horizon <= S`` exclude nothing — the horizon mask reduces
    to the plain prefix — so wrapped SWA slots stay ARMED instead of
    being disarmed wholesale (the pre-fix behavior).  ``horizon >= S``
    excludes every row (vacuous canary — callers should disarm).

    Caches without attention KV leaves (pure-SSM families) return zeros
    — integrity there is vacuous, not checked.  Runs unchanged per shard
    under the slot-sharded manual shard_map (no cross-slot terms).
    """
    b = cache["pos"].shape[0]
    total = jnp.zeros((b,), jnp.uint32)
    layers = cache.get("layers")
    if layers is None:
        return total
    upto = jnp.asarray(upto, jnp.int32)
    hz = None if horizon is None else jnp.broadcast_to(
        jnp.asarray(horizon, jnp.int32), (b,))
    for name in _KV_LEAVES:
        leaf = layers.get(name)
        if leaf is None:
            continue
        if name in _PLANE_LEAVES:                       # rows minor
            leaf = jnp.moveaxis(leaf, -1, 2)
        f = byte_fold(leaf, 3)                          # (L, B, S)
        s = leaf.shape[2]
        rw = 2 * jnp.arange(s, dtype=jnp.uint32) + 1
        r = jnp.arange(s, dtype=jnp.int32)[None, :]
        if hz is None:
            mask = r < upto[:, None]
        else:
            occupied = r < jnp.minimum(upto, s)[:, None]
            dist = jnp.mod(r - upto[:, None], s)        # ring distance
            mask = occupied & (dist >= hz[:, None])
        mask = mask.astype(jnp.uint32)
        total = total + jnp.sum(f * rw[None, None, :] * mask[None],
                                axis=(0, 2), dtype=jnp.uint32)
    return total


def ssm_state_checksum(cfg: ModelConfig, cache):
    """(B,) uint32 canary over each slot's recurrent SSM state.

    The SSM analogue of ``kv_slot_checksum`` — but the invariant is
    different: the recurrent ``h``/``conv`` state legitimately changes
    INSIDE a decode chunk (it integrates every step), so there is no
    stable-across-the-chunk prefix to pin.  What must hold is at-REST
    integrity: the checksum taken after one chunk must match right
    before the next, because nothing but decode, admission and slot
    resets may touch the state — and the engine re-arms at each of
    those.  A mismatch on an armed idle slot is memory corruption.

    Folds every element (no row mask — state has no sequence axis) via
    the same bit-exact ``byte_fold``; caches without SSM state return
    zeros.  Per-slot terms only, so it runs unchanged per shard under
    the manual shard_map.
    """
    b = cache["pos"].shape[0]
    total = jnp.zeros((b,), jnp.uint32)
    layers = cache.get("layers")
    if layers is None:
        return total
    for name in ("h", "conv"):
        leaf = layers.get(name)
        if leaf is None:
            continue
        f = byte_fold(leaf, 2)                          # (L, B)
        total = total + jnp.sum(f, axis=0, dtype=jnp.uint32)
    return total


def attend_decode(cfg: ModelConfig, layer_cache, q, pos,
                  kv_fmt: Optional[str]):
    """q (B, H, hd) attends to one layer's cache; pos (B,) per-slot positions.

    Each slot attends over its OWN valid length (``min(pos[b]+1, window)``)
    — ragged slots are first-class, not a broadcast scalar. Returns
    (B, H, hd) f32.
    """
    b, h, hd = q.shape
    kvh = cfg.n_kv_heads
    w = cfg.sliding_window
    pos = _per_slot(pos, b)
    lengths = jnp.minimum(pos + 1, w) if w else pos + 1

    if "block" in layer_cache:
        # paged: gather the pool through the block table into the exact
        # dense (B, S, ...) view, then fall through to the SAME
        # attention code — shapes, masking and reduction order are
        # identical to the fixed-slot engine, so outputs are bitwise
        # equal on valid rows (garbage rows are masked by `lengths`).
        # The view is in the row layout; the kernel reads the plane view.
        layer_cache = paged_layer_view(layer_cache)
        if kv_fmt is not None:
            layer_cache = _to_planes(layer_cache, _group_bytes(kv_fmt))

    layer = layer_cache.get("layer")
    if kv_fmt is not None:
        return decode_attention_planes(
            q, layer_cache["k_packed"], layer_cache["k_meta"],
            layer_cache["v_packed"], layer_cache["v_meta"], lengths,
            get_format(kv_fmt), layer=layer)

    k, v = layer_cache["k"], layer_cache["v"]                  # (B,S,KVH,hd)
    if layer is not None:
        k, v = k[layer], v[layer]
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd).astype(jnp.float32) * (hd ** -0.5)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    s = k.shape[1]
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, hd)
