"""Slot snapshots: the resumable state of one live serving request.

A DECODING slot's full restartable state is small and already
compressed: its KV rows ``[0, pos)`` in whatever format the engine
serves (packed NxFP bytes stay packed — no dequant round trip, the
direct-cast footprint argument applied to serving state), plus a few
per-slot scalars (``pos``, PRNG key, sampling temperature, stop token,
generation budget/progress) and the host-side partial output.  That is
everything preempt/resume, live shard migration and crash recovery
need, and restoring it through ``write_cache_slot`` is bit-exact: the
resumed request's remaining stream is identical to an uninterrupted
run.

The device payload is held as numpy (host RAM, picklable); KV row
leaves are trimmed to the rows actually written so an early suspend of
a long-budget request doesn't ship the whole preallocated slot.  SWA
ring caches trim to ``min(pos, ring_rows)`` — once the ring has
wrapped, every row is live.
"""
from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Any, Dict, List, Optional

import numpy as np

from ..models.kvcache import _KV_LEAVES, row_capacity
from ..models.lm import _batch_axis

__all__ = ["SlotSnapshot", "pack_device_state", "unpack_device_state",
           "slot_row_capacity", "take_owner_row", "save_checkpoint",
           "load_checkpoint"]

_ROW_LEAVES = frozenset(_KV_LEAVES)  # leaves with a sequence-row axis (2)


def slot_row_capacity(cache: Dict[str, Any]) -> Optional[int]:
    """Row capacity (window or max_len) of an engine cache's KV leaves
    (the stored layout, ``kvcache.row_capacity``).

    For a PAGED cache (DESIGN.md §14) the logical capacity is the block
    table's pages-per-slot times the pool's page size — the same number
    the dense layout stores directly, so snapshots from either engine
    interchange.  ``None`` for caches without attention KV (pure SSM) —
    nothing to trim or pad there.
    """
    layers = cache.get("layers")
    if layers is None:
        return None
    if "block" in layers:
        pool = next(v for n, v in layers.items() if n.startswith("pool_"))
        return int(layers["block"].shape[2]) * int(pool.shape[2])
    return row_capacity(layers)


def pack_device_state(solo: Dict[str, Any], used_rows: int) -> Dict[str, Any]:
    """Host-side snapshot payload from a batch-1 cache slice.

    KV row leaves keep only ``[0, used_rows)``; everything else (pos,
    ring meta rows travel with their packed rows, SSM state has no row
    axis) is copied whole.  Bytes are copied verbatim — packed uint8
    codes and uint16 scale meta never round-trip through dequant.
    """
    out: Dict[str, Any] = {"pos": np.array(solo["pos"], copy=True)}
    for gname, group in solo.items():
        if gname == "pos":
            continue
        g = {}
        for name, leaf in group.items():
            arr = np.asarray(leaf)
            if name in _ROW_LEAVES:
                arr = arr[:, :, :used_rows]
            g[name] = np.array(arr, copy=True)
        out[gname] = g
    return out


def unpack_device_state(dev: Dict[str, Any], row_capacity: Optional[int]):
    """Zero-pad trimmed KV rows back to the engine's slot capacity.

    The padding is written over rows the restored request has not
    reached: attention reads mask to ``pos`` and the KV canary folds
    only ``[0, pos)``, so zeros there cannot perturb anything.
    """
    out: Dict[str, Any] = {"pos": dev["pos"]}
    for gname, group in dev.items():
        if gname == "pos":
            continue
        g = {}
        for name, arr in group.items():
            if (name in _ROW_LEAVES and row_capacity is not None
                    and arr.shape[2] < row_capacity):
                pad = np.zeros(arr.shape[:2] + (row_capacity - arr.shape[2],)
                               + arr.shape[3:], arr.dtype)
                arr = np.concatenate([arr, pad], axis=2)
            g[name] = arr
        out[gname] = g
    return out


def take_owner_row(stacked: Dict[str, Any], owner: int) -> Dict[str, Any]:
    """Pick one shard's batch-1 slice out of a shard-stacked extract.

    Under manual shard_map every shard slices its local slot and the
    out-specs stack them along the batch axis; only the owning shard's
    row holds the request (the others sliced whichever local slot
    aliased the index).
    """
    out: Dict[str, Any] = {"pos": np.asarray(stacked["pos"][owner:owner + 1])}
    for gname, group in stacked.items():
        if gname == "pos":
            continue
        ax = _batch_axis(gname)
        out[gname] = {name: np.take(np.asarray(leaf), [owner], axis=ax)
                      for name, leaf in group.items()}
    return out


@dataclasses.dataclass
class SlotSnapshot:
    """Everything needed to resume one in-flight request in any free slot.

    ``device`` is the numpy payload from ``pack_device_state``;
    ``queue_delay``/``ttft`` are the request's REALIZED values (they
    happened before the suspension and survive clock rebasing across
    serves or processes); ``decode_spent`` accumulates occupied decode
    seconds across suspensions so ``decode_tok_s`` never charges the
    request for wall time it spent parked.
    """
    req: Any                   # the live Request (post-degrade)
    pos: int                   # rows written / ring pointer
    used_rows: int             # rows shipped in ``device``
    device: Dict[str, Any]     # batch-1 numpy cache slice, rows trimmed
    tok: int                   # next input token (last sampled/emitted)
    key: np.ndarray            # (2,) uint32 PRNG state after last chunk
    n_gen: int                 # tokens emitted so far
    max_new: int               # remaining budget baseline (post-degrade)
    temp: float
    stop: int
    out: List[int]             # partial output tokens (host copy)
    queue_delay: float         # realized at first admission
    ttft: float                # realized at first token
    decode_spent: float        # occupied seconds before this suspension
    # learned speculative draft length (0 = engine not speculative when
    # snapshotted; a speculative engine re-arms the default on resume).
    # Snapshots are only ever taken at chunk boundaries, where every
    # speculative round has fully committed — ``pos`` is always the last
    # COMMITTED position, never mid-draft state (DESIGN.md §13).
    spec_k: int = 0

    @property
    def nbytes(self) -> int:
        """Device-payload bytes — what a migration actually ships."""
        total = int(self.device["pos"].nbytes)
        for gname, group in self.device.items():
            if gname == "pos":
                continue
            total += sum(int(leaf.nbytes) for leaf in group.values())
        return total


def save_checkpoint(path, ck: Dict[str, Any]) -> None:
    """Atomically persist an engine checkpoint (write-then-rename)."""
    tmp = str(path) + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(ck, f)
    os.replace(tmp, str(path))


def load_checkpoint(path) -> Dict[str, Any]:
    with open(str(path), "rb") as f:
        return pickle.load(f)
