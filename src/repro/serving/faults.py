"""Seeded fault-injection harness for the continuous serving engines.

A ``FaultPlan`` is a declarative, seed-deterministic schedule of faults
(DESIGN.md §11) the engine consults at chunk boundaries through no-op-by-
default hooks: with no plan (or an exhausted one) the serve loop runs the
exact same device programs on the exact same inputs, so the bitwise
serving oracle is untouched.  Four fault kinds:

- ``nan_logits``: poison the victim slot's logits to NaN inside the next
  decode chunk (a ``jnp.where`` on a device-side mask — the all-False
  mask is the no-op default).  Exercises the finite-logits sentinel.
- ``kv_flip``: XOR random bytes of the victim slot's *packed* KV rows
  already written (rows ``[0, pos)``).  Exercises the opt-in KV canary
  (``kv_integrity=True``); requires a packed KV format.
- ``delay``: host-side sleep at a chunk boundary — models a slow shard /
  GC pause and lets deadline enforcement be tested without flakiness.
- ``burst``: rewrites request arrival times into a ``[t0, t0 + span)``
  burst (order-preserving) to drive the bounded admission queue into
  shedding.  Applied once at ``serve()`` entry, not at chunk boundaries.
- ``shard_down``: drains one shard of a sharded engine at the chunk
  boundary — live requests snapshot-migrate onto healthy shards and the
  scheduler stops routing there.  Exercises live migration end to end;
  an unsharded engine has no shards and rejects the plan loudly.

Faults are one-shot: each fires at the first chunk boundary ``>= chunk``
where its victim is actually live (so a fault aimed at a queued request
waits for admission instead of silently missing).  All randomness flows
from ``default_rng([seed, fault_index])`` — the same plan on the same
workload corrupts the same bytes every run.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax

__all__ = ["Fault", "FaultPlan", "flip_kv_bytes", "KINDS"]

KINDS = ("nan_logits", "kv_flip", "delay", "burst", "shard_down")


@dataclasses.dataclass(frozen=True)
class Fault:
    """One scheduled fault.

    kind:    one of ``KINDS``.
    chunk:   earliest chunk boundary (0-based, counted per ``serve()``)
             at which the fault may fire.
    uid:     victim request uid for nan_logits / kv_flip.
    shard:   victim shard for shard_down; informational tag for delay
             faults (which "shard" stalled).
    seconds: sleep length for delay faults.
    n_bytes: number of packed-KV bytes to corrupt for kv_flip.
    t0/span: burst window for arrival-time rewrites.
    """
    kind: str
    chunk: int = 0
    uid: Optional[int] = None
    shard: Optional[int] = None
    seconds: float = 0.0
    n_bytes: int = 1
    t0: float = 0.0
    span: float = 0.0

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"expected one of {KINDS}")
        if self.kind in ("nan_logits", "kv_flip") and self.uid is None:
            raise ValueError(f"{self.kind} fault needs a victim uid")
        if self.kind == "shard_down" and self.shard is None:
            raise ValueError("shard_down fault needs a victim shard")


@dataclasses.dataclass
class FaultPlan:
    """A seeded schedule of ``Fault``s plus one-shot firing state."""
    faults: Sequence[Fault] = ()
    seed: int = 0

    def __post_init__(self):
        self._fired: set = set()

    def reset(self) -> None:
        """Re-arm every fault (called at ``serve()`` entry)."""
        self._fired.clear()

    def pending(self, kind: str, chunk_idx: int) -> List[Tuple[int, Fault]]:
        """Unfired faults of ``kind`` whose chunk boundary has arrived."""
        return [(i, f) for i, f in enumerate(self.faults)
                if f.kind == kind and i not in self._fired
                and f.chunk <= chunk_idx]

    def fire(self, i: int) -> None:
        self._fired.add(i)

    def rng(self, i: int) -> np.random.Generator:
        """Per-fault generator: deterministic in (plan seed, fault index)."""
        return np.random.default_rng([self.seed, i])

    def apply_arrivals(self, requests):
        """Apply burst faults: collapse arrivals into ``[t0, t0 + span)``.

        Arrival ORDER is preserved (requests are re-timed, not reordered),
        so admission-policy comparisons stay apples-to-apples.  Burst
        faults fire here, once, at serve() entry.
        """
        reqs = list(requests)
        for i, f in self.pending("burst", chunk_idx=10**9):
            self.fire(i)
            order = sorted(range(len(reqs)),
                           key=lambda j: (reqs[j].arrival_time, j))
            offs = np.sort(self.rng(i).uniform(0.0, max(f.span, 0.0),
                                               size=len(reqs)))
            for rank, j in enumerate(order):
                reqs[j] = dataclasses.replace(
                    reqs[j], arrival_time=f.t0 + float(offs[rank]))
        return reqs


def flip_kv_bytes(cache, slot: int, n_rows: int, rng, n_bytes: int = 1):
    """XOR ``n_bytes`` random bytes in slot ``slot``'s packed KV rows.

    Corrupts only rows ``[0, n_rows)`` — rows the cache has already
    committed — across the packed payload/meta leaves, mimicking an HBM
    bit flip in quantized KV state.  Dense/SSM caches have no packed
    leaves and raise: the canary (and this fault) is a statement about
    the packed-KV byte stream.  Returns a new cache pytree; device
    placement (sharding) of the edited leaf is preserved.
    """
    layers = cache.get("layers") or {}
    names = [n for n in ("k_packed", "v_packed", "k_meta", "v_meta")
             if layers.get(n) is not None]
    if not names:
        raise ValueError("kv_flip needs a packed KV cache "
                         "(kv_format with packed k/v leaves)")
    if n_rows <= 0:
        return cache
    new_layers = dict(layers)
    for _ in range(n_bytes):
        name = names[int(rng.integers(len(names)))]
        buf = new_layers[name]
        arr = np.array(jax.device_get(buf))     # copy: device_get is RO
        if arr.dtype == np.uint16:  # meta leaves: flip one byte of the u16
            view = arr.view(np.uint8).reshape(arr.shape + (2,))
        else:
            view = arr
        # the stored packed leaves keep their context rows on the last
        # axis (the plane view, models.kvcache)
        row = int(rng.integers(min(n_rows, arr.shape[-1])))
        idx = [int(rng.integers(d)) for d in view.shape]
        idx[1], idx[arr.ndim - 1] = slot, row
        idx = tuple(idx)
        view[idx] ^= np.uint8(rng.integers(1, 256))
        new_layers[name] = jax.device_put(arr, buf.sharding)
    return dict(cache, layers=new_layers)
