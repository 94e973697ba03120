"""Paged NxFP KV cache: block-table serving engines (DESIGN.md §14).

``ContinuousEngine`` preallocates every slot's KV arena at ``max_len``
(or the SWA window), so HBM is budgeted for the worst case whether or
not any request ever reaches it.  ``PagedContinuousEngine`` keeps the
same host loop, the same compiled decode/prefill/snapshot programs and
the same bitwise guarantees, but stores attention KV in a physical page
pool indexed through per-slot block tables: a request pins only
``ceil(min(prompt + max_new, window) / page_size)`` pages, so a fixed
KV HBM budget holds several times the dense engine's concurrent
in-flight requests (``benchmarks/serving_bench.py --scenario paged``
measures the multiplier).

The split of responsibilities:

- ``serving.paged.PagePool`` (host, jax-free): free-list allocation,
  refcounts, the shared-prefix registry, COW accounting.
- ``models/kvcache.py`` + ``models/lm.py`` (device): pool leaves, the
  block-table gather/scatter inside write/attend/snapshot — every
  compiled program dispatches on the ``block`` leaf, so this module
  never forks a model body.
- this module (the glue): every allocator decision is mirrored into
  the device block table through one tiny compiled program
  (``_table_write``), and every slot-retirement path releases its
  pages through the ``_reset_dispatch`` hook.

Bitwise contract: the dense-slot engine stays the oracle.  The paged
layout preserves each slot's LOGICAL row space exactly (window-sized
ring or max_len), the gathered pool view is the dense leaf bit for bit
on valid rows, and garbage rows (null/stale pages) surface only where
attention masks them to an exact-zero contribution — so every greedy
stream is bit-identical to the dense engine's, per slot, under whole
and chunked admission, suspend/resume, and sharding.

Prefix sharing is MEMORY dedupe, not compute dedupe: a claimant's
block table maps the registry's pages and its own prefill rewrites
them with byte-identical rows, so no skip-this-page flag threads
through any compiled program.  An SWA claimant that may outlive its
window reserves one replacement page per claimed page at admission and
is copy-on-write-privatized (``_cow_sweep``) before any dispatch whose
write horizon could wrap into shared territory — registry pages are
never clobbered, and the break can never hit an exhausted pool.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core.qtensor import QuantPolicy
from repro.models.common import ModelConfig, gated_update_slice
from repro.models.lm import init_paged_cache
from repro.sharding import shard_map_manual
from .engine import cached_program, named_jit
from .paged import NULL_PAGE, PagePool, auto_page_size
from .scheduler import ContinuousEngine, Request, SlotScheduler
from .sharded import _R, ShardedContinuousEngine, _owner_apply
from .snapshot import SlotSnapshot

__all__ = ["PagedContinuousEngine", "ShardedPagedContinuousEngine"]


def _table_write(cache, slot, row, apply=None):
    """Commit one slot's block-table row (L-replicated) on device.

    ``row`` is the slot's (P,) physical page map in logical order —
    NULL_PAGE beyond its reservation.  The table is L-replicated by
    construction (every layer maps rows identically), so one (1, P)
    update broadcast over L keeps it scan-compatible.  ``apply``
    (traced bool) owner-masks the write for the sharded engine.
    """
    layers = dict(cache["layers"])
    blk = layers["block"]                                    # (L, B, P)
    rep = jnp.broadcast_to(jnp.asarray(row, jnp.int32)[None, None, :],
                           (blk.shape[0], 1, blk.shape[2]))
    layers["block"] = gated_update_slice(blk, rep, (0, slot, 0), apply)
    return dict(cache, layers=layers)


def _copy_page_fn(cache, src, dst):
    """Device copy of one physical page, src -> dst, on every pool leaf.

    The COW primitive: the new page must hold the old page's bytes
    verbatim (packed codes and meta alike) so the claimant's gathered
    view is unchanged by the remap.  One compiled program serves every
    (src, dst) pair — both are traced scalars.
    """
    layers = dict(cache["layers"])
    for name, leaf in cache["layers"].items():
        if name.startswith("pool_"):
            page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=1)
            layers[name] = jax.lax.dynamic_update_slice_in_dim(
                leaf, page, dst, axis=1)
    return dict(cache, layers=layers)


class PagedContinuousEngine(ContinuousEngine):
    """``ContinuousEngine`` over a paged KV cache with prefix sharing.

    Same request semantics and host loop as the dense engine; admission
    is additionally gated on page availability (``SlotScheduler.
    admission_gate``), so a free SLOT without free PAGES queues the
    request instead of corrupting the pool.  ``n_pages`` defaults to
    the dense engine's footprint (every slot can hold its full row
    capacity); provision FEWER pages to serve more slots than the dense
    layout could back — the bench's concurrency multiplier.

    ``prefix_sharing`` content-hashes page-aligned prompt prefixes:
    admissions whose prompt extends a registered prefix map the shared
    pages instead of drawing fresh ones (refcounted, LRU-evicted,
    COW-broken before any divergent write).  ``kv_integrity`` is not
    served — the KV canary folds dense leaves and shared pages break
    its stable-prefix premise; quarantine still works via the
    finite-logits sentinel.
    """

    def __init__(self, cfg: ModelConfig, params, policy: QuantPolicy,
                 n_slots: int = 4, max_len: int = 2048,
                 n_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 prefix_sharing: bool = True, **kw):
        if kw.get("kv_integrity"):
            raise ValueError(
                "kv_integrity is not served by the paged engine: the KV "
                "canary pins a slot-private stable prefix, which prefix "
                "sharing deliberately violates")
        rows = cfg.sliding_window if cfg.sliding_window else max_len
        if page_size is None:
            page_size = auto_page_size(rows)
        if rows % page_size:
            raise ValueError(
                f"page_size {page_size} must divide the slot row "
                f"capacity {rows} (sliding window or max_len)")
        if n_pages is None:
            n_pages = self._default_n_pages(n_slots, rows // page_size)
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.prefix_sharing = bool(prefix_sharing)
        self._table_width = rows // self.page_size
        self._make_pools()
        super().__init__(cfg, params, policy, n_slots=n_slots,
                         max_len=max_len, **kw)

    # -- pool plumbing ------------------------------------------------------

    def _default_n_pages(self, n_slots: int, per_slot: int) -> int:
        """Dense-equivalent provisioning: every slot can hold its full
        logical capacity, plus the reserved null page."""
        return n_slots * per_slot + 1

    def _make_pools(self) -> None:
        """One engine-wide pool (the sharded engine builds one per shard)."""
        self.pool = PagePool(self.n_pages, self.page_size)

    def _pool_of(self, shard: Optional[int]) -> PagePool:
        return self.pool

    def _all_pools(self) -> List[PagePool]:
        return [self.pool]

    def _pool_monitor(self) -> float:
        """Worst pool occupancy in [0, 1] — feeds shedding watermarks."""
        return max(p.occupancy() for p in self._all_pools())

    def pool_stats(self) -> List[Dict[str, Any]]:
        """Per-pool allocator counters (occupancy, high watermark, COW
        breaks, prefix hits, evictions) for benches and dashboards."""
        pools = self._all_pools()
        out = []
        for shard, pool in enumerate(pools):
            st = pool.stats()
            st["shard"] = shard if len(pools) > 1 else None
            out.append(st)
        return out

    def _emit_pool(self, shard: Optional[int]) -> None:
        st = self._pool_of(shard).stats()
        self._emit("pool", shard=shard, used=st["used"], free=st["free"],
                   occupancy=round(st["occupancy"], 4),
                   hwm=st["high_watermark"], shared=st["prefix_pages_shared"],
                   chunk=self._chunk_idx)

    # -- sizing and sharing policy ------------------------------------------

    def _pages_for(self, tokens_len: int, max_new: int) -> int:
        """Logical pages a request needs for its whole tenancy."""
        if not self._has_attn_kv:
            return 0
        rows = tokens_len + max_new
        w = self.cfg.sliding_window
        if w:
            rows = min(rows, w)
        return -(-rows // self.page_size)

    def _horizon_bound(self) -> int:
        """Static upper bound on rows ONE slot writes past ``pos`` in a
        single decode dispatch — including post-done overshoot."""
        if self.speculative is None:
            return self.chunk
        return max(self.chunk, self.speculative.k + 1)

    def _share_terms(self, req: Request):
        """(claim tokens, reserve, register_ok) for one fresh admission.

        A prompt participates in sharing when sharing is on, it spans at
        least one page, and (SWA) it fits the window — a wrapping
        PREFILL would rewrite claimed pages with divergent rows, which
        nothing may do.  ``reserve`` marks a claimant whose DECODE may
        wrap (prompt + budget + one dispatch's overshoot past the
        window): it pre-draws one COW replacement per claimed page so
        the later break cannot exhaust the pool, and its own prefix is
        NOT registered (its pages stop being prefix content at the
        wrap).
        """
        t = len(req.tokens)
        w = self.cfg.sliding_window
        if not (self.prefix_sharing and self._has_attn_kv
                and t >= self.page_size and (not w or t <= w)):
            return None, False, False
        can_wrap = bool(w) and t + req.max_new + self._horizon_bound() > w
        return list(req.tokens), can_wrap, not can_wrap

    def _admission_gate(self, req: Request, shard: Optional[int],
                        resumable: bool) -> bool:
        """Page-availability gate the scheduler consults after its pick."""
        if not self._has_attn_kv:
            return True
        pool = self._pool_of(shard)
        n = self._pages_for(len(req.tokens), req.max_new)
        if resumable:           # restores never share (divergent rows)
            return pool.would_fit(n)
        tokens, reserve, _ = self._share_terms(req)
        return pool.would_fit(n, tokens=tokens, reserve=reserve)

    # -- allocator <-> device-table mirroring -------------------------------

    def _write_table(self, slot: int, pages: Sequence[int]) -> None:
        row = np.full((self._table_width,), NULL_PAGE, np.int32)
        row[:len(pages)] = pages
        self.cache = self._table(self.cache, jnp.int32(slot),
                                 jnp.asarray(row))

    def _alloc_slot(self, slot: int, req: Request,
                    share: bool = True) -> None:
        """Pin a request's pages and mirror them into the block table."""
        if not self._has_attn_kv:
            return
        shard = self._shard_of(slot)
        pool = self._pool_of(shard)
        n = self._pages_for(len(req.tokens), req.max_new)
        tokens, reserve, _ = (self._share_terms(req) if share
                              else (None, False, False))
        m = pool.claimable(tokens, n) if tokens is not None else 0
        row = pool.allocate(slot, n, tokens=tokens, reserve=reserve)
        if row is None:
            # the admission gate ran on this request with this pool —
            # nothing allocates between the gate and here
            raise RuntimeError(
                f"page pool exhausted admitting uid={req.uid} into slot "
                f"{slot} ({n} pages needed, {pool.free} free)")
        self._write_table(slot, row)
        if m:
            self._emit("prefix-hit", uid=req.uid, slot=slot, shard=shard,
                       pages=m, rows=m * self.page_size,
                       reserved=m if reserve else 0)
        self._emit_pool(shard)

    # -- engine hook overrides ----------------------------------------------

    def _init_slot_cache(self):
        return init_paged_cache(self.cfg, self.n_slots, self.max_len,
                                self._kv, self.n_pages, self.page_size)

    def _build_programs(self) -> None:
        super()._build_programs()
        if self._has_attn_kv:
            self._build_paged_programs()

    def _build_paged_programs(self) -> None:
        cfg, kv, mk = self.cfg, self._kv, self._mesh_key
        key = (cfg, kv, mk, self.n_pages, self.page_size)
        self._table = cached_program(
            ("paged_table",) + key, lambda: named_jit("paged_table",
                                                      _table_write))
        self._copy_page = cached_program(
            ("paged_copy",) + key, lambda: named_jit("paged_copy",
                                                     _copy_page_fn))

    def _make_sched(self) -> SlotScheduler:
        sched = super()._make_sched()
        if self._has_attn_kv:
            # reclaim leftovers of an ABORTED previous serve (exception
            # mid-flight): release the pages host-side and null the
            # device table rows so whole-mode garbage writes from the
            # parked slots route to the drop path, not into pages a new
            # request may be handed
            for pool in self._all_pools():
                for slot in list(pool._slots):
                    pool.release(slot)
                    self._write_table(slot, [])
            sched.admission_gate = self._admission_gate
            sched.pool_monitor = self._pool_monitor
        return sched

    def _reset_dispatch(self, slot: int) -> None:
        super()._reset_dispatch(slot)
        if not self._has_attn_kv:
            return
        shard = self._shard_of(slot)
        pool = self._pool_of(shard)
        if pool.holds(slot):
            pool.release(slot)
            self._write_table(slot, [])
            self._emit_pool(shard)

    def _admit_dispatch(self, slot: int, req: Request):
        self._alloc_slot(slot, req)
        return super()._admit_dispatch(slot, req)

    def _start_prefill(self, sched: SlotScheduler, slot: int, req: Request,
                       now: float, shard=None) -> Dict[str, Any]:
        self._alloc_slot(slot, req)
        return super()._start_prefill(sched, slot, req, now, shard=shard)

    def _restore_dispatch(self, slot: int, snap: SlotSnapshot) -> None:
        # a restored slot's rows diverge from any registered prefix the
        # moment its decode resumes, so it re-enters unshared; the
        # snapshot zero-pads to full capacity and rows beyond the
        # allocation drop through null table entries
        self._alloc_slot(slot, snap.req, share=False)
        super()._restore_dispatch(slot, snap)

    def _arm_slot(self, slot: int, req: Request, tok0, key) -> None:
        super()._arm_slot(slot, req, tok0, key)
        if not self._has_attn_kv:
            return
        _, _, register_ok = self._share_terms(req)
        if register_ok:
            shard = self._shard_of(slot)
            pool = self._pool_of(shard)
            if pool.register_prefix(req.tokens, slot):
                self._emit_pool(shard)

    def _dispatch_chunk(self, poison):
        self._cow_sweep()
        return super()._dispatch_chunk(poison)

    def _cow_sweep(self) -> None:
        """Privatize shared pages of any slot whose next dispatch could
        wrap its SWA ring into them.

        Runs right before every decode dispatch with the dispatch's
        EXACT write horizon: a slot at ``pos`` may write rows
        ``pos .. pos + horizon - 1`` (mod window), so ``pos + horizon >
        window`` is the first moment shared territory is reachable —
        including post-done overshoot writes inside the chunk.  Non-SWA
        slots never write shared pages (decode rows land strictly past
        the page-aligned shared prefix), so the sweep is SWA-only.
        """
        w = self.cfg.sliding_window
        if not w or not self.prefix_sharing or not self._has_attn_kv:
            return
        holders = [s for s in range(self.n_slots)
                   if self._pool_of(self._shard_of(s)).has_shared(s)]
        if not holders:
            return
        hz = self._chunk_horizon()
        pos = np.asarray(jax.device_get(self.cache["pos"]))
        for slot in holders:
            if int(pos[slot]) + hz <= w:
                continue
            shard = self._shard_of(slot)
            pool = self._pool_of(shard)
            pairs = pool.cow_break(slot)
            for _, old, new in pairs:
                self.cache = self._copy_page(self.cache, jnp.int32(old),
                                             jnp.int32(new))
            self._write_table(slot, pool.slot_pages(slot))
            self._emit("cow-break", slot=slot, shard=shard,
                       pages=len(pairs), pos=int(pos[slot]),
                       chunk=self._chunk_idx)
            self._emit_pool(shard)


class ShardedPagedContinuousEngine(PagedContinuousEngine,
                                   ShardedContinuousEngine):
    """Slot-sharded serving over per-shard page pools.

    Pool leaves shard their page axis over 'data' exactly as slot
    leaves shard their batch axis (the same per-group prefix specs),
    so each shard owns a physically disjoint pool slice — block tables
    hold LOCAL physical indices and every shard has its own local null
    page 0.  Admission routing composes pool pressure with slot load:
    the scheduler consults the page gate per candidate shard and takes
    the least-loaded shard whose pool fits the request.  Prefix sharing
    is not served (a registry per shard would only dedupe within a
    shard and the COW copy program is not shard_map'd); pass
    ``prefix_sharing=False`` explicitly or leave the default.
    """

    def __init__(self, cfg: ModelConfig, params, policy: QuantPolicy,
                 mesh, n_slots: int = 4, prefix_sharing: bool = False,
                 **kw):
        if prefix_sharing:
            raise ValueError(
                "prefix_sharing is not served sharded: the registry and "
                "COW copy are engine-global, pools are per-shard")
        # _make_pools runs inside PagedContinuousEngine.__init__, before
        # ShardedContinuousEngine.__init__ validates and re-sets these
        if "data" not in mesh.axis_names:
            raise ValueError(f"slot sharding needs a 'data' mesh axis, "
                             f"got {mesh.axis_names}")
        self._pool_shards = int(mesh.shape["data"])
        super().__init__(cfg, params, policy, n_slots=n_slots, mesh=mesh,
                         prefix_sharing=False, **kw)

    def _default_n_pages(self, n_slots: int, per_slot: int) -> int:
        """Dense-equivalent per shard: each shard's slot quota at full
        capacity, plus that shard's own local null page."""
        s = self._pool_shards
        return s * ((n_slots // s) * per_slot + 1)

    def _make_pools(self) -> None:
        s = self._pool_shards
        if self.n_pages % s:
            raise ValueError(f"n_pages ({self.n_pages}) must be divisible "
                             f"by the 'data' axis ({s}) — pools are "
                             f"per-shard pool-leaf slices")
        self.pool = None
        self._pools = [PagePool(self.n_pages // s, self.page_size)
                       for _ in range(s)]

    def _pool_of(self, shard: Optional[int]) -> PagePool:
        return self._pools[0 if shard is None else shard]

    def _all_pools(self) -> List[PagePool]:
        return list(self._pools)

    def _cache_eval_shape(self):
        cfg, kv, max_len = self.cfg, self._kv, self.max_len
        return jax.eval_shape(
            lambda: init_paged_cache(cfg, self.n_slots, max_len, kv,
                                     self.n_pages, self.page_size))

    def _init_slot_cache(self):
        cache = init_paged_cache(self.cfg, self.n_slots, self.max_len,
                                 self._kv, self.n_pages, self.page_size)
        put = {n: jax.tree.map(
            lambda _, sp=self._cspec[n]: NamedSharding(self.mesh, sp),
            cache[n]) for n in cache}
        return jax.device_put(cache, put)

    def _build_paged_programs(self) -> None:
        cfg, kv, mk = self.cfg, self._kv, self._mesh_key
        mesh, cspec = self.mesh, self._cspec
        nloc = self.slots_per_shard

        def table_body(cache, slot, row):
            # every shard runs the same program on its local cache
            # slice; the owner alone commits its local slot's row —
            # the row values are LOCAL physical indices in the owner's
            # pool slice, meaningless (and unwritten) elsewhere
            _, local, apply = _owner_apply(slot, nloc)
            return _table_write(cache, local, row, apply=apply)

        self._table = cached_program(
            ("paged_table", cfg, kv, mk, nloc, self.n_pages,
             self.page_size),
            lambda: named_jit("paged_table", shard_map_manual(
                table_body, mesh, in_specs=(cspec, _R, _R),
                out_specs=cspec)))
        # no COW copy program: prefix sharing (the only writer of shared
        # pages) is not served sharded
