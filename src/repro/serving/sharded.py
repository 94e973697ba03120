"""Slot-sharded continuous serving: the slot axis over a 'data' mesh.

``ContinuousEngine`` runs one host loop against one device.  This module
scales the SAME loop over a multi-device 'data' mesh (DESIGN.md §10): the
B-slot cache is partitioned as S contiguous blocks of ``n_slots / S``
slots, one block per shard, and every dispatch that touches it — the
decode chunk, the chunked-prefill lane, whole-prompt admission, the
first-token finish and the eviction park — runs under a FULLY-MANUAL
``shard_map`` (``sharding.shard_map_manual``; manual over every mesh
axis, which is the one shard_map shape the CPU partitioner does not
CHECK-abort on, so the bitwise oracle can run under
``--xla_force_host_platform_device_count``).

Inside the manual body each shard sees the plain per-shard
continuous-batching problem: local (B/S,) slot vectors, a local cache
slice, its OWN batch-1 prefill lane.  Decode is row-independent end to
end (per-slot rope/ring-write/masked-attend/sampling — the PR-3
invariant), so the body is literally ``ContinuousEngine._chunk_fn`` and
greedy outputs are bit-identical to the unsharded engine, which stays
the oracle.  Slot surgery targets ONE global slot; every shard runs the
same program and the owner (``slot // slots_per_shard``) alone commits
the write, via the value-gated row updates threaded through
``write_cache_slot`` / ``reset_slot`` / ``layer_prefill_chunk``
(``apply=``) — no full-cache selects.

Weights are replicated over the mesh (``P()``); model-axis tensor
parallelism composes via a partial-auto shard_map (manual 'data', auto
'model') — a TPU-only shape, gated like the gradient wire
(``sharding.partial_auto_ok``), left to the first real-TPU run.

The payoff over one-host serving: S shards decode S×B_local slots for
one dispatch's host latency, admission routes to the least-loaded shard
(``ShardedSlotScheduler``), and each shard owns a prefill LANE — S
prompts mid-prefill concurrently where PR 4 had one global lane, with
idle shards riding the fused lane dispatch as no-ops (``n_valid=0``
drops their scatter rows; ``active=False`` gates their SSM writes).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.qtensor import QuantPolicy
from repro.models import (init_cache, init_lane, prefill_chunk,
                          prefill_into_slot, read_cache_slot, reset_slot,
                          write_cache_slot)
from repro.models.common import ModelConfig
from repro.models.kvcache import kv_slot_checksum, ssm_state_checksum
from repro.sharding import (mesh_fingerprint, shard_map_manual,
                            slot_cache_specs)
from .engine import cached_program, named_jit
from .scheduler import (PREFILLING, ContinuousEngine,
                        ShardedSlotScheduler, SlotScheduler)
from .snapshot import take_owner_row

_R = P()            # replicated
_Pd = P("data")     # leading dim over the slot shards


def _owner_apply(slot, nloc):
    """(owner shard, local slot, am-I-the-owner) for a global slot.

    Every shard evaluates the same expression inside the manual body;
    ``local`` is in range on every shard (same value everywhere), and
    only the owner's ``apply`` is True — the value-gated updates
    (``common.gated_update_slice``) do the rest.
    """
    owner = slot // nloc
    return owner, slot - owner * nloc, \
        jax.lax.axis_index("data") == owner


class ShardedContinuousEngine(ContinuousEngine):
    """``ContinuousEngine`` with the slot axis sharded over 'data'.

    Same host loop, same request semantics, same bitwise guarantees as
    the unsharded engine (greedy outputs are bit-identical — the
    unsharded engine is the oracle; see tests/test_sharded_serving.py).
    Requires an effectively 1-D ``('data',)`` mesh of S devices with
    ``n_slots % S == 0``; every other constructor argument matches
    ``ContinuousEngine``.
    """

    def __init__(self, cfg: ModelConfig, params, policy: QuantPolicy,
                 mesh, n_slots: int = 4, **kw):
        if "data" not in mesh.axis_names:
            raise ValueError(f"slot sharding needs a 'data' mesh axis, "
                             f"got {mesh.axis_names}")
        extra = [a for a in mesh.axis_names
                 if a != "data" and mesh.shape[a] != 1]
        if extra:
            # model-axis TP inside the manual region would need manual
            # collectives the model bodies don't emit; the composed
            # manual-data/auto-model shape is partial-auto = TPU-only
            raise ValueError(f"fully-manual slot sharding supports a "
                             f"data-only mesh; non-trivial axes {extra}")
        s = int(mesh.shape["data"])
        if n_slots % s:
            raise ValueError(f"n_slots ({n_slots}) must be divisible by "
                             f"the 'data' axis ({s})")
        self.mesh = mesh
        self.n_shards = s
        self.slots_per_shard = n_slots // s
        # drain state persists across serve() calls: a shard taken down
        # stays out of rotation until a new engine is built
        self._drained: set = set()
        self._drain_req: set = set()
        super().__init__(cfg, params, policy, n_slots=n_slots, **kw)

    # -- placement ----------------------------------------------------------

    def _mesh_fingerprint(self):
        return mesh_fingerprint(self.mesh)

    def _place_params(self, params):
        rep = NamedSharding(self.mesh, _R)
        return jax.device_put(params, jax.tree.map(lambda _: rep, params))

    def _init_slot_cache(self):
        cache = init_cache(self.cfg, self.n_slots, self.max_len, self._kv)
        put = {n: jax.tree.map(
            lambda _, sp=self._cspec[n]: NamedSharding(self.mesh, sp),
            cache[n]) for n in cache}
        return jax.device_put(cache, put)

    def _cache_eval_shape(self):
        """Abstract cache pytree the per-group shard specs derive from.

        Overridable so layout variants (the paged cache) shard through
        the same program-building path: ``slot_cache_specs`` maps each
        group's batch-prefix spec over whatever leaves the layout has.
        """
        cfg, kv, max_len = self.cfg, self._kv, self.max_len
        return jax.eval_shape(
            lambda: init_cache(cfg, self.n_slots, max_len, kv))

    # -- shard_map'd programs ------------------------------------------------

    def _build_programs(self) -> None:
        cfg, kv, max_len = self.cfg, self._kv, self.max_len
        mesh, mk, nloc = self.mesh, self._mesh_key, self.slots_per_shard
        cspec = self._cspec = slot_cache_specs(self._cache_eval_shape())

        def admit_body(params, batch, cache, slot, key, temperature):
            # owner-only prefill (ROADMAP pod-scale item): the batch-1
            # prefill used to run REPLICATED on every shard (identical
            # bits, S-1 shards' compute wasted).  Per-device control flow
            # is legal under the fully-manual shard_map, so non-owners
            # now take the cond's cheap branch — cache untouched, zero
            # logits — and only the owner pays the prefill.  The host
            # reads the owner's row of the stacked outputs, so the
            # non-owner garbage tok0/key rows are never consumed.
            _, local, apply = _owner_apply(slot, nloc)

            def owner(c):
                return prefill_into_slot(cfg, params, batch, c, local,
                                         max_len, kv, apply=apply)

            def rider(c):
                return jnp.zeros((1, cfg.vocab), jnp.float32), c

            logits, new_cache = jax.lax.cond(apply, owner, rider, cache)
            tok0, key_out = ContinuousEngine._first_token(
                logits, key, temperature)
            # per-shard scalars leave as a (S,)-stacked 'data' dim — the
            # host reads the owner's row; out_specs P() would need a
            # replication proof the manual body can't give
            return tok0.reshape(1), key_out.reshape(1, 2), new_cache

        # nloc rides every key whose body closes over it: engines with a
        # different n_slots on the SAME mesh map slots differently
        self._prefill = cached_program(
            ("admit", cfg, kv, max_len, mk, nloc),
            lambda: named_jit("admit", shard_map_manual(
                admit_body, mesh,
                in_specs=(_R, _R, cspec, _R, _R, _R),
                out_specs=(_Pd, _Pd, cspec))))

        def reset_body(cache, slot):
            _, local, apply = _owner_apply(slot, nloc)
            return reset_slot(cfg, cache, local, apply=apply)

        self._reset = self._keep_cache(cached_program(
            ("reset", cfg, mk, nloc),
            lambda: named_jit("reset_slot", shard_map_manual(
                reset_body, mesh, in_specs=(cspec, _R), out_specs=cspec),
                donate_argnums=0)), 0, None)

        # the decode chunk body IS the unsharded one — decode is row-
        # independent, so manual sharding is pure slicing (the bitwise
        # oracle rests exactly here); only (n_steps, greedy) are static
        chunk_in = (_R, _Pd, cspec, _Pd, _Pd, _Pd, _Pd, _Pd, _Pd, _Pd, _Pd)
        chunk_out = (_Pd, _Pd, cspec, _Pd, _Pd, _Pd, _Pd)

        def build_chunk():
            memo: Dict[Any, Any] = {}

            def chunk(params, tok, cache, keys, done, n_gen, max_new,
                      temp, stop, live, poison, *, n_steps: int,
                      greedy: bool):
                fn = memo.get((n_steps, greedy))
                if fn is None:
                    body = functools.partial(
                        ContinuousEngine._chunk_fn, cfg=cfg, kv_fmt=kv,
                        n_steps=n_steps, greedy=greedy)
                    fn = memo[(n_steps, greedy)] = named_jit(
                        "decode_chunk", shard_map_manual(
                            body, mesh, in_specs=chunk_in,
                            out_specs=chunk_out))
                return fn(params, tok, cache, keys, done, n_gen, max_new,
                          temp, stop, live, poison)

            return chunk

        self._chunk_jit = cached_program(("cont_chunk", cfg, kv, mk),
                                         build_chunk)

        if self.speculative is not None:
            # the speculative chunk body is the unsharded one, sliced:
            # draft, verify and accept/commit are all per-slot (rows
            # independent), so each shard runs its local slots' rounds
            # and the greedy bitwise oracle carries over unchanged.
            # Acceptance stats come back per-slot; the host aggregates
            # per shard (``spec_shard_stats``).
            spec_in = (_R, _R) + chunk_in[1:] + (_Pd,)
            spec_out = chunk_out + (_Pd, _Pd)

            def build_spec():
                memo: Dict[Any, Any] = {}

                def spec(params, draft, tok, cache, keys, done, n_gen,
                         max_new, temp, stop, live, poison, spec_k, *,
                         k: int, n_rounds: int, greedy: bool):
                    fn = memo.get((k, n_rounds, greedy))
                    if fn is None:
                        body = functools.partial(
                            ContinuousEngine._spec_chunk_fn, cfg=cfg,
                            kv_fmt=kv, k=k, n_rounds=n_rounds,
                            greedy=greedy)
                        fn = memo[(k, n_rounds, greedy)] = named_jit(
                            "spec_chunk", shard_map_manual(
                                body, mesh, in_specs=spec_in,
                                out_specs=spec_out))
                    return fn(params, draft, tok, cache, keys, done,
                              n_gen, max_new, temp, stop, live, poison,
                              spec_k)

                return spec

            self._spec_jit = cached_program(("spec_chunk", cfg, kv, mk),
                                            build_spec)

        def snap_body(cache, slot):
            # every shard slices its local alias of the global slot; the
            # out-specs stack the batch-1 slices along the batch axis and
            # the host keeps the owner's row (snapshot.take_owner_row)
            _, local, _ = _owner_apply(slot, nloc)
            return read_cache_slot(cache, local)

        self._snap = cached_program(
            ("snap", cfg, kv, mk, nloc),
            lambda: named_jit("snap", shard_map_manual(
                snap_body, mesh, in_specs=(cspec, _R), out_specs=cspec)))

        def restore_body(cache, solo, slot):
            # the restore scatter is admission's owner-masking applied to
            # a replicated batch-1 payload: every shard runs the program,
            # only the owner commits the rows
            _, local, apply = _owner_apply(slot, nloc)
            return write_cache_slot(cache, solo, local, apply=apply)

        self._restore_prog = cached_program(
            ("restore", cfg, kv, mk, nloc),
            lambda: named_jit("restore", shard_map_manual(
                restore_body, mesh, in_specs=(cspec, _R, _R),
                out_specs=cspec)))

        if self.kv_integrity:
            # the canaries are per-slot arithmetic over the local cache
            # slice — the manual bodies are the unsharded checksums
            # verbatim
            if self._has_attn_kv:
                def kv_body(cache, upto, horizon):
                    return kv_slot_checksum(cfg, cache, upto,
                                            horizon=horizon)

                self._kv_check = cached_program(
                    ("kv_check", cfg, kv, mk),
                    lambda: named_jit("kv_check", shard_map_manual(
                        kv_body, mesh, in_specs=(cspec, _Pd, _R),
                        out_specs=_Pd)))
            if self._has_ssm:
                def ssm_body(cache):
                    return ssm_state_checksum(cfg, cache)

                self._ssm_check = cached_program(
                    ("ssm_check", cfg, mk),
                    lambda: named_jit("ssm_check", shard_map_manual(
                        ssm_body, mesh, in_specs=(cspec,),
                        out_specs=_Pd)))

    def _build_lane(self) -> None:
        cfg, kv, mesh, mk = self.cfg, self._kv, self.mesh, self._mesh_key
        cspec, pch = self._cspec, self.p_chunk
        lspec = P(None, "data")     # lane leaves stack shards at axis 1
        lane = init_lane(cfg, self.max_len, pch, n_lanes=self.n_shards)
        self.lane = jax.device_put(lane, jax.tree.map(
            lambda _: NamedSharding(mesh, lspec), lane))

        ring = self._lane_ring

        def lane_body(params, toks, cache, lane, slot, offset, n_valid,
                      active, wrapped, *, with_head: bool):
            # local view: ONE shard's lane advancing its own in-flight
            # prompt by one (1, P) chunk — idle shards run the same
            # program as a no-op (n_valid=0 drops every scatter row,
            # active=False gates the SSM slot writes).  ``wrapped`` is
            # PER SHARD: the unsharded engine picks the ring-lane graph
            # statically (one cursor, one flag), but the fused dispatch
            # advances S lanes whose prompts lap the scratch at different
            # chunks — so on ring-capable geometries (``_lane_ring``)
            # each shard selects its graph with a cond on its own flag.
            # Non-ring engines keep the single plain trace.
            def run(w: bool):
                return prefill_chunk(
                    cfg, params, toks, cache, slot[0], offset[0],
                    n_valid[0], lane, kv, with_head=with_head,
                    active=active[0], wrapped=w)

            if not ring:
                return run(False)
            return jax.lax.cond(wrapped[0], lambda: run(True),
                                lambda: run(False))

        def build_lane_fn():
            memo: Dict[bool, Any] = {}

            def lane_fn(params, toks, cache, lane, slot, offset, n_valid,
                        active, wrapped, *, with_head: bool):
                fn = memo.get(with_head)
                if fn is None:
                    body = functools.partial(lane_body,
                                             with_head=with_head)
                    fn = memo[with_head] = named_jit(
                        "lane_chunk", shard_map_manual(
                            body, mesh,
                            in_specs=(_R, _Pd, cspec, lspec, _Pd, _Pd, _Pd,
                                      _Pd, _Pd),
                            out_specs=(_Pd, cspec, lspec)),
                        donate_argnums=2)
                return fn(params, toks, cache, lane, slot, offset,
                          n_valid, active, wrapped)

            return lane_fn

        # ``ring`` rides the key: the cond-over-graphs trace differs from
        # the plain one, and ring-ness depends on max_len (via the lane
        # row count), which no other key component carries
        self._lane_fn = self._keep_cache(
            cached_program(("lane", cfg, kv, pch, mk, ring), build_lane_fn),
            2, 1)
        nloc = self.slots_per_shard

        def finish_body(logits, key, temperature, cache, slot, t):
            # the unsharded finish tail, owner-masked: first-token
            # equality stays shared code, not a copy
            _, local, apply = _owner_apply(slot, nloc)
            tok0, key_out, new_cache = ContinuousEngine._finish_prefill_fn(
                logits, key, temperature, cache, local, t, apply=apply)
            return tok0.reshape(1), key_out.reshape(1, 2), new_cache

        self._finish = self._keep_cache(cached_program(
            ("finish", cfg, mk, nloc),
            lambda: named_jit("lane_finish", shard_map_manual(
                finish_body, mesh,
                in_specs=(_R, _R, _R, cspec, _R, _R),
                out_specs=(_Pd, _Pd, cspec)), donate_argnums=3)), 3, 2)

    def _autotune_probes(self):
        """Probe the PER-SHARD bodies on one device (see base docstring).

        The per-shard decode workload is ``slots_per_shard`` slots
        through the UNSHARDED chunk program (keyed with mesh None, so
        it's shared with any unsharded engine on this config), against a
        throwaway single-device cache, with params read from one
        device's replica — both sides of the stall-budget ratio then
        measure the same regime, free of the GSPMD resharding a
        mesh-placed input would drag into the timings.
        """
        cfg, kv = self.cfg, self._kv
        fn = cached_program(
            ("cont_chunk", cfg, kv, None),
            lambda: named_jit("decode_chunk", functools.partial(
                ContinuousEngine._chunk_fn, cfg=cfg, kv_fmt=kv),
                static_argnames=("n_steps", "greedy")))
        b = self.slots_per_shard
        # the first mesh device's replica of the weights, not a copy: a
        # second full weight set on one device would not fit beside it
        dev = self.mesh.devices.flat[0]
        params = jax.tree.map(
            lambda a: next(s.data for s in a.addressable_shards
                           if s.device == dev), self.params)
        cache = jax.device_put(
            init_cache(cfg, b, self.max_len, kv), dev)
        return fn, params, cache, b

    # -- host loop deltas ----------------------------------------------------

    def _make_sched(self) -> SlotScheduler:
        sched = ShardedSlotScheduler(self.n_shards, self.slots_per_shard,
                                     policy=self.admission_policy,
                                     max_queue=self.max_queue,
                                     shedding=self.shedding,
                                     journal=self.journal)
        self._seed_sched(sched)
        return sched

    def _seed_sched(self, sched: SlotScheduler) -> None:
        super()._seed_sched(sched)
        sched.drained |= self._drained

    def _shard_of(self, slot: int):
        return slot // self.slots_per_shard

    def _snap_dispatch(self, slot: int) -> Dict[str, Any]:
        stacked = jax.device_get(self._snap(self.cache, jnp.int32(slot)))
        return take_owner_row(stacked, slot // self.slots_per_shard)

    def spec_shard_stats(self):
        """Per-shard speculative acceptance: accepted/offered/rate rows.

        The dispatch returns per-SLOT counts; slots map to shards as
        contiguous blocks, so the per-shard rollup is a host-side
        reshape — no extra collective.  Skew across rows is the signal a
        shard is serving draft-hostile traffic (its slots' adaptive k
        will have backed off).
        """
        if self.speculative is None:
            raise ValueError("engine was built without speculative=")
        acc = self._spec_acc_slot.reshape(self.n_shards, -1).sum(axis=1)
        off = self._spec_off_slot.reshape(self.n_shards, -1).sum(axis=1)
        return [{"shard": s, "accepted": int(acc[s]), "offered": int(off[s]),
                 "accept_rate": float(acc[s] / max(off[s], 1))}
                for s in range(self.n_shards)]

    # -- shard drain & live migration (§12) ---------------------------------

    def drain_shard(self, shard: int) -> None:
        """Take ``shard`` out of rotation at the next chunk boundary.

        Its live DECODING requests snapshot-migrate onto healthy shards'
        free slots (suspend-to-queue when none is free — they resume as
        capacity opens), mid-prefill requests abort their lane and
        requeue plain, and the scheduler stops routing admissions there.
        Validated at CALL time: draining the last healthy shard is
        refused loudly rather than discovered mid-sweep.  Safe to call
        mid-serve (``progress_cb``, fault injection) — same chunk-
        boundary contract as ``cancel``/``suspend``.
        """
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"no shard {shard} "
                             f"(n_shards={self.n_shards})")
        healthy_after = (set(range(self.n_shards)) - self._drained
                         - self._drain_req - {shard})
        if not healthy_after:
            raise ValueError(f"draining shard {shard} would leave no "
                             f"healthy shards")
        self._drain_req.add(shard)

    def _migration_target(self, sched) -> Any:
        """Least-loaded healthy shard's first free slot (None if full)."""
        healthy = {sched.shard_of(s) for s in sched.free} - sched.drained
        if not healthy:
            return None
        sh = min(healthy, key=lambda s: (sched.load(s), s))
        return sched.free_on(sh)[0]

    def _drain_sweep(self, sched, state, results, clock) -> None:
        while self._drain_req:              # drain-safe vs concurrent adds
            shard = self._drain_req.pop()
            if shard in self._drained:
                continue
            self._drained.add(shard)
            sched.drained.add(shard)
            self._emit("drain", shard=shard, live=sched.load(shard),
                       chunk=self._chunk_idx)
            lo = shard * self.slots_per_shard
            for slot in range(lo, lo + self.slots_per_shard):
                if slot not in sched.active:
                    continue
                if sched.phase.get(slot) == PREFILLING:
                    # a mid-prefill slot has no resumable state (§12):
                    # abort the lane, requeue, restart from chunk 0
                    req = self._abort_prefill(sched, slot)
                    sched.queue.append(req)
                    self._emit("suspend", uid=req.uid, slot=slot,
                               shard=shard, resumable=False)
                    continue
                tgt = self._migration_target(sched)
                if tgt is None:
                    # no healthy free slot: park resumable, the resume
                    # drain picks it up as capacity opens
                    self._suspend_slot(sched, state, slot, clock)
                    continue
                snap = self._snapshot_slot(sched, state, slot, clock)
                req = sched.reassign(slot, tgt)
                state.pop(slot, None)
                self._reset_dispatch(slot)
                self._park_slot_flags(slot)
                self._resume(sched, state, tgt, req, snap, clock,
                             event="migrate")

    def _lifecycle(self, sched, state, results, clock) -> None:
        super()._lifecycle(sched, state, results, clock)
        self._drain_sweep(sched, state, results, clock)

    def _drop_lane_cursor(self, slot: int) -> None:
        self._pf = {sh: pf for sh, pf in self._pf.items()
                    if pf["slot"] != slot}

    def _decode_live(self):
        # the sharded chunk program always takes the live vector (one
        # trace either mode); whole mode's live flags are maintained by
        # _arm_slot/eviction just the same
        return jnp.asarray(self._live)

    def _admit_dispatch(self, slot: int, req):
        batch = {"tokens": np.asarray(req.tokens, np.int32)[None]}
        key = jax.random.PRNGKey(req.seed)
        tok0, keys, self.cache = self._prefill(
            self.params, batch, self.cache, jnp.int32(slot), key,
            jnp.float32(req.temperature))
        owner = slot // self.slots_per_shard
        with self._loop.span("serve.lane_wait"):
            return np.asarray(tok0)[owner], np.asarray(keys)[owner]

    # per-shard lane cursors: {shard: cursor}; a missing key = idle lane
    def _park_lane(self) -> None:
        self._pf = {}

    def _lane_busy(self) -> bool:
        return bool(self._pf)

    def _advance_lane(self, sched: SlotScheduler, state: Dict[int, Any],
                      clock) -> None:
        """Advance EVERY shard's lane by one chunk in ONE fused dispatch.

        First, idle lanes pick up work: shards with a free slot and no
        in-flight prompt admit from the shared queue, least-loaded shard
        first (the policy still ranks WHICH request).  Then one
        shard_map'd dispatch advances all in-flight lanes together —
        S prompts mid-prefill cost the same wall-clock as one — and
        shards whose prompt completed run the finish (first-token sample
        + pos arm), exactly as the unsharded lane would have.
        """
        now = clock()
        while True:
            idle = [s for s in range(self.n_shards)
                    if s not in self._pf and s not in sched.drained
                    and sched.free_on(s)]
            if not idle:
                break
            shard = min(idle, key=lambda s: (sched.load(s), s))
            adm = sched.next_admission(now, shard=shard)
            if adm is None:
                break
            slot, req = adm
            snap = sched.resumable.pop(req.uid, None)
            if snap is not None:    # resume: no lane needed, keep going
                self._resume(sched, state, slot, req, snap, clock)
                continue
            self._pf[shard] = self._start_prefill(sched, slot, req, now,
                                                  shard=shard)
        if not self._pf:
            return
        s_n, pch = self.n_shards, self.p_chunk
        toks = np.zeros((s_n, pch), np.int32)
        lslot = np.zeros((s_n,), np.int32)
        offs = np.zeros((s_n,), np.int32)
        nval = np.zeros((s_n,), np.int32)
        act = np.zeros((s_n,), bool)
        wrap = np.zeros((s_n,), bool)
        finals: Dict[int, int] = {}
        for shard, pf in self._pf.items():
            req, off = pf["req"], pf["offset"]
            t = len(req.tokens)
            nv = min(pch, t - off)
            toks[shard, :nv] = req.tokens[off:off + nv]
            lslot[shard] = pf["slot"] % self.slots_per_shard
            offs[shard] = off
            nval[shard] = nv
            act[shard] = True
            wrap[shard] = off >= self._lane_rows
            if off + nv >= t:
                finals[shard] = t
        busy = sorted(self._pf)
        with self._loop.span(
                "serve.lane", uid=[self._pf[s]["req"].uid for s in busy],
                offset=[int(offs[s]) for s in busy],
                n_valid=[int(nval[s]) for s in busy], final=sorted(finals)):
            self._loop.add(lane_tokens=nval.sum())
            out, self.cache, self.lane = self._lane_fn(
                self.params, toks, self.cache, self.lane, jnp.asarray(lslot),
                jnp.asarray(offs), jnp.asarray(nval), jnp.asarray(act),
                jnp.asarray(wrap), with_head=bool(finals))
            for shard, pf in self._pf.items():
                if act[shard]:
                    pf["offset"] += int(nval[shard])
            for shard, t in finals.items():
                pf = self._pf.pop(shard)
                slot, req = pf["slot"], pf["req"]
                # out row `shard` is the owner's final-chunk logits
                key = jax.random.PRNGKey(req.seed)
                temp = jnp.float32(req.temperature)
                with self._loop.span("serve.lane_wait"):
                    tok0, keys, self.cache = self._finish(
                        out[shard:shard + 1], key, temp, self.cache,
                        jnp.int32(slot), jnp.int32(t))
                    tok0 = np.asarray(tok0)[shard]
                    keys = np.asarray(keys)[shard]
                self._arm_slot(slot, req, tok0, keys)
                sched.mark_decoding(slot)
                state[slot] = {"admit_time": pf["admit_time"], "out": [],
                               "prev_n_gen": 0,
                               "queue_delay": (pf["admit_time"]
                                               - req.arrival_time),
                               "ttft": clock() - req.arrival_time,
                               "decode_spent": 0.0}
                self._emit("prefill-done", uid=req.uid, shard=shard,
                           slot=slot, prompt=t, ttft=state[slot]["ttft"])
