"""Continuous-batching scheduler: admit requests into live decode slots.

``ServeEngine`` serves FIXED batches in lockstep — every sequence waits
for the slowest, and a finished slot idles until the whole batch drains.
This module adds the other half of a production serving loop (DESIGN.md
§8): a ``ContinuousEngine`` that keeps ONE persistent B-slot cache on
device and a ``SlotScheduler`` that, at every chunk boundary (the natural
admission point PR 2 created), evicts finished slots and prefills queued
requests into them while the neighbors keep decoding.

Admission itself comes in two modes (DESIGN.md §9):

- ``prefill_mode="whole"`` — one monolithic batch-1 prefill dispatch per
  admission.  Simple, and it compiles one program per distinct prompt
  length; a long prompt stalls every decoding slot for its whole length.
- ``prefill_mode="chunked"`` — the chunked-prefill LANE: prompts are
  split across chunk boundaries into fixed-shape (1, P_CHUNK) partial
  prefills (``models.prefill_chunk``), at most one lane chunk advancing
  between decode chunks.  Admission stalls are bounded by P_CHUNK, and
  the fixed shape means ONE compiled program for every prompt length —
  no mid-traffic retraces.  Slots move PREFILLING -> DECODING; mid-lane
  slots ride the decode batch write-masked (``live``).

WHICH queued request a free slot admits is a pluggable
``AdmissionPolicy`` (FIFO, shortest-prompt-first, TTFT-deadline
least-slack) behind ``SlotScheduler.next_admission``.

The engine also scales out: ``serving.sharded.ShardedContinuousEngine``
runs this same loop with the slot axis sharded over a 'data' mesh
(DESIGN.md §10) — ``ShardedSlotScheduler`` here does its shard-routed
admission bookkeeping, and the construction hooks on ``ContinuousEngine``
(``_build_programs`` / ``_build_lane`` / ``_make_sched`` / lane-cursor
plumbing) are the seams it overrides.

The whole design leans on the per-slot position plumbing: ``cache["pos"]``
is a (B,) vector, each slot ropes/writes/attends at its own offset, and
``prefill_into_slot`` scatters a batch-1 prefill into one slot of the live
cache. Per-request determinism is preserved exactly — a request served
through the continuous engine emits the SAME greedy tokens as serving it
alone through ``ServeEngine(loop="host")``, and sampled requests follow
the per-request seed's split chain — which is what makes the whole
scheduler testable against a bit-equality oracle.  Since the decode path
routes MoE through per-slot expert capacity (``moe_ffn_decode``), the
guarantee covers ``family="moe"`` too — under WHOLE-prompt admission.
MoE prefill routes with chunk-local expert capacity, so the one
combination outside the bitwise contract is ``family="moe"`` +
``prefill_mode="chunked"`` (allowed — padding is masked out of routing,
the serving behavior is sane — but logged at engine init; DESIGN.md §9).
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qtensor import QuantPolicy, dense_like, direct_cast_tree
from repro.kernels.ops import quantize_qtensor
from repro.models import (decode_loop, init_cache, init_lane, prefill_chunk,
                          prefill_into_slot, read_cache_slot, reset_slot,
                          write_cache_slot)
from repro.models.common import ModelConfig, gated_update_slice
from repro.models.kvcache import kv_slot_checksum, ssm_state_checksum
from .engine import cached_program, mask_chunk_emissions, named_jit
from .events import Journal, Loop, replay
from .faults import flip_kv_bytes
from .snapshot import (SlotSnapshot, load_checkpoint, pack_device_state,
                       save_checkpoint, slot_row_capacity,
                       unpack_device_state)
from .speculative import AdaptiveK, SpeculativeConfig, pack_emissions, \
    spec_round

logger = logging.getLogger("repro.serving.scheduler")


class Status:
    """Terminal request statuses (DESIGN.md §11) — plain strings so they
    serialize into the JSONL event stream and bench CSVs unchanged.

    Every submitted request gets EXACTLY ONE result with one of these:
    OK (ran to completion), DEADLINE_EXPIRED (its ``deadline_s`` elapsed —
    queued requests are dropped, decoding ones return their partial
    output), CANCELLED (``ContinuousEngine.cancel``, same partial-output
    semantics), SHED (bounded-queue backpressure rejected it unstarted),
    FAILED (its slot tripped a containment check and the retry budget was
    exhausted; tokens are the pre-fault prefix).
    """

    OK = "OK"
    DEADLINE_EXPIRED = "DEADLINE_EXPIRED"
    CANCELLED = "CANCELLED"
    SHED = "SHED"
    FAILED = "FAILED"


@dataclasses.dataclass
class Request:
    """One generation request entering the queue.

    ``arrival_time`` is seconds relative to the serve-loop start (0 =
    already waiting); the scheduler admits a request only once its
    arrival has passed, which is how benchmarks replay Poisson traffic.
    ``seed`` drives this request's private sampling chain — a sampled
    request reproduces ``ServeEngine(rng_seed=seed)`` serving it alone.
    ``deadline_s`` is an END-TO-END budget from arrival: once exceeded
    the request is evicted at the next chunk boundary with whatever it
    generated so far (DESIGN.md §11).  ``retries`` is the quarantine
    budget — how many times a containment trip may requeue this request
    instead of failing it.  ``priority`` (higher = more urgent) feeds
    priority admission and preemption (DESIGN.md §12): under a
    ``PreemptionPolicy`` a waiting high-priority request may suspend the
    lowest-priority decoding slot and take its place — the suspended
    request resumes later bit-identically from its slot snapshot.
    ``tier`` names a per-slot serving tier (weights x KV x prefill-act
    formats, DESIGN.md §15) on a ``TieredContinuousEngine``; None takes
    the engine's default tier, and non-tiered engines ignore it.
    """
    uid: int
    tokens: np.ndarray                  # (T,) int32 prompt
    max_new: int
    temperature: float = 0.0
    stop_token: Optional[int] = None
    arrival_time: float = 0.0
    seed: int = 0
    deadline_s: Optional[float] = None
    retries: int = 0
    priority: int = 0
    tier: Optional[str] = None


@dataclasses.dataclass
class RequestResult:
    """Terminal record for one request.  ``status`` says HOW it ended
    (``Status``); non-OK results still carry the partial ``tokens``
    generated before eviction (empty for SHED / queued expiry).
    ``degraded`` flags requests served under a shedding-policy degrade
    tier (capped ``max_new`` / forced greedy)."""

    uid: int
    tokens: np.ndarray                  # (n_generated,) int32
    n_generated: int
    queue_delay: float                  # arrival -> FIRST admission (s)
    ttft: float                         # arrival -> first token (s)
    decode_seconds: float               # OCCUPIED slot seconds (suspended
    #                                     wall time between preempt/resume
    #                                     is excluded, so decode_tok_s
    #                                     prices the slot, not the parking)
    status: str = Status.OK
    degraded: bool = False

    @property
    def ok(self) -> bool:
        return self.status == Status.OK

    @property
    def decode_tok_s(self) -> float:
        return self.n_generated / max(self.decode_seconds, 1e-9)


# ---------------------------------------------------------------------------
# admission policies: WHICH arrived request does a free slot take?
# ---------------------------------------------------------------------------

class AdmissionPolicy:
    """Picks the next request to admit from the waiting queue.

    ``select`` returns an INDEX into ``queue`` (only requests whose
    ``arrival_time`` has passed are eligible) or None to admit nothing.
    The scheduler owns slot bookkeeping; policies only rank the queue —
    which is all shortest-prompt-first / deadline scheduling needs.
    """

    name = "fifo"

    def select(self, queue: Sequence[Request], now: float) -> Optional[int]:
        raise NotImplementedError

    def expired(self, queue: Sequence[Request], now: float) -> List[int]:
        """Indices of arrived requests this policy considers UNSERVABLE.

        The scheduler evicts them with ``Status.DEADLINE_EXPIRED``
        instead of leaving them to rot at the back of the ranking (the
        pre-fix ``TtftDeadline`` bug: negative-slack requests were still
        admitted — burning a slot on a request that already missed its
        deadline).  Default: nothing expires.
        """
        return []


class FifoPolicy(AdmissionPolicy):
    """First-come-first-served (PR-3 behavior, the baseline)."""

    name = "fifo"

    def select(self, queue, now):
        for i, r in enumerate(queue):
            if r.arrival_time <= now:
                return i
        return None


class ShortestPromptFirst(AdmissionPolicy):
    """Admit the arrived request with the SHORTEST prompt (ties: FIFO).

    Long-prompt traffic: prefill cost scales with prompt length, so
    short requests stuck behind a long one pay someone else's admission
    stall.  Classic SJF — minimizes mean wait, at the cost of possible
    long-prompt starvation under sustained short-prompt pressure.
    """

    name = "spf"

    def select(self, queue, now):
        arrived = [(len(r.tokens), i) for i, r in enumerate(queue)
                   if r.arrival_time <= now]
        return min(arrived)[1] if arrived else None


class TtftDeadline(AdmissionPolicy):
    """Least-slack-first against a TTFT deadline.

    Every request implicitly owes a first token by ``arrival_time +
    deadline_s``; slack = deadline - now - estimated own prefill time
    (``prefill_s_per_tok * prompt_len``).  Admitting the minimum-slack
    request spends spare time where it exists instead of FIFO's
    arrival-order head-of-line blocking: an old long prompt and a fresh
    short one are ranked by who is closest to blowing their deadline.

    Requests whose slack has gone NEGATIVE are never selected — their
    deadline is already unmeetable, and admitting one spends a slot (and
    a prefill) producing a first token that is late by construction.
    They surface through ``expired`` so the scheduler can evict them
    with an explicit ``DEADLINE_EXPIRED`` status instead.
    """

    name = "ttft-deadline"

    def __init__(self, deadline_s: float = 0.25,
                 prefill_s_per_tok: float = 0.0):
        self.deadline_s = deadline_s
        self.prefill_s_per_tok = prefill_s_per_tok

    def _slack(self, r: Request, now: float) -> float:
        return (r.arrival_time + self.deadline_s - now
                - len(r.tokens) * self.prefill_s_per_tok)

    def select(self, queue, now):
        arrived = [(self._slack(r, now), i) for i, r in enumerate(queue)
                   if r.arrival_time <= now and self._slack(r, now) >= 0.0]
        return min(arrived)[1] if arrived else None

    def expired(self, queue, now):
        return [i for i, r in enumerate(queue)
                if r.arrival_time <= now and self._slack(r, now) < 0.0]


class PriorityAdmission(AdmissionPolicy):
    """Admit the arrived request with the HIGHEST ``Request.priority``
    (ties: FIFO).  The admission half of "interactive overtakes batch" —
    pair it with ``PriorityPreemption`` so a high-priority request also
    gets a slot when none is free, not just first pick of one.
    """

    name = "priority"

    def select(self, queue, now):
        arrived = [(-r.priority, r.arrival_time, i)
                   for i, r in enumerate(queue) if r.arrival_time <= now]
        return min(arrived)[2] if arrived else None


# ---------------------------------------------------------------------------
# load shedding: WHAT gives way when the arrived queue exceeds max_queue?
# ---------------------------------------------------------------------------

class SheddingPolicy:
    """Backpressure policy for a bounded admission queue (DESIGN.md §11).

    When the ARRIVED portion of the queue (future arrivals don't count —
    they aren't load yet) exceeds ``SlotScheduler.max_queue``,
    ``over_budget`` decides what gives: it returns ``(shed, degrade)``
    where ``shed`` is queue indices to evict with ``Status.SHED`` and
    ``degrade`` is ``(index, max_new_cap, force_greedy)`` triples to keep
    serving under a cheaper tier.  ``arrived`` is pre-sorted oldest
    first, so slicing its ends is arrival-order shedding.
    """

    name = "reject-new"

    def over_budget(self, sched: "SlotScheduler", arrived: List[int],
                    n_over: int, now: float
                    ) -> Tuple[List[int], List[Tuple[int, int, bool]]]:
        raise NotImplementedError


class RejectNew(SheddingPolicy):
    """Shed the NEWEST over-budget arrivals (default).  The queue keeps
    its oldest waiters — nothing already enqueued loses its place, and a
    fresh burst bounces off a full queue the way a 503 would."""

    name = "reject-new"

    def over_budget(self, sched, arrived, n_over, now):
        return arrived[-n_over:], []


class DropOldest(SheddingPolicy):
    """Shed the OLDEST arrivals.  Under sustained overload the oldest
    waiters are the ones most likely to have blown their deadline anyway;
    dropping them keeps observed queue delay bounded for the survivors
    (tail-latency-biased shedding)."""

    name = "drop-oldest"

    def over_budget(self, sched, arrived, n_over, now):
        return arrived[:n_over], []


class DegradeOverBudget(SheddingPolicy):
    """Serve over-budget arrivals under a DEGRADED tier instead of
    shedding them: their ``max_new`` is capped at ``max_new_cap`` (and
    sampling forced greedy when ``force_greedy``) at admission, trading
    answer length for admission under load.  ``hard_cap`` (optional,
    counted in arrived requests) bounds the degraded backlog itself —
    beyond it the newest arrivals are shed outright, so overload stays
    bounded even when traffic outruns the degraded tier.

    Results served under this tier carry ``degraded=True``.  A per-slot
    nxfp4-KV degrade tier is the ROADMAP follow-up; capped ``max_new``
    is the degrade axis this policy implements.

    ``pool_watermark`` (paged engines, DESIGN.md §14) adds a MEMORY
    trigger to the queue-length one: when the engine's page-pool
    occupancy reaches the watermark (a fraction in (0, 1]), every
    arrived waiter is treated as over budget and admitted degraded —
    shorter answers free pages sooner, which is the backpressure a
    paged cache actually wants (queue length says nothing about HBM).
    Ignored by engines without a page pool.
    """

    name = "degrade"

    def __init__(self, max_new_cap: int = 8, force_greedy: bool = True,
                 hard_cap: Optional[int] = None,
                 pool_watermark: Optional[float] = None):
        self.max_new_cap = max_new_cap
        self.force_greedy = force_greedy
        self.hard_cap = hard_cap
        self.pool_watermark = pool_watermark

    def over_budget(self, sched, arrived, n_over, now):
        shed: List[int] = []
        if self.hard_cap is not None and len(arrived) > self.hard_cap:
            shed = arrived[self.hard_cap:]
            arrived = arrived[:self.hard_cap]
            n_over = max(n_over - len(shed), 0)
        degrade = [(i, self.max_new_cap, self.force_greedy)
                   for i in (arrived[-n_over:] if n_over else [])]
        return shed, degrade


# ---------------------------------------------------------------------------
# preemption: WHICH decoding slot yields when a more urgent request waits?
# ---------------------------------------------------------------------------

class PreemptionPolicy:
    """Decides which DECODING slots to suspend for waiting requests.

    ``victims`` returns slot ids to suspend this chunk boundary; each
    victim is snapshotted (``SlotSnapshot`` — packed KV rows + sampling
    state) and requeued as RESUMABLE, so preemption costs a pause, never
    lost work: the resumed stream is bit-identical to an uninterrupted
    run.  The default policy never preempts (PR-6 behavior).
    """

    name = "none"

    def victims(self, sched: "SlotScheduler", now: float) -> List[int]:
        return []


class PriorityPreemption(PreemptionPolicy):
    """Suspend the lowest-priority decoding slot for a strictly
    higher-priority arrived waiter ("interactive overtakes batch").

    Waiters claim free slots first (preemption is a last resort), then
    each remaining waiter — most urgent first — may displace the
    lowest-priority decoding slot if its own priority is STRICTLY
    higher.  Strict comparison is the anti-thrash rule: the suspended
    request re-enters the queue at its old priority and can never
    preempt its preemptor back.  Mid-prefill slots are not preempted
    (their lane restarts from chunk 0 — nothing resumable to save yet).
    """

    name = "priority"

    def victims(self, sched, now):
        waiting = sorted((r for r in sched.queue if r.arrival_time <= now),
                         key=lambda r: (-r.priority, r.arrival_time))
        if not waiting:
            return []
        pool = sorted(((r.priority, s) for s, r in sched.active.items()
                       if sched.phase.get(s) == DECODING))
        budget = len(sched.free)
        out: List[int] = []
        for w in waiting:
            if budget > 0:
                budget -= 1
                continue
            if pool and pool[0][0] < w.priority:
                out.append(pool.pop(0)[1])
            else:
                break
        return out


# ---------------------------------------------------------------------------
# slot bookkeeping
# ---------------------------------------------------------------------------

PREFILLING = "PREFILLING"
DECODING = "DECODING"


class SlotScheduler:
    """Queue + free-slot bookkeeping behind a pluggable admission policy.

    ``next_admission`` pairs a free slot with whichever arrived request
    the policy ranks first.  Slots carry a phase tag — PREFILLING while
    the chunked lane is still feeding their prompt, DECODING once their
    first token exists — so observers (and the engine's decode loop) can
    tell a mid-prefill slot from a live one.

    With ``max_queue`` set, the ARRIVED queue is bounded: each
    ``enforce_bounds`` call hands the overflow to the ``shedding``
    policy (default ``RejectNew``), which sheds or degrades it —
    backpressure is explicit and observable, never an unbounded backlog.
    ``expire_queued`` evicts queued requests whose per-request deadline
    (or the admission policy's own deadline model) has already passed.
    """

    def __init__(self, n_slots: int, policy: Optional[AdmissionPolicy] = None,
                 max_queue: Optional[int] = None,
                 shedding: Optional[SheddingPolicy] = None,
                 journal: Optional[Journal] = None):
        self.n_slots = n_slots
        self.policy = policy or FifoPolicy()
        self.max_queue = max_queue
        self.shedding = shedding or RejectNew()
        self.journal = journal or Journal()
        self.queue: List[Request] = []
        self.free: List[int] = list(range(n_slots))
        self.active: Dict[int, Request] = {}
        self.phase: Dict[int, str] = {}
        # uid -> (max_new_cap, force_greedy): degrade-tier markers applied
        # at admission time; popped into RequestResult.degraded at finish
        self.degraded: Dict[int, Tuple[Optional[int], bool]] = {}
        # uid -> SlotSnapshot: queued requests that are RESUMABLE — they
        # re-enter through snapshot restore, not a fresh prefill. Every
        # path that removes a queued request (admission, shed, expire,
        # cancel) must consume/pop its snapshot alongside.
        self.resumable: Dict[int, SlotSnapshot] = {}
        # shards taken out of rotation (sharded engine only: admission
        # never routes to a drained shard; empty set for unsharded)
        self.drained: set = set()
        # paged-engine hooks (DESIGN.md §14), both optional:
        # admission_gate(req, shard, resumable) -> bool vetoes a policy
        # pick whose KV pages don't fit right now (a free SLOT is no
        # longer sufficient); pool_monitor() -> occupancy in [0, 1]
        # feeds shedding policies with a pool_watermark.
        self.admission_gate = None
        self.pool_monitor = None

    def _gate(self, req: Request, shard: Optional[int],
              resumable: bool) -> bool:
        if self.admission_gate is None:
            return True
        return bool(self.admission_gate(req, shard, resumable))

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _take(self, idx: int, slot: int) -> Tuple[int, Request]:
        """Move queue[idx] into ``slot``, applying any degrade marker."""
        self.free.remove(slot)
        req = self.queue.pop(idx)
        mark = self.degraded.get(req.uid)
        if mark is not None:
            cap, greedy = mark
            if cap is not None:
                req = dataclasses.replace(req,
                                          max_new=min(req.max_new, cap))
            if greedy:
                req = dataclasses.replace(req, temperature=0.0)
        self.active[slot] = req
        self.phase[slot] = DECODING
        return slot, req

    def next_admission(self, now: float) -> Optional[Tuple[int, Request]]:
        """Pop (slot, request) if a slot is free, the policy picks one,
        and the admission gate (pages, for paged engines) accepts it."""
        if not self.free or not self.queue:
            return None
        idx = self.policy.select(self.queue, now)
        if idx is None:
            return None
        req = self.queue[idx]
        if not self._gate(req, None, req.uid in self.resumable):
            return None
        return self._take(idx, self.free[0])

    def next_resume(self, now: float) -> Optional[Tuple[int, Request]]:
        """Pop (slot, request) ONLY if the policy's pick is resumable.

        Resume admission bypasses the prefill lane (a snapshot restore
        is one scatter, not a prompt), so the engine drains these before
        lane work each iteration — but strictly in policy order: a
        resumable request never jumps a non-resumable one the policy
        ranks higher.
        """
        if not self.free or not self.queue or not self.resumable:
            return None
        idx = self.policy.select(self.queue, now)
        if idx is None or self.queue[idx].uid not in self.resumable:
            return None
        if not self._gate(self.queue[idx], None, True):
            return None
        return self._take(idx, self.free[0])

    def pop_queued(self, uid: int) -> Optional[Request]:
        """Remove and return the queued request with ``uid`` (else None)."""
        for i, r in enumerate(self.queue):
            if r.uid == uid:
                return self.queue.pop(i)
        return None

    def expire_queued(self, now: float) -> List[Request]:
        """Pop arrived queued requests whose deadline already passed."""
        idx = {i for i, r in enumerate(self.queue)
               if r.deadline_s is not None and r.arrival_time <= now
               and now - r.arrival_time > r.deadline_s}
        idx.update(self.policy.expired(self.queue, now))
        return [self.queue.pop(i) for i in sorted(idx, reverse=True)]

    def enforce_bounds(self, now: float) -> List[Request]:
        """Apply the shedding policy; returns the requests shed (if any).

        The bound applies to the BACKLOG: arrived waiters beyond what
        currently-free slots can absorb immediately (the sweep runs
        before admission each iteration, so without the ``free`` credit
        an initial burst would shed requests an idle slot was about to
        serve).  Degrade markers are recorded here (and logged once per
        uid); they take effect when ``_take`` admits the marked request.

        A shedding policy with a ``pool_watermark`` adds a MEMORY
        trigger: when ``pool_monitor`` (set by paged engines) reports
        occupancy at or past the watermark, every arrived waiter counts
        as over budget — with ``DegradeOverBudget`` that admits the
        backlog under the cheap tier until pages free up.
        """
        wm = getattr(self.shedding, "pool_watermark", None)
        pressure = (wm is not None and self.pool_monitor is not None
                    and self.pool_monitor() >= wm)
        if self.max_queue is None and not pressure:
            return []
        arrived = sorted((i for i, r in enumerate(self.queue)
                          if r.arrival_time <= now),
                         key=lambda i: (self.queue[i].arrival_time, i))
        n_over = (len(arrived) - self.max_queue - len(self.free)
                  if self.max_queue is not None else 0)
        if pressure:
            n_over = max(n_over, len(arrived))
        if n_over <= 0:
            return []
        shed_idx, degrades = self.shedding.over_budget(self, arrived,
                                                       n_over, now)
        for i, cap, greedy in degrades:
            uid = self.queue[i].uid
            if uid not in self.degraded:
                self.degraded[uid] = (cap, greedy)
                self.journal.emit(logger, "degrade", uid=uid,
                                  max_new_cap=cap, greedy=greedy,
                                  policy=self.shedding.name)
        shed = [self.queue.pop(i) for i in sorted(set(shed_idx),
                                                  reverse=True)]
        for r in shed:
            self.degraded.pop(r.uid, None)
        return shed

    def mark_prefilling(self, slot: int) -> None:
        self.phase[slot] = PREFILLING

    def mark_decoding(self, slot: int) -> None:
        self.phase[slot] = DECODING

    def release(self, slot: int) -> Request:
        req = self.active.pop(slot)
        self.phase.pop(slot, None)
        self.free.append(slot)
        return req

    def suspend_to_queue(self, slot: int, snap: SlotSnapshot) -> Request:
        """Release ``slot`` and requeue its request as RESUMABLE."""
        req = self.release(slot)
        self.resumable[req.uid] = snap
        self.queue.append(req)
        return req

    def reassign(self, old: int, new: int) -> Request:
        """Move a live request between slots (live migration bookkeeping).

        The phase tag travels; ``old`` returns to the free list (its
        shard may be drained — routing, not the free list, keeps drained
        slots out of admission).  Device/host state moves are the
        engine's job.
        """
        req = self.active.pop(old)
        ph = self.phase.pop(old)
        self.free.remove(new)
        self.free.append(old)
        self.active[new] = req
        self.phase[new] = ph
        return req

    def next_arrival(self) -> Optional[float]:
        return min((r.arrival_time for r in self.queue), default=None)

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.active)


class ShardedSlotScheduler(SlotScheduler):
    """Slot bookkeeping over a sharded slot axis: global slot ids map to
    ``(shard, local_slot)`` and admission is ROUTED to the owning shard.

    The slot-sharded engine (``serving.sharded``) partitions the B-slot
    cache as S contiguous blocks of ``slots_per_shard`` slots, one block
    per 'data'-mesh shard — so slot ``g`` lives on shard ``g // L`` at
    local index ``g % L``.  ``next_admission`` still lets the
    ``AdmissionPolicy`` rank the queue (WHICH request), but the SLOT now
    comes from a specific shard: the caller's shard when given (each
    shard runs its own prefill lane), else the least-loaded shard with a
    free slot (ties break to the lowest shard id) — spreading decode
    occupancy evenly instead of FIFO free-list order piling early
    admissions onto shard 0.

    Pure host bookkeeping — no mesh or devices needed, which is what
    keeps the routing logic unit-testable outside a subprocess.
    """

    def __init__(self, n_shards: int, slots_per_shard: int,
                 policy: Optional[AdmissionPolicy] = None, **kw):
        super().__init__(n_shards * slots_per_shard, policy, **kw)
        self.n_shards = n_shards
        self.slots_per_shard = slots_per_shard

    def shard_of(self, slot: int) -> int:
        return slot // self.slots_per_shard

    def local_slot(self, slot: int) -> int:
        return slot % self.slots_per_shard

    def load(self, shard: int) -> int:
        """Occupied slots on ``shard`` (prefilling and decoding alike)."""
        return sum(1 for s in self.active if self.shard_of(s) == shard)

    def free_on(self, shard: int) -> List[int]:
        return [s for s in self.free if self.shard_of(s) == shard]

    def healthy_free(self) -> List[int]:
        """Free slots on shards still in rotation (drain-aware)."""
        return [s for s in self.free if self.shard_of(s) not in self.drained]

    def next_admission(self, now: float, shard: Optional[int] = None
                       ) -> Optional[Tuple[int, Request]]:
        """Pop (global_slot, request), routed to ``shard`` (or least-loaded).

        Drained shards are out of rotation: routed-to-drained returns
        None (the caller's lane is being retired) and least-loaded picks
        only among healthy shards.
        """
        if not self.queue:
            return None
        if shard is not None and shard in self.drained:
            return None
        idx = self.policy.select(self.queue, now)
        if idx is None:
            return None
        req = self.queue[idx]
        resum = req.uid in self.resumable
        if shard is not None:
            free = self.free_on(shard)
            if not free or not self._gate(req, shard, resum):
                return None
            return self._take(idx, free[0])
        with_free = {self.shard_of(s) for s in self.free} - self.drained
        # least-loaded first; a shard whose page pool can't fit the pick
        # is skipped — another shard's pool may still have room
        for sh in sorted(with_free, key=lambda s: (self.load(s), s)):
            if self._gate(req, sh, resum):
                return self._take(idx, self.free_on(sh)[0])
        return None

    def next_resume(self, now: float) -> Optional[Tuple[int, Request]]:
        """Resume routing: policy's resumable pick -> least-loaded healthy
        shard (a snapshot restores into ANY free slot — the restore
        scatter is owner-masked exactly like admission)."""
        if not self.queue or not self.resumable:
            return None
        healthy = {self.shard_of(s) for s in self.free} - self.drained
        if not healthy:
            return None
        idx = self.policy.select(self.queue, now)
        if idx is None or self.queue[idx].uid not in self.resumable:
            return None
        req = self.queue[idx]
        for shard in sorted(healthy, key=lambda s: (self.load(s), s)):
            if self._gate(req, shard, True):
                return self._take(idx, self.free_on(shard)[0])
        return None


class ContinuousEngine:
    """Continuous-batching serving over one persistent B-slot device cache.

    The decode hot loop is the same on-device chunked ``lax.scan`` as
    ``ServeEngine`` — but between chunks the scheduler admits/evicts, so
    slots run RAGGED: per-slot positions, per-slot temperature/stop/
    max_new vectors, per-slot PRNG keys. Finished slots keep decoding
    until evicted (their emissions are masked on device, exactly like the
    fixed engine's done rows), so throughput is bounded by slot
    occupancy, not by the slowest request in an arbitrary batch.

    ``prefill_mode="whole"`` admits with one monolithic batch-1 prefill
    (one program per distinct prompt length — bucket lengths, or pay a
    compile per novel length mid-traffic).  ``prefill_mode="chunked"``
    splits prompts into fixed-shape (1, ``p_chunk``) lane chunks
    interleaved with decode chunks: admission stalls are bounded by
    ``p_chunk`` and ONE program serves every prompt length.  Both modes
    emit bit-identical greedy tokens to solo host-loop serving (the
    "whole" path doubles as the equality oracle for "chunked") — except
    ``family="moe"`` under chunked admission, whose prefill routing is
    chunk-local (warned at init; use "whole" when the oracle matters).
    """

    def __init__(self, cfg: ModelConfig, params, policy: QuantPolicy,
                 n_slots: int = 4, max_len: int = 2048, chunk: int = 16,
                 warn_compile: bool = True, prefill_mode: str = "whole",
                 p_chunk=32,
                 admission_policy: Optional[AdmissionPolicy] = None,
                 p_chunk_candidates: Sequence[int] = (16, 32, 64, 128),
                 kv_integrity: bool = False,
                 max_queue: Optional[int] = None,
                 shedding: Optional[SheddingPolicy] = None,
                 preemption: Optional[PreemptionPolicy] = None,
                 speculative: Optional[SpeculativeConfig] = None):
        self.cfg = cfg
        self.policy = policy
        self.n_slots = n_slots
        self.max_len = max_len
        self.chunk = chunk
        raw_params = params
        params = (direct_cast_tree(params, policy,
                                   quantize_fn=quantize_qtensor)
                  if policy.weight_fmt else params)
        kv = policy.kv_fmt
        self._kv = kv
        self.speculative = speculative
        draft = None
        if speculative is not None:
            # MoE is outside the speculative contract: expert capacity is
            # resolved per dispatch, so a (B, k+1)-token verify drops
            # different tokens than k+1 single-token dispatches — no
            # bitwise-stable batched scoring (same reason MoE prefill is
            # outside the chunked-vs-whole oracle)
            if cfg.family not in ("dense", "ssm", "hybrid"):
                raise ValueError(f"speculative decode does not serve "
                                 f"family={cfg.family!r}")
            if speculative.draft == "recycled":
                if not policy.weight_fmt:
                    raise ValueError(
                        "draft='recycled' dequantizes the engine's cast "
                        "weights — it needs a quantized product "
                        "(policy.weight_fmt)")
                draft = dense_like(params)
            else:
                draft = direct_cast_tree(
                    raw_params,
                    dataclasses.replace(policy,
                                        weight_fmt=speculative.draft),
                    quantize_fn=quantize_qtensor)
            self._adaptive = AdaptiveK(speculative, n_slots)
            self.spec_accepted = 0      # candidates accepted (all chunks)
            self.spec_offered = 0       # candidates offered (all chunks)
            self._spec_acc_slot = np.zeros((n_slots,), np.int64)
            self._spec_off_slot = np.zeros((n_slots,), np.int64)
        self.admission_policy = admission_policy
        assert prefill_mode in ("whole", "chunked"), prefill_mode
        self.prefill_mode = prefill_mode
        self.kv_integrity = kv_integrity
        self.max_queue = max_queue
        self.shedding = shedding
        self.preemption = preemption
        self.journal = Journal()
        self._loop = Loop(logger)           # serve-loop spans and records
        self._cancel_uids: set = set()
        self._suspend_uids: set = set()
        self._fault_plan = None
        self._chunk_idx = 0
        # attention-KV prefix canary (vacuous for pure-SSM families: no
        # KV rows to pin — their canary is the at-rest SSM-state fold)
        self._has_attn_kv = cfg.family != "ssm"
        self._has_ssm = cfg.family in ("ssm", "hybrid")
        self._kv_armed = np.zeros((n_slots,), bool)
        self._kv_sum = np.zeros((n_slots,), np.uint32)
        self._kv_upto = np.zeros((n_slots,), np.int32)
        self._kv_horizon = chunk
        self._ssm_armed = np.zeros((n_slots,), bool)
        self._ssm_sum = np.zeros((n_slots,), np.uint32)
        self._ssm_bad = np.zeros((n_slots,), bool)
        # snapshots awaiting resume in the NEXT serve (checkpoint restore
        # seeds these; serve() hands them to its scheduler)
        self._pending_resume: Dict[int, SlotSnapshot] = {}
        # live-serve introspection handles (checkpoint()/drain sweeps run
        # from progress_cb and need the current sched/state/clock)
        self._sched = None
        self._state: Optional[Dict[int, Any]] = None
        self._results: Optional[List[RequestResult]] = None
        self._clock = None
        # compile-cache keys carry the mesh identity (None = unsharded):
        # a sharded and an unsharded engine on identical (cfg, kv, ...)
        # must never hand each other executables (ISSUE-5)
        self._mesh_key = self._mesh_fingerprint()
        self.params = self._place_params(params)
        self.draft_params = (self._place_params(draft)
                             if draft is not None else None)
        self._build_programs()
        self._pf: Optional[Any] = None      # in-flight lane cursor(s)
        self.cache = self._init_slot_cache()
        self._seen_prompt_lens: set = set()
        self._warn_compile = warn_compile
        # host-visible slot state (tiny; re-uploaded each chunk call)
        self._tok = np.zeros((n_slots,), np.int32)
        self._keys = np.zeros((n_slots, 2), np.uint32)
        self._done = np.ones((n_slots,), bool)      # all parked
        self._live = np.zeros((n_slots,), bool)     # admitted AND decoding
        self._n_gen = np.zeros((n_slots,), np.int32)
        self._max_new = np.zeros((n_slots,), np.int32)
        self._temp = np.zeros((n_slots,), np.float32)
        self._stop = np.full((n_slots,), -1, np.int32)
        if prefill_mode == "chunked":
            if cfg.family not in ("dense", "moe", "ssm", "hybrid"):
                raise ValueError(f"chunked prefill does not serve "
                                 f"family={cfg.family!r}")
            if p_chunk == "auto":
                p_chunk = self._autotune_p_chunk(p_chunk_candidates)
            if cfg.sliding_window and p_chunk > cfg.sliding_window:
                # one lane chunk must hit distinct ring rows
                raise ValueError(f"p_chunk ({p_chunk}) must be <= "
                                 f"sliding_window ({cfg.sliding_window})")
            if cfg.family in ("ssm", "hybrid") and p_chunk % cfg.ssm_chunk:
                # lane scan chunking must align with the whole-prompt
                # oracle's associative-scan grouping for bit-equality
                raise ValueError(f"p_chunk ({p_chunk}) must be a multiple "
                                 f"of ssm_chunk ({cfg.ssm_chunk})")
            if cfg.family == "moe":
                logger.warning(
                    "family='moe' + prefill_mode='chunked': expert "
                    "capacity is chunk-local, so outputs are NOT "
                    "bit-identical to whole-prompt admission (use "
                    "prefill_mode='whole' when the oracle matters)")
            self.p_chunk = p_chunk
            # natural-order scratch rows: ABSOLUTE prompt offsets index
            # the lane, so prompts longer than this must fail loudly at
            # submit (SWA rings wrap the LIVE cache, but a clamped lane
            # write would silently corrupt rows inside the window)
            self._lane_rows = -(-max_len // p_chunk) * p_chunk
            # ring-aware lane: SWA prompts LONGER than the scratch wrap
            # it modulo _lane_rows instead of failing at submit — sound
            # whenever the scratch still covers a full window plus the
            # incoming chunk (every attended key then sits un-clobbered
            # in the ring; see models.attention.self_attention_resume).
            # The sharded engine keeps the strict bound (its fused lane
            # rides per-shard cursors this flag doesn't thread through).
            self._lane_ring = bool(cfg.sliding_window) and \
                self._lane_rows >= cfg.sliding_window + p_chunk
            self._build_lane()

    # -- construction hooks (the sharded engine overrides these) ------------

    def _mesh_fingerprint(self):
        """Hashable mesh identity for compile-cache keys (unsharded: None)."""
        return None

    def _place_params(self, params):
        """Device placement for the (cast) weights (unsharded: as-is)."""
        return params

    def _init_slot_cache(self):
        return init_cache(self.cfg, self.n_slots, self.max_len, self._kv)

    def _build_programs(self) -> None:
        cfg, kv, max_len, mk = self.cfg, self._kv, self.max_len, self._mesh_key
        self._prefill = cached_program(
            ("admit", cfg, kv, max_len, mk),
            lambda: named_jit("admit", functools.partial(
                self._admit_fn, cfg=cfg, kv_fmt=kv, max_len=max_len)))
        self._reset = self._keep_cache(cached_program(
            ("reset", cfg, mk),
            lambda: named_jit("reset_slot",
                              functools.partial(reset_slot, cfg),
                              donate_argnums=0)), 0, None)
        self._chunk_jit = cached_program(
            ("cont_chunk", cfg, kv, mk),
            lambda: named_jit(
                "decode_chunk",
                functools.partial(self._chunk_fn, cfg=cfg, kv_fmt=kv),
                static_argnames=("n_steps", "greedy")))
        if self.speculative is not None:
            self._spec_jit = cached_program(
                ("spec_chunk", cfg, kv, mk),
                lambda: named_jit(
                    "spec_chunk",
                    functools.partial(self._spec_chunk_fn, cfg=cfg,
                                      kv_fmt=kv),
                    static_argnames=("k", "n_rounds", "greedy")))
        # snapshot extract/restore: one fixed-shape program each (slot is
        # a traced index), shared by suspend, migration and checkpoint
        self._snap = cached_program(
            ("snap", cfg, kv, mk), lambda: named_jit("snap", read_cache_slot))
        self._restore_prog = cached_program(
            ("restore", cfg, kv, mk),
            lambda: named_jit("restore", write_cache_slot))
        if self.kv_integrity:
            if self._has_attn_kv:
                self._kv_check = cached_program(
                    ("kv_check", cfg, kv, mk),
                    lambda: named_jit("kv_check", functools.partial(
                        kv_slot_checksum, cfg)))
            if self._has_ssm:
                self._ssm_check = cached_program(
                    ("ssm_check", cfg, mk),
                    lambda: named_jit("ssm_check", functools.partial(
                        ssm_state_checksum, cfg)))

    def _build_lane(self) -> None:
        cfg, kv, mk = self.cfg, self._kv, self._mesh_key
        self.lane = init_lane(cfg, self.max_len, self.p_chunk)
        self._lane_fn = self._keep_cache(cached_program(
            ("lane", cfg, kv, self.p_chunk, mk),
            lambda: self._lane_program(cfg, kv)), 2, 1)
        self._finish = self._keep_cache(cached_program(
            ("finish", cfg, mk),
            lambda: named_jit("lane_finish", self._finish_prefill_fn,
                              donate_argnums=3)), 3, 2)

    def _lane_program(self, cfg, kv):
        """The unsharded lane program; its cache argument is donated."""
        return named_jit("lane_chunk", functools.partial(
            self._lane_chunk_fn, cfg=cfg, kv_fmt=kv),
            static_argnames=("with_head", "wrapped"), donate_argnums=2)

    def _keep_cache(self, prog, arg: int, out):
        """``prog``, whose argument ``arg`` is the donated serving cache,
        called so that the engine is always left on a live cache.

        Donation lets XLA update the cache in place (DESIGN.md §7), and
        deletes the buffers passed.  The engine's own dispatches rebind
        ``self.cache`` to the returned cache (result ``out``, or the
        whole result for None); so does this wrapper whenever the cache
        passed is ``self.cache``, for callers that drop the result — a
        probe or a timing loop run on the engine's own state.
        """
        def call(*args, **kwargs):
            res = prog(*args, **kwargs)
            if args[arg] is self.cache:
                self.cache = res if out is None else res[out]
            return res
        return call

    # -- p_chunk autotuning (ROADMAP follow-up) -----------------------------

    def _time_best(self, fn, n: int = 3) -> float:
        jax.block_until_ready(fn())             # compile + warm
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        return min(times)       # dispatch noise only: min is honest

    def _autotune_probes(self):
        """(decode chunk fn, params, probe cache, probe slot count).

        Both sides of the stall-budget comparison must run in ONE
        execution regime, so the base engine probes its own programs
        against its own cache.  The sharded engine overrides this to
        probe the PER-SHARD bodies on a single device (its real decode
        program is shard_map'd but the lane probe is not — timing one
        side through GSPMD resharding would skew the ratio).
        """
        return self._chunk_jit, self.params, self.cache, self.n_slots

    def _autotune_p_chunk(self, candidates: Sequence[int],
                          stall_factor: float = 2.0) -> int:
        """Pick the lane chunk from a short warmup sweep (p_chunk="auto").

        The tradeoff is the one ``serving_bench``'s chunk-size rows
        measure: a BIGGER lane chunk amortizes dispatch overhead (fewer
        lane dispatches per prompt -> faster prefill, better aggregate
        tok/s) but stalls every decoding slot LONGER per chunk (worse
        decode tail latency) — and the crossover is a backend property,
        not a constant (the CPU optimum is a dispatch-overhead artifact;
        ROADMAP flags re-measuring on TPU).  So: time one decode chunk
        (the stall unit the lane interleaves with) and one lane dispatch
        per candidate, then take the highest-throughput candidate whose
        lane chunk costs at most ``stall_factor`` decode chunks; if none
        qualifies, the smallest candidate (tightest stall bound) wins.
        Candidates violating the lane's static constraints (SWA ring
        width, ssm_chunk alignment, max_len) are dropped up front.
        Results stay on ``self.p_chunk_sweep`` for benches to report.
        """
        cfg, kv = self.cfg, self._kv
        cands = sorted({int(p) for p in candidates if p <= self.max_len
                        and (not cfg.sliding_window
                             or p <= cfg.sliding_window)
                        and (cfg.family not in ("ssm", "hybrid")
                             or p % cfg.ssm_chunk == 0)})
        if not cands:
            raise ValueError(f"p_chunk='auto': no candidate in "
                             f"{tuple(candidates)} satisfies the lane "
                             f"constraints of {cfg.name}")
        chunk_fn, params, cache, b = self._autotune_probes()
        own = cache is self.cache
        zi = jnp.zeros((b,), jnp.int32)
        decode_s = self._time_best(lambda: chunk_fn(
            params, zi, cache, jnp.zeros((b, 2), jnp.uint32),
            jnp.ones((b,), bool), zi, zi, jnp.zeros((b,), jnp.float32),
            jnp.full((b,), -1, jnp.int32), jnp.zeros((b,), bool),
            jnp.zeros((b,), bool),
            n_steps=self.chunk, greedy=True))
        self.p_chunk_sweep: Dict[int, float] = {}
        for p in cands:
            lane = init_lane(cfg, self.max_len, p)
            # keyed like the unsharded lane program, so the winner's
            # compile is reused by _build_lane (and by every later
            # engine on the same config); the sharded engine's per-shard
            # lane body is this same batch-1 computation, so the choice
            # transfers even though its fused program is keyed apart
            fn = cached_program(
                ("lane", cfg, kv, p, None),
                lambda: self._lane_program(cfg, kv))
            toks = np.zeros((1, p), np.int32)

            def lane_call():
                # the lane donates the cache: each call runs on the last
                # call's result
                nonlocal cache
                out = fn(params, toks, cache, lane, jnp.int32(0),
                         jnp.int32(0), jnp.int32(p), with_head=False)
                cache = out[1]
                return out

            self.p_chunk_sweep[p] = self._time_best(lane_call)
        if own:
            self.cache = cache
        budget = stall_factor * decode_s
        ok = [p for p in cands if self.p_chunk_sweep[p] <= budget]
        best = (max(ok, key=lambda p: p / self.p_chunk_sweep[p]) if ok
                else cands[0])
        logger.info(
            "p_chunk autotune: decode chunk %.2fms, sweep {%s} -> %d",
            decode_s * 1e3,
            ", ".join(f"{p}: {s * 1e3:.2f}ms"
                      for p, s in self.p_chunk_sweep.items()), best)
        return best

    # -- jitted bodies ------------------------------------------------------

    @staticmethod
    def _first_token(logits, key, temperature):
        """Sample a request's FIRST token off its prefill logits (1, V).

        Argmax, or categorical on the request's OWN key chain — the same
        ``split`` sequence the solo engine walks.  Shared by monolithic
        admission and the lane's final chunk, so chunked-vs-whole
        first-token equality holds by construction, not by copy-paste.
        """
        greedy = jnp.argmax(logits, axis=-1)
        key2, sub = jax.random.split(key)
        safe = jnp.where(temperature > 0, temperature, 1.0)
        sampled = jax.random.categorical(sub, logits / safe, axis=-1)
        tok0 = jnp.where(temperature > 0, sampled[0], greedy[0])
        key_out = jnp.where(temperature > 0, key2, key)
        return tok0.astype(jnp.int32), key_out

    @staticmethod
    def _admit_fn(params, batch, cache, slot, key, temperature,
                  *, cfg, kv_fmt, max_len):
        """Prefill one request into ``slot`` and sample its first token.

        One dispatch per admission: batch-1 prefill, slot scatter, and
        the first-token sample (``_first_token``).
        """
        logits, new_cache = prefill_into_slot(cfg, params, batch, cache,
                                              slot, max_len, kv_fmt)
        tok0, key_out = ContinuousEngine._first_token(logits, key,
                                                      temperature)
        return tok0, key_out, new_cache

    @staticmethod
    def _lane_chunk_fn(params, tokens, cache, lane, slot, offset, n_valid,
                       *, cfg, kv_fmt, with_head: bool,
                       wrapped: bool = False):
        """One fixed-shape lane advance (see ``models.prefill_chunk``).

        ``with_head`` (static) is True only for a prompt's FINAL chunk —
        intermediate chunks skip the vocab-head matmul their discarded
        return would have paid for (two compiled programs total, both
        prompt-length-independent).  ``wrapped`` (static) selects the
        ring-lane graph once an SWA prompt's offset has lapped the
        scratch (``offset >= lane rows``) — unwrapped chunks compile the
        exact pre-ring program.
        """
        return prefill_chunk(cfg, params, tokens, cache, slot, offset,
                             n_valid, lane, kv_fmt, with_head=with_head,
                             wrapped=wrapped)

    @staticmethod
    def _finish_prefill_fn(logits, key, temperature, cache, slot, t,
                           apply=None):
        """Final-chunk tail: sample the first token and un-park the slot.

        The lane's final logits ARE the whole-prompt prefill logits, and
        the sample is the shared ``_first_token``, so the first token
        (greedy or the seed chain's categorical) matches the monolithic
        path exactly.  ``pos[slot] <- t`` arms the slot for decode;
        ``apply`` (traced bool) owner-masks the arm for the sharded
        engine, which wraps this same tail per shard.
        """
        tok0, key_out = ContinuousEngine._first_token(logits, key,
                                                      temperature)
        pos = gated_update_slice(cache["pos"],
                                 jnp.asarray(t, jnp.int32).reshape(1),
                                 (slot,), apply)
        return tok0, key_out, dict(cache, pos=pos)

    @staticmethod
    def _chunk_fn(params, tok, cache, keys, done, n_gen, max_new,
                  temperature, stop, live, poison, *, cfg, kv_fmt,
                  n_steps: int, greedy: bool):
        """One dispatch = ``n_steps`` ragged decode steps, fully on device.

        Same emission semantics as ``ServeEngine._chunk_fn`` plus a
        per-slot ``max_new`` budget: step i of slot b is live iff the slot
        was not done at entry, no stop token landed strictly earlier in
        the chunk, and its budget ``n_gen + i < max_new`` still holds —
        so a slot emits exactly the tokens the solo host loop would.
        PRNG keys are PER SLOT ((B, 2) uint32, vmapped split per step):
        each slot's chain is its request's seed chain, independent of its
        neighbors — admission order cannot perturb sampling. ``greedy``
        (static: no sampled slot is live this chunk) skips the per-step
        vmapped split+categorical — on CPU the per-slot threefry chain
        costs ~2x decode itself, and greedy slots never read their keys.
        ``live`` (B,) bool freezes not-live slots' cache state (position,
        K/V writes, SSM integration): mid-chunked-prefill and parked
        slots step through the batch without clobbering lane-owned rows.

        Robustness plumbing (DESIGN.md §11): ``poison`` (B,) bool is the
        fault-injection hook — marked slots' logits become NaN inside
        the scan (the all-False default is a no-op ``where``, bitwise
        transparent).  The extra ``finite`` output is the containment
        SENTINEL: per-slot AND of ``isfinite`` over every step's logits,
        scanned alongside decode at no extra dispatch — a NaN/Inf at ANY
        step trips it even if later steps look sane again.  Rows are
        independent (attention and MoE-decode routing are per-slot), so
        a poisoned slot cannot perturb its neighbors — which is what
        makes quarantine-and-continue sound.
        """
        def split_fn(ks):
            if greedy:          # keys untouched; sampled slots don't exist
                return ks, ks
            s = jax.vmap(jax.random.split)(ks)          # (B, 2, 2)
            return s[:, 0], s[:, 1]

        def sample(logits, subs):
            g = jnp.argmax(logits, axis=-1)
            if greedy:
                return g
            safe = jnp.where(temperature > 0, temperature, 1.0)
            s = jax.vmap(jax.random.categorical)(subs,
                                                 logits / safe[:, None])
            return jnp.where(temperature > 0, s, g)

        def inject(logits):
            return jnp.where(poison[:, None], jnp.float32(jnp.nan), logits)

        def probe(logits):
            return jnp.all(jnp.isfinite(logits), axis=-1)

        toks, tok, cache, keys, aux = decode_loop(
            cfg, params, tok, cache, n_steps, kv_fmt, sample, keys,
            split_fn=split_fn, live=live, logits_fn=inject, probe_fn=probe)
        finite = jnp.all(aux, axis=0)
        emitted, n_gen, done = mask_chunk_emissions(toks, done, n_gen,
                                                    stop, max_new)
        return emitted, tok, cache, keys, done, n_gen, finite

    @staticmethod
    def _spec_chunk_fn(params, draft_params, tok, cache, keys, done,
                       n_gen, max_new, temperature, stop, live, poison,
                       spec_k, *, cfg, kv_fmt, k: int, n_rounds: int,
                       greedy: bool):
        """The speculative decode chunk: ``n_rounds`` draft/verify/commit
        rounds in one dispatch (DESIGN.md §13).

        Each round (``serving.speculative.spec_round``) drafts ``k``
        candidates per live slot with the DRAFT weights, scores all
        ``k+1`` rows in one TARGET-weight forward, and commits only the
        accepted prefix — each slot advances by its OWN ``n_accept + 1``,
        which is exactly the ragged per-slot `pos` plumbing the engine
        already runs on.  Emission/stop/budget semantics are the
        non-speculative chunk's, applied round-by-round, and the ragged
        per-round emissions are left-packed (``pack_emissions``) into
        the contiguous per-slot prefix the harvest loop reads.  ``k``
        and ``n_rounds`` are static (one program per distinct round
        length — the adaptive controller halves/doubles, keeping the set
        logarithmic); ``spec_k`` (B,) caps acceptance per slot WITHOUT
        retracing.  The two extra outputs are the adaptive-k signal:
        per-slot accepted and offered candidate counts for the chunk.

        The chunk's emitted width is ``n_rounds * (k+1)`` — at least
        ``chunk`` when rounds fully accept, and never read beyond each
        slot's ``n_gen`` delta by the host.  Rows are independent end to
        end (draft, verify and commit are per-slot), so the body runs
        unchanged per shard under the fully-manual shard_map.
        """
        b = tok.shape[0]

        def round_body(carry, _):
            tok, cache, keys, done, n_gen, finite, acc, off = carry
            live_r = ~done if live is None else (live & ~done)
            (emitted, n_emit, tok, cache, keys, done, n_gen, fin_r,
             a) = spec_round(
                cfg, params, draft_params, tok, cache, keys, done,
                n_gen, max_new, temperature, stop, live_r, poison,
                spec_k, kv_fmt=kv_fmt, k=k, greedy=greedy)
            acc = acc + jnp.where(live_r, a, 0)
            off = off + jnp.where(live_r, jnp.minimum(spec_k, k), 0)
            return (tok, cache, keys, done, n_gen, finite & fin_r, acc,
                    off), (emitted, n_emit)

        zero = jnp.zeros((b,), jnp.int32)
        carry = (tok, cache, keys, done, n_gen, jnp.ones((b,), bool),
                 zero, zero)
        (tok, cache, keys, done, n_gen, finite, acc, off), \
            (toks_r, n_r) = jax.lax.scan(round_body, carry, None,
                                         length=n_rounds)
        emitted = pack_emissions(toks_r, n_r)
        return emitted, tok, cache, keys, done, n_gen, finite, acc, off

    # -- host loop ----------------------------------------------------------

    def _emit(self, event: str, **fields) -> None:
        """Journal-sequenced event record (the engine's recovery log)."""
        self.journal.emit(logger, event, **fields)

    def _arm_slot(self, slot: int, req: Request, tok0, key) -> None:
        """Host-side slot state for a freshly admitted, decoding request."""
        with self._loop.span("serve.lane_wait"):
            self._tok[slot] = int(tok0)
        self._keys[slot] = np.asarray(key, np.uint32)
        self._done[slot] = False
        self._live[slot] = True
        self._n_gen[slot] = 0
        self._max_new[slot] = req.max_new
        self._temp[slot] = req.temperature
        self._stop[slot] = -1 if req.stop_token is None else req.stop_token
        self._ssm_armed[slot] = False
        if self.speculative is not None:
            self._adaptive.arm(slot)

    def _park_slot_flags(self, slot: int) -> None:
        """Host flag parking for a slot leaving service (finish, abort,
        quarantine, suspend, migrate-out).  One place so the canaries
        disarm everywhere a slot's device state is about to be reset."""
        self._live[slot] = False
        self._done[slot] = True
        self._temp[slot] = 0.0   # parked slots don't hold the
        self._stop[slot] = -1    # chunk in sampled mode
        self._kv_armed[slot] = False
        self._ssm_armed[slot] = False

    def _admit_dispatch(self, slot: int, req: Request):
        """Run the whole-prompt admission program; host (tok0, key) out."""
        batch = {"tokens": np.asarray(req.tokens, np.int32)[None]}
        key = jax.random.PRNGKey(req.seed)
        tok0, key, self.cache = self._prefill(
            self.params, batch, self.cache, jnp.int32(slot), key,
            jnp.float32(req.temperature))
        return tok0, key

    def _admit(self, slot: int, req: Request, now: float,
               clock) -> Dict[str, Any]:
        t = len(req.tokens)
        if self._warn_compile and t not in self._seen_prompt_lens:
            self._seen_prompt_lens.add(t)
            logger.info("first prompt of length %d: compiling prefill "
                        "(bucket prompt lengths to bound compiles)", t)
        with self._loop.span("serve.lane", uid=req.uid, offset=0,
                             n_valid=t, final=True):
            tok0, key = self._admit_dispatch(slot, req)
            self._loop.add(lane_tokens=t)
            self._arm_slot(slot, req, tok0, key)
        admit_done = clock()
        self._emit("admit", uid=req.uid, slot=slot,
                   shard=self._shard_of(slot), prompt=t, max_new=req.max_new,
                   queue_delay=now - req.arrival_time)
        # queue_delay/ttft are REALIZED here and survive later suspensions
        # (and clock rebasing across serves); decode_spent accumulates
        # occupied seconds from earlier occupancies of this request
        return {"admit_time": now, "out": [], "prev_n_gen": 0,
                "queue_delay": now - req.arrival_time,
                "ttft": admit_done - req.arrival_time, "decode_spent": 0.0}

    def _admit_ready(self, sched: SlotScheduler, state: Dict[int, Any],
                     now: float, clock) -> None:
        """Whole-prompt admission: drain every (free slot, arrived req) pair.

        A picked request with a pending snapshot resumes (one restore
        scatter) instead of prefilling from scratch — the policy ranked
        it; how it re-enters is the snapshot's business.
        """
        while True:
            adm = sched.next_admission(now)
            if adm is None:
                return
            slot, req = adm
            snap = sched.resumable.pop(req.uid, None)
            if snap is not None:
                self._resume(sched, state, slot, req, snap, clock)
            else:
                state[slot] = self._admit(slot, req, now, clock)

    # lane-cursor plumbing (the sharded engine keeps one cursor PER SHARD)
    def _park_lane(self) -> None:
        self._pf = None

    def _lane_busy(self) -> bool:
        return self._pf is not None

    def _decode_live(self):
        """The ``live`` argument for the decode chunk.

        Whole mode never has a mid-prefill rider, so it skips the live
        gating entirely (``None`` lowers to the cheaper PR-3 decode path;
        parked-slot garbage writes are harmless there because admission
        overwrites the whole slot).
        """
        if self.prefill_mode != "chunked":
            return None
        return jnp.asarray(self._live)

    def _shard_of(self, slot: int) -> Optional[int]:
        """Owning shard of ``slot`` for event records (unsharded: None)."""
        return None

    def _reset_dispatch(self, slot: int) -> None:
        """Device-side slot retirement (park pos, zero SSM state).

        The ONE place a leaving slot's device state is reset — finish,
        prefill abort, suspend, quarantine and shard-drain migration all
        route through here, which is where the paged engine hooks page
        release + block-table clearing.
        """
        self.cache = self._reset(self.cache, jnp.int32(slot))

    def _drop_lane_cursor(self, slot: int) -> None:
        """Forget any in-flight lane cursor feeding ``slot`` (abort path).

        The lane scratch itself needs no cleanup: a later prefill writes
        (and only ever reads) rows below its own cursor.
        """
        if self._pf is not None and self._pf["slot"] == slot:
            self._pf = None

    def _make_sched(self) -> SlotScheduler:
        sched = SlotScheduler(self.n_slots, policy=self.admission_policy,
                              max_queue=self.max_queue,
                              shedding=self.shedding, journal=self.journal)
        self._seed_sched(sched)
        return sched

    def _seed_sched(self, sched: SlotScheduler) -> None:
        """Carry restore-pending snapshots (and drained shards, sharded)
        into a fresh scheduler at serve() entry."""
        sched.resumable.update(self._pending_resume)
        self._pending_resume = {}

    def _start_prefill(self, sched: SlotScheduler, slot: int, req: Request,
                       now: float, shard=None) -> Dict[str, Any]:
        """Park a slot for lane feeding; returns its lane cursor.

        The parked-slot invariants live HERE, once: the slot rides the
        decode batch write-masked until armed, so its live/done flags and
        sampling vectors must be cleared before the next decode chunk —
        the sharded engine's per-shard lanes reuse this parking verbatim.
        """
        sched.mark_prefilling(slot)
        self._park_slot_flags(slot)
        self._emit("prefill-start", uid=req.uid, shard=shard, slot=slot,
                   prompt=len(req.tokens),
                   chunks=-(-len(req.tokens) // self.p_chunk),
                   queue_delay=now - req.arrival_time)
        return {"slot": slot, "req": req, "offset": 0, "admit_time": now}

    def _advance_lane(self, sched: SlotScheduler, state: Dict[int, Any],
                      clock) -> None:
        """Chunked admission: start/advance the ONE in-flight prefill.

        Each call moves the lane by at most ``p_chunk`` prompt tokens (one
        fixed-shape dispatch), so the stall a decode chunk ever waits
        behind is bounded by one lane chunk — not a whole prompt.  On the
        final chunk the slot is armed exactly as ``_admit`` would arm it.
        """
        now = clock()
        while self._pf is None:
            adm = sched.next_admission(now)
            if adm is None:
                return
            slot, req = adm
            snap = sched.resumable.pop(req.uid, None)
            if snap is not None:    # resume: no lane needed, keep admitting
                self._resume(sched, state, slot, req, snap, clock)
                continue
            self._pf = self._start_prefill(sched, slot, req, now)
        pf = self._pf
        slot, req, off = pf["slot"], pf["req"], pf["offset"]
        t = len(req.tokens)
        n_valid = min(self.p_chunk, t - off)
        final = off + n_valid >= t
        with self._loop.span("serve.lane", uid=req.uid, offset=off,
                             n_valid=n_valid, final=final):
            chunk_toks = np.zeros((1, self.p_chunk), np.int32)
            chunk_toks[0, :n_valid] = req.tokens[off:off + n_valid]
            logits = self._lane_dispatch(req, chunk_toks, slot, off,
                                         n_valid, final)
            self._loop.add(lane_tokens=n_valid)
            pf["offset"] = off + n_valid
            if not final:
                return
            tok0, key = self._finish_dispatch(logits, req, slot)
            self._arm_slot(slot, req, tok0, key)
            sched.mark_decoding(slot)
            state[slot] = {"admit_time": pf["admit_time"], "out": [],
                           "prev_n_gen": 0,
                           "queue_delay": pf["admit_time"] - req.arrival_time,
                           "ttft": clock() - req.arrival_time,
                           "decode_spent": 0.0}
            self._emit("prefill-done", uid=req.uid, slot=slot, prompt=t,
                       ttft=state[slot]["ttft"])
            self._pf = None

    def _lane_dispatch(self, req: Request, toks, slot: int, off: int,
                       n_valid: int, final: bool):
        """Run one lane chunk of ``req`` (``toks`` (1, p_chunk), its first
        ``n_valid`` from prompt offset ``off``) into ``slot``; returns the
        chunk's logits (the head's only on the ``final`` chunk)."""
        logits, self.cache, self.lane = self._lane_fn(
            self.params, toks, self.cache, self.lane, jnp.int32(slot),
            jnp.int32(off), jnp.int32(n_valid), with_head=final,
            wrapped=off >= self._lane_rows)
        return logits

    def _finish_dispatch(self, logits, req: Request, slot: int):
        """The final lane chunk's tail: sample ``req``'s first token and
        arm ``slot`` on the device; returns (tok0, key), on the device.
        The program's call is part of the first-token wait: where the
        device's memory is full, its outputs are allocated only once the
        lane chunk's buffers are freed."""
        key = jax.random.PRNGKey(req.seed)
        temp, at = jnp.float32(req.temperature), jnp.int32(slot)
        with self._loop.span("serve.lane_wait"):
            tok0, key, self.cache = self._finish(
                logits, key, temp, self.cache, at, len(req.tokens))
        return tok0, key

    # -- request lifecycle: cancellation, deadlines, shedding, quarantine ----

    _EVENT_OF = {Status.CANCELLED: "cancel",
                 Status.DEADLINE_EXPIRED: "expire",
                 Status.SHED: "shed"}

    def cancel(self, uid: int) -> None:
        """Request cancellation of ``uid`` in the current ``serve`` run.

        Honored at the next chunk boundary: a queued request is dropped,
        a decoding one completes early with its partial output, both with
        ``Status.CANCELLED``.  Unknown/finished uids are a no-op.  Safe
        to call from a ``progress_cb`` or another thread (set-add/pop on
        a plain set; no token is ever half-emitted — eviction happens
        only between chunks).
        """
        self._cancel_uids.add(uid)

    def suspend(self, uid: int) -> None:
        """Request suspension of ``uid`` at the next chunk boundary.

        A DECODING request is snapshotted (``SlotSnapshot``) and
        requeued RESUMABLE: when the admission policy next picks it (and
        a slot is free), it restores and continues bit-identically to an
        uninterrupted run.  A PREFILLING request aborts its lane and
        requeues plain (restarts from chunk 0 — DESIGN.md §12); queued,
        unknown and finished uids are a no-op.  Same thread-safety
        contract as ``cancel``.
        """
        self._suspend_uids.add(uid)

    def _unadmitted(self, sched: SlotScheduler, req: Request, status: str,
                    now: float, results: List[RequestResult]) -> None:
        """Terminal result for a request that is leaving the QUEUE.

        Usually a request that never produced a token — but a suspended
        (resumable) one that gets shed/expired/cancelled while parked
        still owns partial output and realized timings; its snapshot is
        consumed into the result here so no generated token is ever
        silently dropped.
        """
        snap = sched.resumable.pop(req.uid, None)
        out = (np.asarray(snap.out, np.int32) if snap is not None
               else np.zeros((0,), np.int32))
        results.append(RequestResult(
            uid=req.uid, tokens=out, n_generated=len(out),
            queue_delay=(snap.queue_delay if snap is not None
                         else now - req.arrival_time),
            ttft=snap.ttft if snap is not None else float("inf"),
            decode_seconds=snap.decode_spent if snap is not None else 0.0,
            status=status,
            degraded=sched.degraded.pop(req.uid, None) is not None))
        self._emit(self._EVENT_OF[status], uid=req.uid, status=status,
                   queue_delay=now - req.arrival_time)

    def _finish_slot(self, sched: SlotScheduler, state: Dict[int, Any],
                     slot: int, status: str, now: float,
                     results: List[RequestResult]) -> None:
        """Evict a DECODING slot with its (possibly partial) output.

        The one slot-retirement path: scheduler release, device-side slot
        reset (park pos, zero SSM state), host flag parking, result
        construction and the ``finish`` event all live here so OK
        completion and deadline/cancel eviction cannot drift apart.
        """
        req = sched.release(slot)
        st = state.pop(slot, None)
        self._reset_dispatch(slot)
        self._park_slot_flags(slot)
        out = st["out"] if st else []
        ttft = st["ttft"] if st else float("inf")
        qd = st["queue_delay"] if st else now - req.arrival_time
        # decode_seconds = OCCUPIED time only: this occupancy plus any
        # accumulated before a suspension — parked wall time between
        # preempt and resume never counts against decode_tok_s
        spent = (st["decode_spent"] + (now - st["admit_time"])) if st \
            else 0.0
        res = RequestResult(
            uid=req.uid, tokens=np.asarray(out, np.int32),
            n_generated=len(out), queue_delay=qd,
            ttft=ttft, decode_seconds=spent, status=status,
            degraded=sched.degraded.pop(req.uid, None) is not None)
        results.append(res)
        self._emit("finish", uid=req.uid, slot=slot,
                   shard=self._shard_of(slot), status=status, n=len(out),
                   ttft=ttft, tok_s=res.decode_tok_s)

    def _abort_prefill(self, sched: SlotScheduler, slot: int) -> Request:
        """Tear down a PREFILLING slot (cancel/deadline/suspend mid-lane)."""
        self._drop_lane_cursor(slot)
        req = sched.release(slot)
        self._reset_dispatch(slot)
        self._park_slot_flags(slot)
        return req

    # -- slot snapshots: suspend / resume / preempt / migrate (§12) ---------

    def _snap_dispatch(self, slot: int) -> Dict[str, Any]:
        """Device->host batch-1 slice of ``slot`` (sharded override picks
        the owner's row out of the shard-stacked extract)."""
        return jax.device_get(self._snap(self.cache, jnp.int32(slot)))

    def _restore_dispatch(self, slot: int, snap: SlotSnapshot) -> None:
        """Scatter a snapshot's device payload into ``slot``.

        The trimmed KV rows zero-pad back to slot capacity on the host
        (pad rows sit beyond ``pos`` — masked out of attention and the
        canary alike), then one ``write_cache_slot`` program commits the
        whole slot: packed bytes verbatim, no dequant round trip.
        """
        solo = unpack_device_state(snap.device, slot_row_capacity(self.cache))
        self.cache = self._restore_prog(self.cache, solo, jnp.int32(slot))

    def _snapshot_slot(self, sched: SlotScheduler, state: Dict[int, Any],
                       slot: int, clock) -> SlotSnapshot:
        """READ-ONLY ``SlotSnapshot`` of a live DECODING slot.

        Pure extraction — the slot keeps decoding undisturbed, which is
        what lets ``checkpoint`` snapshot a running engine.  KV rows are
        trimmed to ``min(pos, capacity)``: direct rows below an unwrapped
        ring pointer, the whole ring once SWA has wrapped.
        """
        req = sched.active[slot]
        solo = self._snap_dispatch(slot)
        pos = int(np.asarray(solo["pos"])[0])
        rows = slot_row_capacity(self.cache)
        used = min(pos, rows) if rows is not None else 0
        st = state[slot]
        return SlotSnapshot(
            req=req, pos=pos, used_rows=used,
            device=pack_device_state(solo, used),
            tok=int(self._tok[slot]), key=self._keys[slot].copy(),
            n_gen=int(self._n_gen[slot]), max_new=int(self._max_new[slot]),
            temp=float(self._temp[slot]), stop=int(self._stop[slot]),
            out=list(st["out"]), queue_delay=st["queue_delay"],
            ttft=st["ttft"],
            decode_spent=st["decode_spent"] + (clock() - st["admit_time"]),
            spec_k=(int(self._adaptive.k[slot])
                    if self.speculative is not None else 0))

    def snapshot_slot(self, slot: int) -> SlotSnapshot:
        """Public read-only snapshot of a live slot (mid-serve, e.g. from
        a ``progress_cb`` — migration-cost measurements use this)."""
        if self._sched is None or slot not in self._sched.active:
            raise ValueError(f"slot {slot} holds no live request")
        return self._snapshot_slot(self._sched, self._state, slot,
                                   self._clock)

    def _suspend_slot(self, sched: SlotScheduler, state: Dict[int, Any],
                      slot: int, clock, event: str = "suspend") -> None:
        """Snapshot a DECODING slot and requeue its request as resumable."""
        snap = self._snapshot_slot(sched, state, slot, clock)
        req = sched.suspend_to_queue(slot, snap)
        state.pop(slot, None)
        self._reset_dispatch(slot)
        self._park_slot_flags(slot)
        self._emit(event, uid=req.uid, slot=slot,
                   shard=self._shard_of(slot), n_gen=snap.n_gen,
                   pos=snap.pos, nbytes=snap.nbytes)

    def _resume(self, sched: SlotScheduler, state: Dict[int, Any],
                slot: int, req: Request, snap: SlotSnapshot, clock,
                event: str = "resume") -> None:
        """Restore a snapshot into ``slot`` and rejoin the decode batch.

        Every bit the decode chunk reads — KV rows, ring pointer, SSM
        state, next token, PRNG key, budget counters, sampling vector —
        comes back exactly as suspended, so the remaining stream is the
        uninterrupted run's remaining stream.
        """
        self._restore_dispatch(slot, snap)
        self._tok[slot] = snap.tok
        self._keys[slot] = np.asarray(snap.key, np.uint32)
        self._done[slot] = False
        self._live[slot] = True
        self._n_gen[slot] = snap.n_gen
        self._max_new[slot] = snap.max_new
        self._temp[slot] = snap.temp
        self._stop[slot] = snap.stop
        self._kv_armed[slot] = False
        self._ssm_armed[slot] = False
        if self.speculative is not None:
            # the learned draft length survives preempt/migrate/restore;
            # pre-speculative snapshots (spec_k=0) re-arm at the default
            self._adaptive.arm(slot, snap.spec_k)
        sched.mark_decoding(slot)
        state[slot] = {"admit_time": clock(), "out": list(snap.out),
                       "prev_n_gen": snap.n_gen,
                       "queue_delay": snap.queue_delay, "ttft": snap.ttft,
                       "decode_spent": snap.decode_spent}
        self._emit(event, uid=req.uid, slot=slot,
                   shard=self._shard_of(slot), n_gen=snap.n_gen,
                   pos=snap.pos)

    def _resume_ready(self, sched: SlotScheduler, state: Dict[int, Any],
                      clock) -> None:
        """Drain policy-picked resumable requests into free slots.

        Runs before lane/admission work each iteration: a resume is one
        restore scatter, so it never waits behind a busy prefill lane.
        """
        now = clock()
        while True:
            adm = sched.next_resume(now)
            if adm is None:
                return
            slot, req = adm
            snap = sched.resumable.pop(req.uid)
            self._resume(sched, state, slot, req, snap, clock)

    def _preempt_sweep(self, sched: SlotScheduler, state: Dict[int, Any],
                       clock) -> None:
        """Apply the preemption policy at the chunk boundary."""
        if self.preemption is None:
            return
        for slot in self.preemption.victims(sched, clock()):
            self._suspend_slot(sched, state, slot, clock, event="preempt")

    def drain_shard(self, shard: int) -> None:
        """Take ``shard`` out of rotation (sharded engines only).

        Honored at the next chunk boundary: live DECODING requests
        migrate to healthy shards via snapshot restore, PREFILLING ones
        requeue and restart their lane, and admission stops routing to
        the shard.  The base engine has no shards to drain.
        """
        raise ValueError("drain_shard needs a sharded engine "
                         "(ShardedContinuousEngine)")

    # -- crash recovery: checkpoint / restore (§12) -------------------------

    def checkpoint(self, path) -> Dict[str, Any]:
        """Persist the running serve's resumable state to ``path``.

        Callable mid-serve (from a ``progress_cb`` — i.e. at a chunk
        boundary, the engine's only consistent point).  Captures every
        live DECODING slot as a read-only ``SlotSnapshot`` (the slots
        keep decoding), queued requests with their pending resume
        snapshots, mid-prefill requests as plain restarts, results so
        far, and the journal cursor.  The write is atomic
        (write-then-rename), so a crash DURING checkpointing leaves the
        previous checkpoint intact.  Restore with a FRESH engine's
        ``restore(path)`` + ``serve``.
        """
        sched, state = self._sched, self._state
        if sched is None:
            raise RuntimeError("checkpoint() runs mid-serve — call it "
                               "from a progress_cb")
        snaps, restarts = [], []
        for slot in list(sched.active):
            if sched.phase.get(slot) == PREFILLING:
                restarts.append(sched.active[slot])  # lane restarts chunk 0
            else:
                snaps.append(self._snapshot_slot(sched, state, slot,
                                                 self._clock))
        self._emit("checkpoint", path=str(path), live=len(snaps),
                   queued=len(sched.queue), chunk=self._chunk_idx)
        ck = {"version": 1, "cfg": self.cfg.name, "kv": self._kv,
              "n_slots": self.n_slots, "max_len": self.max_len,
              "seq": self.journal.seq, "chunk_idx": self._chunk_idx,
              "snapshots": snaps, "prefilling": restarts,
              "queued": list(sched.queue),
              "resumable": dict(sched.resumable),
              "results": list(self._results)}
        save_checkpoint(path, ck)
        return ck

    def restore(self, path) -> Tuple[List[Request], List[RequestResult]]:
        """Load a checkpoint into THIS (fresh) engine.

        Returns ``(requests, prior_results)``: hand ``requests`` to
        ``serve()`` — suspended-at-checkpoint requests resume from their
        snapshots bit-identically, mid-prefill and queued ones admit
        normally — and concatenate ``prior_results`` (requests already
        finished before the checkpoint) with the new serve's results for
        the complete set.  Arrival times are rebased to 0 (their waits
        already happened; snapshots carry the realized timings).  The
        journal cursor resumes where the checkpoint left it.
        """
        ck = load_checkpoint(path)
        if ck["cfg"] != self.cfg.name or ck["kv"] != self._kv:
            raise ValueError(
                f"checkpoint was taken on cfg={ck['cfg']!r} kv={ck['kv']!r}"
                f"; this engine is cfg={self.cfg.name!r} kv={self._kv!r}")
        if ck["max_len"] > self.max_len:
            raise ValueError(f"checkpoint max_len {ck['max_len']} exceeds "
                             f"this engine's {self.max_len}")
        self.journal.seq = ck["seq"]
        self._pending_resume = dict(ck["resumable"])
        reqs: List[Request] = []
        for snap in ck["snapshots"]:
            self._pending_resume[snap.req.uid] = snap
            reqs.append(snap.req)
        reqs.extend(ck["prefilling"])
        reqs.extend(ck["queued"])
        reqs = [dataclasses.replace(r, arrival_time=0.0) for r in reqs]
        self._emit("restore", path=str(path), n=len(reqs),
                   chunk=ck["chunk_idx"])
        return reqs, list(ck["results"])

    # terminal journal kinds: a uid that reached one of these needs no
    # replay (finish covers OK / FAILED; the queue-exit kinds cover the
    # rest — ``requeue`` after a quarantine is NOT terminal, the later
    # finish of the retry is)
    _TERMINAL_KINDS = frozenset(("finish", "cancel", "expire", "shed"))

    def restore_from_journal(self, requests: Sequence[Request],
                             messages: Iterable[str]
                             ) -> Tuple[List[Request], List[int]]:
        """Rebuild the pending work of a crashed serve from its event log.

        The cheap tier of crash recovery (DESIGN.md §12/§14): when no
        checkpoint exists (or the checkpoint file died with the host),
        the JSONL journal alone still says WHICH requests reached a
        terminal state.  Given the original ``requests`` and the
        captured log ``messages``, this returns the requests that still
        owe a result — every one re-enters through a fresh prefill (no
        snapshots: partially generated tokens of in-flight requests are
        re-generated, bit-identically, from scratch) — plus the journal
        sequence gaps ``replay`` detected (non-empty gaps mean the log
        lost records and the pending set may over-serve).  Terminal
        results themselves live in the caller's hands (the journal
        records status, not tokens); this method only guarantees no
        request is silently dropped.  The engine's journal cursor
        resumes past the highest replayed record, so post-recovery
        events extend the same sequence.  Use ``restore(path)`` when a
        checkpoint IS available — it resumes mid-stream instead of
        re-prefilling.
        """
        events, gaps = replay(messages)
        done = {e["uid"] for e in events
                if e.get("event") in self._TERMINAL_KINDS and "uid" in e}
        seqs = [e["seq"] for e in events if isinstance(e.get("seq"), int)]
        if seqs:
            self.journal.seq = max(self.journal.seq, max(seqs) + 1)
        pending = [dataclasses.replace(r, arrival_time=0.0)
                   for r in requests if r.uid not in done]
        self._emit("restore", source="journal", n=len(pending),
                   replayed=len(events), gaps=len(gaps))
        return pending, gaps

    def _lifecycle(self, sched: SlotScheduler, state: Dict[int, Any],
                   results: List[RequestResult], clock) -> None:
        """Chunk-boundary lifecycle sweep: cancels, deadlines, shedding.

        Runs BEFORE admission each iteration so a doomed request never
        eats a prefill, and before the decode chunk so an evicted slot's
        budget is not spent on tokens nobody will read.
        """
        now = clock()
        uids = set()
        while self._cancel_uids:            # drain-safe vs concurrent adds
            uids.add(self._cancel_uids.pop())
        for uid in uids:
            req = sched.pop_queued(uid)
            if req is not None:
                self._unadmitted(sched, req, Status.CANCELLED, now, results)
                continue
            slot = next((s for s, r in sched.active.items()
                         if r.uid == uid), None)
            if slot is None:
                continue                    # unknown or already finished
            if sched.phase.get(slot) == PREFILLING:
                req = self._abort_prefill(sched, slot)
                self._unadmitted(sched, req, Status.CANCELLED, now, results)
            else:
                self._finish_slot(sched, state, slot, Status.CANCELLED,
                                  now, results)
        for req in sched.expire_queued(now):
            self._unadmitted(sched, req, Status.DEADLINE_EXPIRED, now,
                             results)
        for slot in list(sched.active):
            req = sched.active[slot]
            if req.deadline_s is None or \
                    now - req.arrival_time <= req.deadline_s:
                continue
            if sched.phase.get(slot) == PREFILLING:
                req = self._abort_prefill(sched, slot)
                self._unadmitted(sched, req, Status.DEADLINE_EXPIRED, now,
                                 results)
            else:
                self._finish_slot(sched, state, slot,
                                  Status.DEADLINE_EXPIRED, now, results)
        for req in sched.enforce_bounds(now):
            self._unadmitted(sched, req, Status.SHED, now, results)
        sus = set()
        while self._suspend_uids:           # drain-safe vs concurrent adds
            sus.add(self._suspend_uids.pop())
        for uid in sus:
            slot = next((s for s, r in sched.active.items()
                         if r.uid == uid), None)
            if slot is None:
                continue                    # queued, unknown or finished
            if sched.phase.get(slot) == PREFILLING:
                req = self._abort_prefill(sched, slot)
                sched.queue.append(req)     # restart the lane from chunk 0
                self._emit("suspend", uid=uid, slot=slot,
                           shard=self._shard_of(slot), resumable=False)
            else:
                self._suspend_slot(sched, state, slot, clock)

    def _quarantine(self, sched: SlotScheduler, state: Dict[int, Any],
                    results: List[RequestResult], bad, cause: Dict[int, str],
                    clock) -> None:
        """Contain slots that tripped a detector this chunk.

        The faulted chunk's emissions are DISCARDED (quarantine runs
        before harvest), the slot is reset and returned to the free list,
        and the victim either requeues (retry budget left — a fresh
        prefill replays it from scratch, so a one-shot fault yields the
        full fault-free output) or fails with its pre-fault prefix.
        Healthy slots are untouched: decode rows are independent, so
        their tokens/cache are bit-identical to a fault-free run.
        """
        for slot in [s for s in list(sched.active) if bad[s]]:
            req = sched.active[slot]
            self._emit("quarantine", uid=req.uid, slot=slot,
                       shard=self._shard_of(slot), cause=cause.get(slot),
                       retries_left=req.retries, chunk=self._chunk_idx - 1)
            st = state.pop(slot, None)
            sched.release(slot)
            self._reset_dispatch(slot)
            self._park_slot_flags(slot)
            if req.retries > 0:
                sched.submit(dataclasses.replace(req,
                                                 retries=req.retries - 1))
                self._emit("requeue", uid=req.uid,
                           retries_left=req.retries - 1)
                continue
            now = clock()
            out = st["out"] if st else []
            ttft = st["ttft"] if st else float("inf")
            qd = st["queue_delay"] if st else now - req.arrival_time
            spent = (st["decode_spent"] + (now - st["admit_time"])) if st \
                else 0.0
            res = RequestResult(
                uid=req.uid, tokens=np.asarray(out, np.int32),
                n_generated=len(out), queue_delay=qd,
                ttft=ttft, decode_seconds=spent, status=Status.FAILED,
                degraded=sched.degraded.pop(req.uid, None) is not None)
            results.append(res)
            self._emit("finish", uid=req.uid, slot=slot,
                       shard=self._shard_of(slot), status=Status.FAILED,
                       n=len(out), ttft=ttft, tok_s=res.decode_tok_s)

    # -- KV integrity canaries (opt-in: kv_integrity=True) ------------------

    def _kv_refresh(self) -> None:
        """Checksum each live slot's stable KV rows before the chunk.

        Decode only APPENDS: the rows the next chunk cannot write are
        immutable through a healthy decode chunk, so their
        position-weighted fold (``kv_slot_checksum``) must read back
        identical afterwards.  The fold is WINDOW-AWARE: it covers each
        slot's occupied rows minus the rows within the chunk's write
        horizon of the ring pointer, so wrapped SWA slots stay armed
        (the pre-fix code disarmed any slot whose window was about to
        wrap, leaving long SWA requests unprotected for most of their
        life).  Only a horizon spanning the whole ring (window <=
        horizon) disarms — every row is then legitimately writable.

        Also the VERIFY point of the SSM at-rest canary: recurrent state
        integrates inside a chunk, so instead of pinning it across the
        decode, ``_ssm_rearm`` folds it right after each chunk and this
        checks nothing moved the bits while the slot sat idle between
        chunks (admission/resume/reset disarm their slots first).  The
        trip is folded into this chunk's containment mask.
        """
        if self._has_attn_kv:
            pos = np.asarray(jax.device_get(self.cache["pos"]))
            armed = self._live.copy()
            hz = self._chunk_horizon()
            w = self.cfg.sliding_window
            if w and hz >= w:
                armed[:] = False    # the whole ring is writable: vacuous
            self._kv_armed = armed
            self._kv_horizon = hz
            self._kv_upto = np.where(armed, pos, 0).astype(np.int32)
            self._kv_sum = np.asarray(jax.device_get(
                self._kv_check(self.cache, jnp.asarray(self._kv_upto),
                               jnp.int32(hz))))
        if self._has_ssm:
            cur = np.asarray(jax.device_get(self._ssm_check(self.cache)))
            self._ssm_bad = (cur != self._ssm_sum) & self._ssm_armed \
                & self._live
        else:
            self._ssm_bad[:] = False

    def _kv_verify(self):
        """(B,) bool: armed slots whose committed rows changed bits."""
        if not self._has_attn_kv:
            return np.zeros((self.n_slots,), bool)
        chk = np.asarray(jax.device_get(
            self._kv_check(self.cache, jnp.asarray(self._kv_upto),
                           jnp.int32(self._kv_horizon))))
        return (chk != self._kv_sum) & self._kv_armed

    def _ssm_rearm(self) -> None:
        """Fold live slots' recurrent state post-chunk; arm for the next
        ``_kv_refresh`` at-rest check."""
        self._ssm_sum = np.asarray(jax.device_get(
            self._ssm_check(self.cache)))
        self._ssm_armed = self._live.copy()

    # -- fault injection (no-op without a plan) -----------------------------

    def _inject_faults(self, sched: SlotScheduler):
        """Apply due faults from the serve's ``FaultPlan``; (B,) poison.

        Without a plan this is a zeros vector and an early return — the
        engine runs the exact fault-free programs.  Victim-targeted
        faults wait (unfired) until their uid is actually DECODING, so a
        fault aimed at a queued request fires on admission instead of
        silently missing its window.
        """
        poison = np.zeros((self.n_slots,), bool)
        plan = self._fault_plan
        if plan is None:
            return poison
        ci = self._chunk_idx
        for i, f in plan.pending("delay", ci):
            plan.fire(i)
            self._emit("fault", kind="delay", shard=f.shard,
                       seconds=f.seconds, chunk=ci)
            time.sleep(f.seconds)
        for i, f in plan.pending("shard_down", ci):
            plan.fire(i)
            self._emit("fault", kind="shard_down", shard=f.shard, chunk=ci)
            self.drain_shard(f.shard)   # honored at the next boundary
        uid2slot = {r.uid: s for s, r in sched.active.items()}
        for i, f in plan.pending("nan_logits", ci):
            s = uid2slot.get(f.uid)
            if s is None or not self._live[s]:
                continue
            plan.fire(i)
            poison[s] = True
            self._emit("fault", kind="nan_logits", uid=f.uid, slot=s,
                       chunk=ci)
        for i, f in plan.pending("kv_flip", ci):
            s = uid2slot.get(f.uid)
            if s is None or not self._live[s]:
                continue
            n_rows = int(np.asarray(jax.device_get(self.cache["pos"]))[s])
            if n_rows <= 0:
                continue
            plan.fire(i)
            self.cache = flip_kv_bytes(self.cache, s, n_rows, plan.rng(i),
                                       n_bytes=f.n_bytes)
            self._emit("fault", kind="kv_flip", uid=f.uid, slot=s,
                       n_bytes=f.n_bytes, chunk=ci)
        return poison

    # -- the decode dispatch (non-speculative or speculative) ---------------

    def _spec_round_shape(self) -> Tuple[int, int]:
        """(k, n_rounds) for the NEXT speculative dispatch.

        The round length is the max live slot's ``spec_k`` (per-slot caps
        ride the dispatch as a vector; the program is compiled per k),
        and the round count keeps the worst-case full-accept advance
        near the engine's configured ``chunk`` so spec and non-spec runs
        admit/evict on comparable boundaries.
        """
        live = self._live & ~self._done
        k = self._adaptive.round_k(live)
        return k, max(1, self.chunk // (k + 1))

    def _chunk_horizon(self) -> int:
        """Max KV rows ONE slot may write in the next decode dispatch
        (the integrity canary excludes ring rows inside this horizon)."""
        if self.speculative is None:
            return self.chunk
        k, n_rounds = self._spec_round_shape()
        return n_rounds * (k + 1)

    def chunk_args(self, poison):
        """The decode chunk's argument row after ``params``: host slot
        vectors (uploaded), the device cache, and the (B,) ``poison``
        mask.  ``_chunk_jit(params, *chunk_args(...), n_steps=chunk,
        greedy=...)`` is the exact decode program ``serve`` dispatches."""
        return (jnp.asarray(self._tok), self.cache,
                jnp.asarray(self._keys), jnp.asarray(self._done),
                jnp.asarray(self._n_gen), jnp.asarray(self._max_new),
                jnp.asarray(self._temp), jnp.asarray(self._stop),
                self._decode_live(), jnp.asarray(poison))

    def _dispatch_chunk(self, poison):
        """Run one decode chunk and fold the results into host slot state.

        Dispatches the speculative program when the engine was built with
        ``speculative=`` (same argument row plus the draft weights and
        the per-slot ``spec_k`` caps; same outputs plus the acceptance
        counts that feed the adaptive-k controller), the plain chunk
        otherwise.  Returns ``(emitted, finite)`` as host arrays — the
        emitted width differs between the two paths (``chunk`` vs
        ``n_rounds * (k+1)``), which the harvest loop never notices: it
        reads each slot's ``n_gen`` delta off the packed prefix.
        """
        loop = self._loop
        with loop.span("serve.upload"):
            args = self.chunk_args(poison)
        greedy = bool((self._temp == 0.0).all())
        with loop.span("serve.dispatch"):
            if self.speculative is None:
                (emitted, tok, self.cache, keys, done, n_gen,
                 finite) = self._chunk_jit(self.params, *args,
                                           n_steps=self.chunk,
                                           greedy=greedy)
                acc = off = None
            else:
                k, n_rounds = self._spec_round_shape()
                (emitted, tok, self.cache, keys, done, n_gen, finite, acc,
                 off) = self._spec_jit(self.params, self.draft_params,
                                       *args, jnp.asarray(self._adaptive.k),
                                       k=k, n_rounds=n_rounds,
                                       greedy=greedy)
        # one host transfer per chunk; copies (not views) because the
        # admission path mutates these slotwise between chunks
        with loop.span("serve.wait"):
            got = jax.device_get((emitted, tok, keys, done, n_gen, finite)
                                 + (() if acc is None else (acc, off)))
        with loop.span("serve.harvest"):
            emitted, tok, keys, done, n_gen, finite = got[:6]
            self._tok = np.array(tok)
            self._keys = np.array(keys, np.uint32)
            self._done = np.array(done)
            self._n_gen = np.array(n_gen)
            if acc is not None:
                acc, off = np.asarray(got[6]), np.asarray(got[7])
                self.spec_accepted += int(acc.sum())
                self.spec_offered += int(off.sum())
                self._spec_acc_slot += acc.astype(np.int64)
                self._spec_off_slot += off.astype(np.int64)
                old_k = self._adaptive.k.copy()
                self._adaptive.update(self._live, acc, off)
                for s in np.nonzero(self._adaptive.k != old_k)[0]:
                    self._emit("spec-k", slot=int(s),
                               k=int(self._adaptive.k[s]),
                               ema=round(float(self._adaptive.ema[s]), 3),
                               chunk=self._chunk_idx)
        return emitted, np.asarray(finite)

    def _count_chunk(self, sched: SlotScheduler) -> None:
        """The next decode chunk's counters for the iteration record:
        the slots that decode in it, its steps (a speculative chunk's
        rows written per slot), and the valid K/V rows its attention
        reads -- at step j (1..steps) a slot reads its position (prompt
        plus tokens generated) plus j rows, at most the sliding window."""
        live = np.nonzero(self._live & ~self._done)[0]
        steps = self._chunk_horizon()
        rows = 0
        if self._has_attn_kv and live.size:
            pos = self._n_gen[live] + np.array(
                [len(sched.active[int(s)].tokens) for s in live])
            read = pos[:, None] + np.arange(1, steps + 1)
            if self.cfg.sliding_window:
                read = np.minimum(read, self.cfg.sliding_window)
            rows = int(read.sum())
        self._loop.add(live=live.size, steps=steps, rows=rows)

    def spec_stats(self) -> Dict[str, Any]:
        """Aggregate speculative acceptance counters (benches read this)."""
        if self.speculative is None:
            raise ValueError("engine was built without speculative=")
        off = max(self.spec_offered, 1)
        return {"accepted": self.spec_accepted,
                "offered": self.spec_offered,
                "accept_rate": self.spec_accepted / off}

    def _check_request(self, r: Request) -> None:
        """Reject a request the engine cannot serve correctly, up front.

        A full-cache slot would clamp-write its last row and return
        garbage with no error (SWA caches are window-sized rings — they
        wrap instead of overflowing), and a clamped lane write would
        corrupt a chunked prefill silently — so both limits are hard
        errors at submit, not runtime surprises.
        """
        if not self.cfg.sliding_window and \
                len(r.tokens) + r.max_new > self.max_len:
            raise ValueError(
                f"request uid={r.uid}: prompt ({len(r.tokens)}) + "
                f"max_new ({r.max_new}) exceeds max_len "
                f"({self.max_len})")
        # the lane scratch is indexed by ABSOLUTE offset (bit-equality
        # needs natural order), so prompts must fit it — unless the lane
        # is a ring too (``_lane_ring``), where writes wrap modulo
        # ``_lane_rows`` and chunked admission accepts any prompt length
        # a whole prefill of the same SWA model would
        if self.prefill_mode == "chunked" and not self._lane_ring and \
                len(r.tokens) > self._lane_rows:
            raise ValueError(
                f"request uid={r.uid}: prompt ({len(r.tokens)}) "
                f"exceeds the prefill-lane scratch "
                f"({self._lane_rows} rows) — raise max_len or use "
                f"prefill_mode='whole'")

    def serve(self, requests: List[Request], progress_cb=None,
              fault_plan=None) -> List[RequestResult]:
        """Drain ``requests`` (honoring arrival times) through the slots.

        Returns one ``RequestResult`` per request — check ``status``:
        completions are OK, evictions carry DEADLINE_EXPIRED/CANCELLED
        with their partial output, backpressure rejects are SHED, and
        containment trips with no retry budget left are FAILED.  The
        loop per iteration: lifecycle sweep (cancels, deadlines,
        bounded-queue shedding) -> admit into free slots whose requests
        have arrived (whole prefills, or ONE lane chunk in chunked mode)
        -> run one decode chunk over ALL slots -> containment checks
        (finite-logits sentinel always; KV canaries when
        ``kv_integrity``) and quarantine -> harvest emissions per slot ->
        evict finished slots (park pos, zero SSM state) -> repeat.  Idle
        gaps (queue non-empty but nothing arrived) sleep to the next
        arrival instead of spinning.

        ``fault_plan`` (a ``serving.faults.FaultPlan``) injects seeded
        faults for chaos testing; None (the default) leaves every hook a
        no-op and the output bit-identical to pre-robustness serving.
        """
        if fault_plan is not None:
            fault_plan.reset()
            requests = fault_plan.apply_arrivals(requests)
        self._fault_plan = fault_plan
        self._chunk_idx = 0
        self._cancel_uids.clear()   # stale cancels/suspends target a
        self._suspend_uids.clear()  # PAST serve
        sched = self._make_sched()
        for r in requests:
            self._check_request(r)
            sched.submit(r)
        # re-park everything at entry: a normal drain leaves exactly this
        # state, but an ABORTED previous serve (exception mid-prefill,
        # KeyboardInterrupt) would otherwise leak its lane cursor and
        # live/done flags into the fresh scheduler — an orphaned slot the
        # new free-list also hands out. Admission overwrites parked
        # slots' cache wholesale, so flags are the only state to clear.
        self._park_lane()
        self._live[:] = False
        self._done[:] = True
        self._kv_armed[:] = False
        self._ssm_armed[:] = False
        t0 = time.time()
        clock = lambda: time.time() - t0   # noqa: E731  (virtual now)
        state: Dict[int, Dict[str, Any]] = {}
        results: List[RequestResult] = []
        chunked = self.prefill_mode == "chunked"
        # expose the live serve to progress_cb-driven introspection
        # (checkpoint(), snapshot_slot(), drain sweeps)
        self._sched, self._state = sched, state
        self._results, self._clock = results, clock
        # each pass of the loop is one iteration: phase spans and, at INFO,
        # one ``iteration`` record before ``progress_cb`` (events.Loop)
        loop = self._loop
        loop.reset()

        while True:
            loop.begin()
            with loop.span("serve.lifecycle"):
                self._lifecycle(sched, state, results, clock)
                work = sched.has_work
                if work:
                    self._preempt_sweep(sched, state, clock)
                    self._resume_ready(sched, state, clock)
            if not work:
                loop.end()
                break
            now = clock()
            if chunked:
                self._advance_lane(sched, state, clock)
            else:
                self._admit_ready(sched, state, now, clock)
            if not self._live.any():
                if not (chunked and self._lane_busy()):
                    # nothing decodes and the lane is idle: sleep to the
                    # next arrival (else the lane keeps grinding)
                    nxt = sched.next_arrival()
                    assert nxt is not None
                    with loop.span("serve.sleep"):
                        time.sleep(max(nxt - clock(), 0.0))
                loop.end()
                continue

            if self.kv_integrity:
                self._kv_refresh()
            poison = self._inject_faults(sched)
            if loop.recording:
                self._count_chunk(sched)
            emitted, finite = self._dispatch_chunk(poison)
            self._chunk_idx += 1
            now = clock()

            with loop.span("serve.harvest"):
                # containment: sentinel (always) + KV canaries (opt-in),
                # then quarantine BEFORE harvest so a faulted chunk's
                # tokens are discarded rather than delivered
                bad = ~np.asarray(finite) & self._live
                cause = {int(s): "nan_logits" for s in np.nonzero(bad)[0]}
                if self.kv_integrity:
                    kv_bad = self._kv_verify() & self._live
                    for s in np.nonzero(kv_bad & ~bad)[0]:
                        cause[int(s)] = "kv_integrity"
                    bad = bad | kv_bad
                    # SSM at-rest trip (computed pre-chunk in
                    # _kv_refresh): the idle-window corruption poisoned
                    # THIS chunk's scan
                    ssm_bad = self._ssm_bad & self._live
                    for s in np.nonzero(ssm_bad & ~bad)[0]:
                        cause[int(s)] = "ssm_integrity"
                    bad = bad | ssm_bad
                if bad.any():
                    self._quarantine(sched, state, results, bad, cause,
                                     clock)

                for slot in list(sched.active):
                    st = state.get(slot)
                    if st is None:      # mid-prefill: nothing to harvest
                        continue
                    delta = int(self._n_gen[slot]) - st["prev_n_gen"]
                    st["out"].extend(emitted[slot, :delta].tolist())
                    st["prev_n_gen"] = int(self._n_gen[slot])
                    if self._done[slot]:
                        self._finish_slot(sched, state, slot, Status.OK,
                                          now, results)
                if self.kv_integrity and self._has_ssm:
                    self._ssm_rearm()
            loop.end()
            if progress_cb is not None:
                progress_cb(self, sched)
        self._fault_plan = None
        self._sched = self._state = self._results = self._clock = None
        return results
