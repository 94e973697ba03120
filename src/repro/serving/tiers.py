"""Per-slot serving tiers: weights x KV x prefill-activation formats.

DESIGN.md §15: the quantized x quantized prefill path gives the engine a
THIRD per-slot quality axis.  A ``TierSpec`` names one point in the
product  {bf16, nxfp6, nxfp4, ...} weights  x  {dense, nxfp4, ...} KV  x
{dense, amxfp4, ...} prefill activations,  and ``TieredContinuousEngine``
carries one tier per slot exactly like per-slot temperature/stop vectors:
requests opt in via ``Request.tier``, everything else rides the engine's
default tier.

Mechanics:

- WEIGHTS: one parameter set per distinct ``weight_fmt`` (the raw tree
  for None, a ``direct_cast_tree`` product otherwise).  Decode always
  runs the tier's cast weights — identical numerics to a single-policy
  engine built at that format.
- KV: one full-B cache ARENA per distinct ``kv_fmt``.  Slot numbering is
  GLOBAL (slot ``s`` exists in every arena; only its tier's arena holds
  live bytes), so the scheduler, admission policies and shedding logic
  are untouched.  Decode dispatches once per (weight_fmt, kv_fmt) group
  present among live slots, with the other tiers' rows ridden done+
  not-live — the same masking that lets mid-prefill slots ride the base
  engine's decode batch.
- PREFILL ACTIVATIONS: ``act_fmt`` threads the §15 quantized-activation
  prefill (``models.common.qact``).  On TPU both operands stay packed and
  the fused dual-dequant ``nxfp_qq_matmul`` kernel streams them; on XLA
  backends the quantized-act tier prefills against RECYCLED dense weights
  (``dense_like`` of the tier's cast product — the PR-8 draft trick), so
  it skips the per-lane-chunk weight dequant a dense-act prefill over
  QTensor weights pays per GEMM per layer.  That is the TTFT win the
  ``prefill_qq`` bench gates on.

Degraded-KV shedding rung (§15): with ``degrade_kv_to=<tier>`` and a
``DegradeOverBudget(pool_watermark=...)`` shedding policy, KV-pool
pressure repacks the OLDEST resident expensive-tier slot's KV into the
cheap tier at a chunk boundary — dequantize the packed rows, re-quantize
at the cheaper format, move the slot between arenas — instead of only
degrading FUTURE admissions.  Repacked requests finish with
``RequestResult.degraded=True`` and a ``kv-repack`` journal event.

Guarantees (tests/test_tiers.py):

- A tier whose formats equal a plain ``ContinuousEngine``'s policy emits
  BIT-IDENTICAL tokens to that engine (the dense tier is bitwise the
  pre-tier engine).
- Quantized-act tiers are deterministic (serve twice -> same bytes) and
  within the documented §15 error bound of their dense-act oracle.

Not composed (rejected at init): ``speculative=`` (draft/verify assumes
ONE weight set), ``preemption=`` / ``kv_integrity=`` (snapshot canaries
are single-arena; plain suspend/resume still works — snapshots carry
their request's tier), and ``p_chunk="auto"`` (the probe rig times the
single-arena cache).  Fault plans targeting KV bytes are not wired into
the arenas.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import get_format
from repro.core.pack import bytes_per_block
from repro.core.qtensor import (QTensor, QuantPolicy, dense_like,
                                direct_cast_tree)
from repro.kernels.ops import quantize_qtensor
from repro.models import (init_cache, init_lane, prefill_chunk,
                          prefill_into_slot, read_cache_slot, reset_slot,
                          write_cache_slot)
from repro.models.common import ModelConfig
from .engine import cached_program, named_jit
from .scheduler import DECODING, ContinuousEngine, Request, SlotScheduler
from .snapshot import (pack_device_state, slot_row_capacity,
                       unpack_device_state)

__all__ = ["TierSpec", "TieredContinuousEngine", "default_tiers",
           "repack_kv", "kv_row_bytes"]


@dataclasses.dataclass(frozen=True)
class TierSpec:
    """One serving tier: weight x KV x prefill-activation formats.

    ``None`` means dense (bf16 weights / bf16 KV / dense activations).
    ``act_fmt`` only shapes PREFILL — decode is dense-activation on every
    tier (single-token GEMVs gain nothing from the qq path).
    """

    weight_fmt: Optional[str] = "nxfp4"
    kv_fmt: Optional[str] = "nxfp4"
    act_fmt: Optional[str] = None

    def __post_init__(self):
        for f in (self.weight_fmt, self.act_fmt):
            if f is not None:
                get_format(f)       # raises on unknown format names
        if self.kv_fmt is not None:
            fmt = get_format(self.kv_fmt)
            if fmt.meta_dtype != "uint16":
                raise ValueError(
                    f"kv_fmt={self.kv_fmt!r}: KV cache meta buffers are "
                    f"uint16 — asymmetric (uint32-meta) formats serve "
                    f"activations, not the cache")


def default_tiers(act_fmt: str = "amxfp4") -> Dict[str, TierSpec]:
    """The three-rung ladder the benches serve: dense premium, cast
    standard, and a quantized-everything economy rung whose prefill runs
    the §15 quantized x quantized path."""
    return {
        "premium": TierSpec(weight_fmt=None, kv_fmt=None, act_fmt=None),
        "standard": TierSpec(weight_fmt="nxfp6", kv_fmt="nxfp4",
                             act_fmt=None),
        "economy": TierSpec(weight_fmt="nxfp4", kv_fmt="nxfp4",
                            act_fmt=act_fmt),
    }


def kv_row_bytes(cfg: ModelConfig, kv_fmt: Optional[str]) -> int:
    """Bytes ONE token's K+V rows occupy across all layers of a slot."""
    kvh, hd, n_layers = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    if cfg.family == "ssm":
        return 0
    if kv_fmt is None:
        return 2 * n_layers * kvh * hd * jnp.dtype(cfg.dtype).itemsize
    fmt = get_format(kv_fmt)
    nb = -(-hd // fmt.block_size)
    bpb = bytes_per_block(fmt.block_size, fmt.bits)
    return 2 * n_layers * kvh * nb * (bpb + 2)      # +2: uint16 meta


def repack_kv(cfg: ModelConfig, solo: Dict[str, Any],
              src_fmt: Optional[str], dst_fmt: Optional[str]):
    """Re-quantize a batch-1 slot cache slice between KV formats.

    Blocks run along head_dim, entirely INSIDE one row, so rows are
    position-independent: the ring layout (row = pos % window) survives
    verbatim and the repacked slot keeps decoding mid-ring.  Rows beyond
    ``pos`` must be zeros (the snapshot trim/pad round-trip guarantees
    it) so the re-quantizer never encodes stale garbage bytes.  SSM
    state and ``pos`` pass through untouched.
    """
    layers = solo.get("layers")
    if layers is None or src_fmt == dst_fmt:
        return solo
    if not any(k in layers for k in ("k", "k_packed")):
        return solo                                 # pure-SSM: no attn KV
    out = dict(layers)
    kvh, hd = cfg.n_kv_heads, cfg.hd
    for base in ("k", "v"):
        if src_fmt is None:
            val = jnp.asarray(out.pop(base))
        else:
            packed = jnp.asarray(out.pop(f"{base}_packed"))
            meta = jnp.asarray(out.pop(f"{base}_meta"))
            n_layers, b, s = packed.shape[:3]
            qt = QTensor(packed, meta, get_format(src_fmt).name,
                         (n_layers, b, s, kvh, hd), -1, hd)
            val = qt.dequantize(cfg.dtype)
        if dst_fmt is None:
            out[base] = val.astype(cfg.dtype)
        else:
            qt = quantize_qtensor(val, dst_fmt, axis=-1)
            out[f"{base}_packed"] = qt.packed
            out[f"{base}_meta"] = qt.meta
    return dict(solo, layers=out)


class TieredContinuousEngine(ContinuousEngine):
    """Continuous batching with a per-slot (weights, KV, prefill-act) tier.

    ``tiers`` maps names to ``TierSpec``; ``Request.tier`` picks one
    (None -> ``default_tier``).  See the module docstring for mechanics
    and the compatibility envelope.
    """

    def __init__(self, cfg: ModelConfig, params,
                 tiers: Dict[str, TierSpec],
                 default_tier: Optional[str] = None,
                 degrade_kv_to: Optional[str] = None, **kw):
        if not tiers:
            raise ValueError("tiers must name at least one TierSpec")
        for bad in ("speculative", "preemption"):
            if kw.get(bad) is not None:
                raise ValueError(
                    f"tiered serving does not compose with {bad}=")
        if kw.get("kv_integrity"):
            raise ValueError("tiered serving does not run the KV canaries "
                             "(per-arena checksums are a follow-up)")
        if kw.get("p_chunk") == "auto":
            raise ValueError("p_chunk='auto' probes the single-arena "
                             "cache; pick a static p_chunk")
        self.tiers = dict(tiers)
        self.default_tier = (default_tier if default_tier is not None
                             else next(iter(self.tiers)))
        if self.default_tier not in self.tiers:
            raise ValueError(f"default_tier {self.default_tier!r} not in "
                             f"tiers {sorted(self.tiers)}")
        if degrade_kv_to is not None and degrade_kv_to not in self.tiers:
            raise ValueError(f"degrade_kv_to {degrade_kv_to!r} not in "
                             f"tiers {sorted(self.tiers)}")
        self.degrade_kv_to = degrade_kv_to
        # uid -> tier overrides (KV-repack moves a LIVE request to the
        # cheap tier; its snapshots/restores must follow the new arena)
        self._uid_tier: Dict[int, str] = {}
        self._raw_params_ref = params
        dspec = self.tiers[self.default_tier]
        policy = QuantPolicy(weight_fmt=dspec.weight_fmt,
                             kv_fmt=dspec.kv_fmt)
        super().__init__(cfg, params, policy, **kw)
        # one weight set per distinct format (the default tier's cast
        # product is the base class's self.params — no duplicate cast)
        self._wparams = {dspec.weight_fmt: self.params}
        for spec in self.tiers.values():
            wf = spec.weight_fmt
            if wf not in self._wparams:
                p = (self._raw_params_ref if wf is None else
                     direct_cast_tree(
                         self._raw_params_ref,
                         dataclasses.replace(policy, weight_fmt=wf),
                         quantize_fn=quantize_qtensor))
                self._wparams[wf] = self._place_params(p)
        # per-tier PREFILL weights: packed for the TPU qq kernel, recycled
        # dense (one dequant at build, amortized over every admission) on
        # XLA backends — the dense-act baseline dequantizes its QTensor
        # weights inside every lane-chunk dispatch instead
        packed_acts = jax.default_backend() == "tpu"
        dense_of: Dict[Optional[str], Any] = {}
        self._prefill_params = {}
        for name, spec in self.tiers.items():
            wp = self._wparams[spec.weight_fmt]
            if (spec.act_fmt is not None and spec.weight_fmt is not None
                    and not packed_acts):
                if spec.weight_fmt not in dense_of:
                    dense_of[spec.weight_fmt] = self._place_params(
                        dense_like(wp))
                wp = dense_of[spec.weight_fmt]
            self._prefill_params[name] = wp
        del self._raw_params_ref
        # KV occupancy accounting for the degrade rung (host-only: pos is
        # prompt_len + n_gen, no device transfer on the lifecycle sweep)
        self._row_bytes = {spec.kv_fmt: kv_row_bytes(cfg, spec.kv_fmt)
                           for spec in self.tiers.values()}
        self._max_row_bytes = max(self._row_bytes.values())
        self._row_cap = (None if cfg.family == "ssm"
                         else (cfg.sliding_window or self.max_len))

    # -- tier resolution ----------------------------------------------------

    def _tier_of(self, req: Request) -> str:
        return self._uid_tier.get(req.uid) or req.tier or self.default_tier

    def _check_request(self, r: Request) -> None:
        super()._check_request(r)
        name = r.tier or self.default_tier
        if name not in self.tiers:
            raise ValueError(f"request uid={r.uid}: unknown tier {name!r} "
                             f"(engine tiers: {sorted(self.tiers)})")

    @property
    def cache(self):
        """The default tier's arena (every KV format keeps its own in
        ``_caches``); the programs donate the arenas they are given."""
        return self._caches[self.tiers[self.default_tier].kv_fmt]

    @cache.setter
    def cache(self, value):
        self._caches[self.tiers[self.default_tier].kv_fmt] = value

    # -- construction hooks -------------------------------------------------

    def _init_slot_cache(self):
        self._caches = {}
        for spec in self.tiers.values():
            if spec.kv_fmt not in self._caches:
                self._caches[spec.kv_fmt] = init_cache(
                    self.cfg, self.n_slots, self.max_len, spec.kv_fmt)
        # host tier index, one entry per slot (parked slots keep their
        # last tier so late resets still hit the right arena)
        self._slot_tier: List[str] = [self.default_tier] * self.n_slots
        return self._caches[self.tiers[self.default_tier].kv_fmt]

    def _build_programs(self) -> None:
        cfg, max_len, mk = self.cfg, self.max_len, self._mesh_key
        self._prefills: Dict[Any, Any] = {}
        self._chunks: Dict[Any, Any] = {}
        for spec in self.tiers.values():
            kvf, af = spec.kv_fmt, spec.act_fmt
            if (kvf, af) not in self._prefills:
                # act_fmt=None lowers the byte-identical pre-tier graph,
                # so it shares the base engine's compile-cache key
                key = (("admit", cfg, kvf, max_len, mk) if af is None
                       else ("admit", cfg, kvf, max_len, mk, af))
                self._prefills[(kvf, af)] = cached_program(
                    key, lambda kvf=kvf, af=af: named_jit(
                        "admit", functools.partial(
                            self._tier_admit_fn, cfg=cfg, kv_fmt=kvf,
                            max_len=max_len, act_fmt=af)))
            if kvf not in self._chunks:
                self._chunks[kvf] = cached_program(
                    ("cont_chunk", cfg, kvf, mk),
                    lambda kvf=kvf: named_jit(
                        "decode_chunk",
                        functools.partial(self._chunk_fn, cfg=cfg,
                                          kv_fmt=kvf),
                        static_argnames=("n_steps", "greedy")))
        dspec = self.tiers[self.default_tier]
        self._prefill = self._prefills[(dspec.kv_fmt, dspec.act_fmt)]
        self._chunk_jit = self._chunks[dspec.kv_fmt]
        # reset/snapshot programs are cache-structure-polymorphic (jit
        # retraces per arena pytree), so one program each serves all tiers
        self._reset = self._keep_cache(cached_program(
            ("reset", cfg, mk),
            lambda: named_jit("reset_slot",
                              functools.partial(reset_slot, cfg),
                              donate_argnums=0)), 0, None)
        self._snap = cached_program(
            ("snap", cfg, self._kv, mk),
            lambda: named_jit("snap", read_cache_slot))
        self._restore_prog = cached_program(
            ("restore", cfg, self._kv, mk),
            lambda: named_jit("restore", write_cache_slot))

    def _build_lane(self) -> None:
        cfg, mk = self.cfg, self._mesh_key
        self.lane = init_lane(cfg, self.max_len, self.p_chunk)
        self._lane_fns: Dict[Any, Any] = {}
        for spec in self.tiers.values():
            kvf, af = spec.kv_fmt, spec.act_fmt
            if (kvf, af) in self._lane_fns:
                continue
            if af is None:      # shares the base engine's lane program
                self._lane_fns[(kvf, af)] = cached_program(
                    ("lane", cfg, kvf, self.p_chunk, mk),
                    lambda kvf=kvf: self._lane_program(cfg, kvf))
            else:
                self._lane_fns[(kvf, af)] = cached_program(
                    ("lane", cfg, kvf, self.p_chunk, mk, af),
                    lambda kvf=kvf, af=af: named_jit(
                        "lane_chunk", functools.partial(
                            self._tier_lane_fn, cfg=cfg, kv_fmt=kvf,
                            act_fmt=af),
                        static_argnames=("with_head", "wrapped"),
                        donate_argnums=2))
        dspec = self.tiers[self.default_tier]
        self._lane_fn = self._keep_cache(
            self._lane_fns[(dspec.kv_fmt, dspec.act_fmt)], 2, 1)
        self._finish = self._keep_cache(cached_program(
            ("finish", cfg, mk),
            lambda: named_jit("lane_finish", self._finish_prefill_fn,
                              donate_argnums=3)), 3, 2)

    # -- jitted bodies ------------------------------------------------------

    @staticmethod
    def _tier_admit_fn(params, batch, cache, slot, key, temperature,
                       *, cfg, kv_fmt, max_len, act_fmt):
        """Whole-prompt admission with the tier's prefill-activation
        format threaded through (act_fmt=None == base ``_admit_fn``)."""
        logits, new_cache = prefill_into_slot(cfg, params, batch, cache,
                                              slot, max_len, kv_fmt,
                                              act_fmt=act_fmt)
        tok0, key_out = ContinuousEngine._first_token(logits, key,
                                                      temperature)
        return tok0, key_out, new_cache

    @staticmethod
    def _tier_lane_fn(params, tokens, cache, lane, slot, offset, n_valid,
                      *, cfg, kv_fmt, act_fmt, with_head: bool,
                      wrapped: bool = False):
        """One lane advance with quantized prefill activations."""
        return prefill_chunk(cfg, params, tokens, cache, slot, offset,
                             n_valid, lane, kv_fmt, with_head=with_head,
                             wrapped=wrapped, act_fmt=act_fmt)

    # -- tier-routed dispatches ---------------------------------------------

    def _admit_dispatch(self, slot: int, req: Request):
        name = self._tier_of(req)
        spec = self.tiers[name]
        self._slot_tier[slot] = name
        kvf = spec.kv_fmt
        batch = {"tokens": np.asarray(req.tokens, np.int32)[None]}
        key = jax.random.PRNGKey(req.seed)
        tok0, key, self._caches[kvf] = self._prefills[(kvf, spec.act_fmt)](
            self._prefill_params[name], batch, self._caches[kvf],
            jnp.int32(slot), key, jnp.float32(req.temperature))
        return tok0, key

    def _start_prefill(self, sched, slot: int, req: Request, now: float,
                       shard=None):
        self._slot_tier[slot] = self._tier_of(req)
        return super()._start_prefill(sched, slot, req, now, shard)

    def _lane_dispatch(self, req: Request, toks, slot: int, off: int,
                       n_valid: int, final: bool):
        """The base lane chunk routed to ``req``'s tier: its lane program,
        prefill weights and KV arena."""
        name = self._tier_of(req)
        spec = self.tiers[name]
        kvf = spec.kv_fmt
        logits, self._caches[kvf], self.lane = \
            self._lane_fns[(kvf, spec.act_fmt)](
                self._prefill_params[name], toks, self._caches[kvf],
                self.lane, jnp.int32(slot), jnp.int32(off),
                jnp.int32(n_valid), with_head=final,
                wrapped=off >= self._lane_rows)
        return logits

    def _finish_dispatch(self, logits, req: Request, slot: int):
        kvf = self.tiers[self._tier_of(req)].kv_fmt
        key = jax.random.PRNGKey(req.seed)
        temp, at = jnp.float32(req.temperature), jnp.int32(slot)
        with self._loop.span("serve.lane_wait"):
            tok0, key, self._caches[kvf] = self._finish(
                logits, key, temp, self._caches[kvf], at, len(req.tokens))
        return tok0, key

    def _reset_dispatch(self, slot: int) -> None:
        kvf = self.tiers[self._slot_tier[slot]].kv_fmt
        self._caches[kvf] = self._reset(self._caches[kvf], jnp.int32(slot))

    def _snap_dispatch(self, slot: int) -> Dict[str, Any]:
        kvf = self.tiers[self._slot_tier[slot]].kv_fmt
        return jax.device_get(self._snap(self._caches[kvf],
                                         jnp.int32(slot)))

    def _restore_dispatch(self, slot: int, snap) -> None:
        name = self._tier_of(snap.req)
        self._slot_tier[slot] = name
        kvf = self.tiers[name].kv_fmt
        solo = unpack_device_state(
            snap.device, slot_row_capacity(self._caches[kvf]))
        self._caches[kvf] = self._restore_prog(self._caches[kvf], solo,
                                               jnp.int32(slot))

    def _dispatch_chunk(self, poison):
        """One decode dispatch PER (weight_fmt, kv_fmt) group among live
        slots; other tiers' rows ride each dispatch done + not-live (their
        host state and cache arenas are untouched — only the group's rows
        merge back).  A single-tier engine degenerates to exactly one
        dispatch with the base engine's argument row.
        """
        emitted_all = np.zeros((self.n_slots, self.chunk), np.int32)
        finite_all = np.ones((self.n_slots,), bool)
        groups: Dict[Any, List[int]] = {}
        for s in np.nonzero(self._live)[0]:
            spec = self.tiers[self._slot_tier[int(s)]]
            groups.setdefault((spec.weight_fmt, spec.kv_fmt),
                              []).append(int(s))
        loop = self._loop
        for wf, kvf in sorted(groups, key=repr):
            slots = groups[(wf, kvf)]
            mask = np.zeros((self.n_slots,), bool)
            mask[slots] = True
            greedy = bool((np.where(mask, self._temp, 0.0) == 0.0).all())
            with loop.span("serve.upload"):
                args = (jnp.asarray(self._tok), self._caches[kvf],
                        jnp.asarray(self._keys),
                        jnp.asarray(self._done | ~mask),
                        jnp.asarray(self._n_gen), jnp.asarray(self._max_new),
                        jnp.asarray(self._temp), jnp.asarray(self._stop),
                        jnp.asarray(self._live & mask),
                        jnp.asarray(np.asarray(poison) & mask))
            with loop.span("serve.dispatch"):
                (emitted, tok, cache, keys, done, n_gen,
                 finite) = self._chunks[kvf](
                    self._wparams[wf], *args, n_steps=self.chunk,
                    greedy=greedy)
            self._caches[kvf] = cache
            with loop.span("serve.wait"):
                got = jax.device_get((emitted, tok, keys, done, n_gen,
                                      finite))
            with loop.span("serve.harvest"):
                self._tok[mask] = np.asarray(got[1])[mask]
                self._keys[mask] = np.asarray(got[2], np.uint32)[mask]
                self._done[mask] = np.asarray(got[3])[mask]
                self._n_gen[mask] = np.asarray(got[4])[mask]
                emitted_all[mask] = np.asarray(got[0])[mask]
                finite_all[mask] = np.asarray(got[5])[mask]
        return emitted_all, finite_all

    # -- degraded-KV shedding rung ------------------------------------------

    def _make_sched(self) -> SlotScheduler:
        self._uid_tier.clear()      # tier overrides are per-serve
        sched = super()._make_sched()
        sched.pool_monitor = self._kv_occupancy
        return sched

    def _kv_occupancy(self) -> float:
        """Fraction of the KV budget live slots occupy, priced at each
        slot's OWN tier (budget = every slot full at the priciest tier).
        Pure host arithmetic: pos is prompt_len + n_gen, no transfer."""
        sched = self._sched
        if sched is None or self._row_cap is None or \
                not self._max_row_bytes:
            return 0.0
        used = 0
        for slot, req in sched.active.items():
            if sched.phase.get(slot) != DECODING:
                continue
            pos = len(req.tokens) + int(self._n_gen[slot])
            kvf = self.tiers[self._slot_tier[slot]].kv_fmt
            used += min(pos, self._row_cap) * self._row_bytes[kvf]
        return used / (self.n_slots * self._row_cap * self._max_row_bytes)

    def _lifecycle(self, sched, state, results, clock) -> None:
        super()._lifecycle(sched, state, results, clock)
        self._degrade_sweep(sched, state, clock)

    def _degrade_sweep(self, sched: SlotScheduler, state: Dict[int, Any],
                       clock) -> None:
        """Over the pool watermark: repack resident expensive-tier slots'
        KV into ``degrade_kv_to`` (oldest first) until occupancy drops
        back under it or no repackable slot remains."""
        if self.degrade_kv_to is None or self.shedding is None:
            return
        wm = getattr(self.shedding, "pool_watermark", None)
        if wm is None:
            return
        dst = self.degrade_kv_to
        dst_cost = self._row_bytes[self.tiers[dst].kv_fmt]
        while self._kv_occupancy() >= wm:
            cands = [(state[s]["admit_time"], s)
                     for s, r in sched.active.items()
                     if sched.phase.get(s) == DECODING and s in state
                     and self._slot_tier[s] != dst
                     and self._row_bytes[
                         self.tiers[self._slot_tier[s]].kv_fmt] > dst_cost]
            if not cands:
                return
            _, slot = min(cands)
            self._repack_slot(sched, slot, dst)

    def _repack_slot(self, sched: SlotScheduler, slot: int,
                     dst_name: str) -> None:
        """Move a LIVE decoding slot to ``dst_name`` at a chunk boundary:
        re-quantize its KV rows into the destination arena, park the
        source arena's slot, and flip the tier index — decode carries on
        mid-stream under the cheaper tier next chunk."""
        src_name = self._slot_tier[slot]
        src, dst = self.tiers[src_name].kv_fmt, self.tiers[dst_name].kv_fmt
        req = sched.active[slot]
        pos = 0
        if src != dst:
            solo = self._snap(self._caches[src], jnp.int32(slot))
            pos = int(np.asarray(jax.device_get(solo["pos"]))[0])
            cap = slot_row_capacity(self._caches[src])
            used = min(pos, cap) if cap is not None else 0
            # trim+pad round trip zeroes rows beyond pos, so the
            # re-quantizer never encodes stale garbage bytes
            dev = unpack_device_state(pack_device_state(solo, used), cap)
            self._caches[dst] = self._restore_prog(
                self._caches[dst], repack_kv(self.cfg, dev, src, dst),
                jnp.int32(slot))
            self._caches[src] = self._reset(self._caches[src],
                                            jnp.int32(slot))
        self._slot_tier[slot] = dst_name
        self._uid_tier[req.uid] = dst_name
        sched.degraded.setdefault(req.uid, (None, False))
        self._emit("kv-repack", uid=req.uid, slot=slot, src=src_name,
                   dst=dst_name, pos=pos,
                   occupancy=round(self._kv_occupancy(), 4))
