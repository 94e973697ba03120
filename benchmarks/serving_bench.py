"""Serving decode throughput: host vs device loop + continuous traffic.

The ISSUE-2 tentpole measurement. The seed engine ran one jit dispatch,
one device→host copy and one ``block_until_ready`` per generated token, so
decode tok/s on small-batch serving was *dispatch-bound* — the paper's
footprint→bandwidth win (§6/Fig. 7) never reached the wall clock. The
on-device chunked loop (DESIGN.md §7) amortizes dispatch over ``chunk``
tokens; this bench reports decode tok/s for both loops across KV/weight
formats (dense bf16, nxfp4, nxfp6 — the last exercising the 5/6-bit
two-block pack tile end to end) and checks greedy outputs stay
bit-identical between the loops.

The ISSUE-3 scenario (``continuous``): Poisson arrivals with MIXED
prompt/output lengths served two ways — fixed FIFO batches through
``ServeEngine`` (every batch runs to its slowest member) vs the
``ContinuousEngine`` slot scheduler (finished slots re-admit at chunk
boundaries, DESIGN.md §8). Reports aggregate useful tok/s and p50/p99
TTFT for both.

The ISSUE-7 scenarios (``preemption``, ``drain``): priority preemption
priced against wait-your-turn on the same workload, and a live shard
drain-and-migrate priced against the same traffic served healthy — both
with the §12 bitwise contract asserted in-bench before any row lands.

The ISSUE-8 scenario (``speculative``): self-speculative decode — the
NxFP4 product verifies, its recycled dense copy drafts — priced against
plain decode at k in {2, 4, 8} on a dequant-dominated model, with the
§13 greedy bitwise contract asserted per k and a >=1.3x best-k gate.

CPU-container caveat (DESIGN.md §6): absolute tok/s is not TPU wall time,
but the dispatch-overhead regime this bench isolates is *worse* on real
accelerators (per-dispatch latency hides more compute), so the host→device
speedup measured here is a lower bound on the serving win.

NXFP_BENCH_QUICK=1 shrinks shapes for the CI smoke row.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.models.common import ModelConfig
from repro.serving import (ContinuousEngine, DegradeOverBudget, DropOldest,
                           Fault, FaultPlan, FifoPolicy, PriorityAdmission,
                           PriorityPreemption, RejectNew, Request,
                           ServeEngine, ShortestPromptFirst,
                           SpeculativeConfig, Status, TieredContinuousEngine,
                           TierSpec, TtftDeadline, default_tiers,
                           parse_event)
from .common import Csv

# small enough that a decode step's FLOPs sit well under the per-dispatch
# host overhead — the dispatch-bound regime the on-device loop targets
# (production decode at small batch is the same regime on TPU: per-step
# compute hides under dispatch+sync latency). head_dim 64 = two 32-blocks,
# so the 5/6-bit KV rows are two-block-tile eligible end to end (a
# head_dim under 64 would silently drop nxfp5/6 attention to the XLA path)
SERVE_CFG = ModelConfig(
    name="serve-lm", family="dense",
    n_layers=1, d_model=64, n_heads=1, n_kv_heads=1,
    d_ff=256, vocab=256, remat=False,
)


def _quick() -> bool:
    return os.environ.get("NXFP_BENCH_QUICK") == "1"


def run_loops(csv: Csv):
    cfg = SERVE_CFG
    b, prompt = 4, 16
    # context stays short by design: the quantity under test is dispatch
    # amortization, and on CPU the XLA-emulated per-step cache dequant
    # grows with context until it buries the dispatch term (~2x per 100
    # cached tokens for quantized KV) — long-context scaling is
    # kernels_bench's decode-attn row, not this bench
    max_new, chunk = (48, 16) if _quick() else (96, 32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab, (b, prompt))
             .astype(np.int32)}

    for fmt in [None, "nxfp4", "nxfp6"]:
        label = fmt or "dense-bf16"
        eng = ServeEngine(cfg, params,
                          QuantPolicy(weight_fmt=fmt, kv_fmt=fmt),
                          max_len=prompt + max_new + 8)
        runs = {}
        for loop in ("host", "device"):
            # warm-up compiles the exact chunk length the timed run uses;
            # best-of-3 timing (greedy decode is deterministic, so the
            # spread is pure host scheduling noise — the quantity under
            # test is dispatch overhead, where min is the honest estimator)
            eng.generate(batch, max_new=chunk, loop=loop, chunk=chunk)
            res = min((eng.generate(batch, max_new=max_new, loop=loop,
                                    chunk=chunk) for _ in range(3)),
                      key=lambda r: r.decode_seconds)
            runs[loop] = res
        identical = bool(
            np.array_equal(runs["host"].tokens, runs["device"].tokens) and
            np.array_equal(runs["host"].n_generated,
                           runs["device"].n_generated))
        for loop, res in runs.items():
            toks = int(res.n_generated.sum())
            tok_s = toks / res.decode_seconds
            us_per_tok = res.decode_seconds / toks * 1e6
            derived = f"tok_s={tok_s:.0f} batch={b}"
            if loop == "device":
                speedup = (runs["host"].decode_seconds /
                           runs["device"].decode_seconds)
                derived += (f" chunk={chunk} speedup_vs_host={speedup:.2f}x "
                            f"bit_identical={identical}")
            csv.add(f"serving/decode/{label}/{loop}-loop", us_per_tok,
                    derived, unit="us_per_tok")
        if not identical:
            raise AssertionError(
                f"greedy device loop diverged from host loop ({label})")


# ---------------------------------------------------------------------------
# self-speculative decoding (ISSUE-8): NxFP target, recycled dense draft
# ---------------------------------------------------------------------------

# sized so the per-step weight-dequant term DOMINATES the step (the regime
# speculation pays off in: the quantized target's step cost is compute the
# recycled bf16 draft does not spend).  d_ff/vocab are the dequant-heavy
# matmuls; head_dim 64 keeps the two-block KV tile eligible
SPEC_BENCH_CFG = ModelConfig(
    name="spec-lm", family="dense",
    n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
    d_ff=1024, vocab=1024, remat=False,
)


def run_speculative(csv: Csv):
    """Speculative vs plain continuous serving at k in {2, 4, 8}.

    The ISSUE-8 tentpole measurement, on the CPU-winning pairing: the
    NxFP4 direct-cast product VERIFIES (it is the model being served —
    its sampling semantics are authoritative) while its own dequantized
    bf16 copy DRAFTS (code recycling: the draft costs no extra memory
    beyond transient dequant, agrees with the target wherever rounding
    didn't move the argmax, and a draft step skips the per-step dequant
    the quantized target pays under XLA emulation).  On TPU the roles
    flip — the packed low-bit draft is the cheap one — via
    ``SpeculativeConfig(draft="nxfp4")`` on a bf16 product; same
    machinery, measured here in the regime this container can measure.

    Every k-row asserts the §13 bitwise contract in-bench before
    reporting (greedy speculative streams == the plain engine's), then
    prices: aggregate decode tok/s vs non-spec, acceptance rate, and
    the measured draft-step overhead (t_draft / t_target).  Acceptance
    gate: best k >= 1.3x non-spec aggregate tok/s.
    """
    cfg = SPEC_BENCH_CFG
    n_slots, prompt, chunk = 4, 16, 8
    if _quick():
        n_req, max_new_choices = 4, (8, 16)
    else:
        n_req, max_new_choices = 8, (24, 32, 48)
    max_len = prompt + max(max_new_choices) + 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    rng = np.random.default_rng(0)
    reqs = _workload(cfg, rng, n_req, (prompt,), max_new_choices, 200.0)

    def serve(spec):
        eng = ContinuousEngine(cfg, params, policy, n_slots=n_slots,
                               max_len=max_len, chunk=chunk,
                               speculative=spec)
        eng.serve([Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                           max_new=chunk + 1)])      # warm compile caches
        t0 = time.time()
        results = eng.serve(reqs)
        wall = time.time() - t0
        return eng, {r.uid: r for r in results}, wall

    _, ref, ref_wall = serve(None)
    useful = sum(r.n_generated for r in ref.values())
    base_tok_s = useful / ref_wall

    # the overhead speculation buys its win against: one draft step vs one
    # target step, timed on the same prefilled cache (best-of-5 — greedy
    # decode is deterministic, the spread is host scheduling noise)
    import functools
    from repro.models import prefill as _prefill
    from repro.models.lm import decode_step as _dstep
    probe_eng = ContinuousEngine(cfg, params, policy, n_slots=1,
                                 max_len=max_len, chunk=chunk,
                                 speculative=SpeculativeConfig(k=2))
    _, cache = jax.jit(functools.partial(
        _prefill, cfg, max_len=max_len, kv_fmt="nxfp4"))(
        probe_eng.params, {"tokens": reqs[0].tokens[None]})
    step = jax.jit(functools.partial(_dstep, cfg, kv_fmt="nxfp4"))
    tok = np.zeros((1, 1), np.int32)

    def best_of(params_):
        jax.block_until_ready(step(params_, tok, cache)[0])   # compile
        ts = []
        for _ in range(5):
            t0 = time.time()
            jax.block_until_ready(step(params_, tok, cache)[0])
            ts.append(time.time() - t0)
        return min(ts)

    t_target = best_of(probe_eng.params)
    t_draft = best_of(probe_eng.draft_params)
    overhead = t_draft / t_target

    derived = (f"tok_s={base_tok_s:.0f} n_req={n_req} slots={n_slots} "
               f"target_step_ms={t_target * 1e3:.1f} "
               f"draft_step_ms={t_draft * 1e3:.1f} "
               f"draft_overhead={overhead:.3f}")
    csv.add("serving/speculative/non-spec", 1e6 / base_tok_s, derived,
            unit="us_per_tok")

    best = 0.0
    for k in (2, 4, 8):
        eng, got, wall = serve(SpeculativeConfig(k=k, draft="recycled"))
        for uid, want in ref.items():   # §13: greedy speculative == plain
            if (got[uid].n_generated != want.n_generated or
                    not np.array_equal(got[uid].tokens, want.tokens)):
                raise AssertionError(
                    f"speculative k={k} diverged from plain decode "
                    f"(uid={uid})")
        st = eng.spec_stats()
        tok_s = sum(r.n_generated for r in got.values()) / wall
        speedup = tok_s / base_tok_s
        best = max(best, speedup)
        derived = (f"tok_s={tok_s:.0f} speedup_vs_nonspec={speedup:.2f}x "
                   f"accept_rate={st['accept_rate']:.2f} "
                   f"accepted={st['accepted']} offered={st['offered']} "
                   f"n_req={n_req} slots={n_slots} bit_identical=True")
        csv.add(f"serving/speculative/k{k}", 1e6 / tok_s, derived,
                unit="us_per_tok")
    if best < 1.3:
        raise AssertionError(
            f"speculative decode best speedup {best:.2f}x < 1.3x "
            f"(draft_overhead={overhead:.3f})")


# ---------------------------------------------------------------------------
# continuous traffic (ISSUE-3): Poisson arrivals, mixed lengths
# ---------------------------------------------------------------------------

def _workload(cfg, rng, n_req, prompt_lens, max_new_choices, rate):
    """Poisson arrivals; prompt lengths bucketed (bounds prefill compiles)."""
    reqs, t = [], 0.0
    for i in range(n_req):
        t += float(rng.exponential(1.0 / rate))
        tl = int(rng.choice(prompt_lens))
        reqs.append(Request(
            uid=i, tokens=rng.integers(0, cfg.vocab, (tl,)).astype(np.int32),
            max_new=int(rng.choice(max_new_choices)), arrival_time=t))
    return reqs


def _serve_fixed_batches(cfg, params, policy, reqs, n_slots, max_len,
                         chunk):
    """Fixed-batch baseline: FIFO groups of ``n_slots``, each batch runs to
    its SLOWEST member's max_new (idle finished slots burn compute), the
    next batch waits for the previous to drain. Shorter prompts are
    right-padded to the group max — the same FLOPs a mask-padding fixed
    server spends. Returns (useful_tok_s, ttft_list, wall)."""
    eng = ServeEngine(cfg, params, policy, max_len=max_len)
    groups = [reqs[i:i + n_slots] for i in range(0, len(reqs), n_slots)]
    # warm the compile caches outside the timed region (both serving paths
    # measure steady-state traffic, not compilation)
    for g in groups:
        t_max = max(len(r.tokens) for r in g)
        toks = np.zeros((len(g), t_max), np.int32)
        eng.generate({"tokens": toks}, max_new=chunk, chunk=chunk)
    t0 = time.time()
    ttfts = []
    for g in groups:
        t_max = max(len(r.tokens) for r in g)
        toks = np.zeros((len(g), t_max), np.int32)
        for j, r in enumerate(g):
            toks[j, :len(r.tokens)] = r.tokens
        last_arrival = max(r.arrival_time for r in g)
        now = time.time() - t0
        if now < last_arrival:          # batch can't form until all arrive
            time.sleep(last_arrival - now)
        start = time.time() - t0
        res = eng.generate({"tokens": toks},
                           max_new=max(r.max_new for r in g), chunk=chunk)
        ttfts += [start + res.prefill_seconds - r.arrival_time for r in g]
    wall = time.time() - t0
    useful = sum(r.max_new for r in reqs)
    return useful / wall, ttfts, wall


def _serve_continuous(cfg, params, policy, reqs, n_slots, max_len, chunk):
    eng = ContinuousEngine(cfg, params, policy, n_slots=n_slots,
                           max_len=max_len, chunk=chunk)
    # warm-up: one tiny request per distinct prompt length + the chunk prog
    warm = {len(r.tokens) for r in reqs}
    eng.serve([Request(uid=-1 - i, tokens=np.zeros((t,), np.int32),
                       max_new=1) for i, t in enumerate(sorted(warm))])
    t0 = time.time()
    results = eng.serve(reqs)
    wall = time.time() - t0
    useful = sum(r.n_generated for r in results)
    return useful / wall, [r.ttft for r in results], wall


def run_continuous(csv: Csv):
    cfg = SERVE_CFG
    n_slots = 4
    # heavy-traffic regime: arrivals outpace service so the queue stays
    # deep, and output lengths are high-variance — the workload where
    # lockstep batches idle the most slots waiting for their straggler
    if _quick():
        n_req, chunk = 12, 8
        max_new_choices, rate = (8, 16, 48), 200.0
    else:
        n_req, chunk = 32, 16
        max_new_choices, rate = (16, 32, 64, 128), 200.0
    prompt_lens = (8, 16)
    max_len = max(prompt_lens) + max(max_new_choices) + 8
    rng = np.random.default_rng(0)
    reqs = _workload(cfg, rng, n_req, prompt_lens, max_new_choices, rate)
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")

    fixed_tok_s, fixed_ttft, fixed_wall = _serve_fixed_batches(
        cfg, params, policy, reqs, n_slots, max_len, chunk)
    cont_tok_s, cont_ttft, cont_wall = _serve_continuous(
        cfg, params, policy, reqs, n_slots, max_len, chunk)

    speedup = cont_tok_s / fixed_tok_s
    for label, tok_s, ttft, wall in [
            ("fixed-batch", fixed_tok_s, fixed_ttft, fixed_wall),
            ("continuous", cont_tok_s, cont_ttft, cont_wall)]:
        p50 = float(np.percentile(ttft, 50)) * 1e3
        p99 = float(np.percentile(ttft, 99)) * 1e3
        derived = (f"tok_s={tok_s:.0f} p50_ttft_ms={p50:.1f} "
                   f"p99_ttft_ms={p99:.1f} n_req={n_req} slots={n_slots}")
        if label == "continuous":
            derived += f" speedup_vs_fixed={speedup:.2f}x"
        csv.add(f"serving/continuous/{label}", 1e6 / tok_s, derived,
                unit="us_per_tok")


# ---------------------------------------------------------------------------
# long-prompt traffic (ISSUE-4): chunked-prefill lane vs whole-prompt
# ---------------------------------------------------------------------------

def _serve_engine(cfg, params, policy, reqs, n_slots, max_len, chunk,
                  warm_lens=(8,), **engine_kw):
    eng = ContinuousEngine(cfg, params, policy, n_slots=n_slots,
                           max_len=max_len, chunk=chunk, **engine_kw)
    # warm only the FIXED-shape programs (decode chunk, BOTH lane-chunk
    # variants — a multi-chunk warm prompt compiles the intermediate
    # with_head=False program too) plus the given prefill lengths:
    # unbucketed traffic means whole-prompt admission meets novel
    # lengths mid-serve and pays the compile there — that cost is the
    # regime under test, not harness noise
    if engine_kw.get("prefill_mode") == "chunked":
        warm_lens = tuple(warm_lens) + (engine_kw["p_chunk"] + 8,)
    eng.serve([Request(uid=-1 - i, tokens=np.zeros((t,), np.int32),
                       max_new=1) for i, t in enumerate(warm_lens)])
    t0 = time.time()
    results = eng.serve(reqs)
    wall = time.time() - t0
    useful = sum(r.n_generated for r in results)
    return useful / wall, results, wall


def run_longprompt(csv: Csv):
    """Long-prompt Poisson traffic, UNBUCKETED lengths: whole vs chunked.

    The regime the chunked lane exists for: every admission carries a
    >=256-token prompt whose length the server has never seen.  Whole-
    prompt admission compiles one prefill program PER DISTINCT LENGTH on
    the serving path and stalls every decoding slot for the monolithic
    dispatch; the lane runs one fixed (1, P_CHUNK) program for all of
    them and bounds each stall at one chunk.  p99 TTFT is the headline
    (acceptance: >=1.5x better at equal-or-better aggregate tok/s).
    """
    cfg = SERVE_CFG
    n_slots = 4
    if _quick():
        n_req, chunk, p_chunk = 8, 8, 32
        lo, hi, max_new_choices, rate = 96, 160, (8, 16), 100.0
    else:
        n_req, chunk, p_chunk = 24, 16, 32
        lo, hi, max_new_choices, rate = 256, 384, (16, 32, 64), 100.0
    rng = np.random.default_rng(0)
    reqs, t = [], 0.0
    for i in range(n_req):
        t += float(rng.exponential(1.0 / rate))
        tl = int(rng.integers(lo, hi))          # unbucketed long prompts
        reqs.append(Request(
            uid=i, tokens=rng.integers(0, cfg.vocab, (tl,)).astype(np.int32),
            max_new=int(rng.choice(max_new_choices)), arrival_time=t))
    max_len = hi + max(max_new_choices) + 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")

    whole_tok_s, whole_res, whole_wall = _serve_engine(
        cfg, params, policy, reqs, n_slots, max_len, chunk,
        prefill_mode="whole", warn_compile=False)
    chunk_tok_s, chunk_res, chunk_wall = _serve_engine(
        cfg, params, policy, reqs, n_slots, max_len, chunk,
        prefill_mode="chunked", p_chunk=p_chunk)

    ident = {r.uid: r.tokens for r in whole_res}
    for r in chunk_res:                 # lane correctness rides the bench
        if not np.array_equal(r.tokens, ident[r.uid]):
            raise AssertionError(
                f"chunked prefill diverged from whole (uid={r.uid})")

    whole_p99 = float(np.percentile([r.ttft for r in whole_res], 99))
    chunk_p99 = float(np.percentile([r.ttft for r in chunk_res], 99))
    for label, tok_s, res, wall in [
            ("whole-prefill", whole_tok_s, whole_res, whole_wall),
            ("chunked-prefill", chunk_tok_s, chunk_res, chunk_wall)]:
        ttft = [r.ttft for r in res]
        p50 = float(np.percentile(ttft, 50)) * 1e3
        p99 = float(np.percentile(ttft, 99)) * 1e3
        derived = (f"tok_s={tok_s:.0f} p50_ttft_ms={p50:.1f} "
                   f"p99_ttft_ms={p99:.1f} n_req={n_req} "
                   f"prompts={lo}..{hi} slots={n_slots}")
        if label == "chunked-prefill":
            derived += (f" p_chunk={p_chunk}"
                        f" p99_ttft_improvement={whole_p99 / chunk_p99:.2f}x"
                        f" tok_s_ratio={chunk_tok_s / whole_tok_s:.2f}x"
                        f" bit_identical=True")
        csv.add(f"serving/longprompt/{label}", 1e6 / tok_s, derived,
                unit="us_per_tok")

    # bucketed control: pre-warm BOTH engines on the (two) prompt lengths
    # so no compile lands in the timed region — isolates the pure
    # stall-interleave effect from the fixed-shape no-retrace effect the
    # rows above include (unbucketed traffic is the production regime;
    # this pair says how much of the win survives perfect bucketing)
    bucket = (lo, (lo + hi) // 2)
    breqs = [dataclasses.replace(
        r, tokens=rng.integers(0, cfg.vocab,
                               (bucket[i % 2],)).astype(np.int32))
        for i, r in enumerate(reqs)]
    res_pair = {}
    for label, kw in [("whole-prefill", dict(prefill_mode="whole")),
                      ("chunked-prefill", dict(prefill_mode="chunked",
                                               p_chunk=p_chunk))]:
        tok_s, results, _ = _serve_engine(
            cfg, params, policy, breqs, n_slots, max_len, chunk,
            warm_lens=bucket, warn_compile=False, **kw)
        res_pair[label] = (tok_s, [r.ttft for r in results])
    w_tok, w_ttft = res_pair["whole-prefill"]
    c_tok, c_ttft = res_pair["chunked-prefill"]
    for label, tok_s, ttft in [("whole-prefill", w_tok, w_ttft),
                               ("chunked-prefill", c_tok, c_ttft)]:
        p99 = float(np.percentile(ttft, 99)) * 1e3
        derived = (f"tok_s={tok_s:.0f} p99_ttft_ms={p99:.1f} "
                   f"prompts={bucket} warmed=True")
        if label == "chunked-prefill":
            imp = np.percentile(w_ttft, 99) / np.percentile(c_ttft, 99)
            derived += (f" p99_ttft_improvement={imp:.2f}x"
                        f" tok_s_ratio={c_tok / w_tok:.2f}x")
        csv.add(f"serving/longprompt-bucketed/{label}", 1e6 / tok_s,
                derived, unit="us_per_tok")


def run_admission_policies(csv: Csv):
    """FIFO vs shortest-prompt-first vs TTFT-deadline on MIXED traffic.

    Short interactive prompts share the queue with long batch prompts
    (the workload where FIFO's head-of-line blocking hurts): SPF should
    collapse the SHORT requests' p99 TTFT; the deadline policy sits
    between, spending slack where it exists.  All on the chunked lane.
    """
    cfg = SERVE_CFG
    n_slots = 2
    if _quick():
        n_req, chunk, p_chunk = 10, 8, 32
        long_len, max_new, rate = 128, 8, 100.0
    else:
        n_req, chunk, p_chunk = 20, 8, 32
        long_len, max_new, rate = 320, 16, 100.0
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    max_len = long_len + max_new + 8

    def workload():
        rng = np.random.default_rng(7)
        reqs, t = [], 0.0
        for i in range(n_req):
            t += float(rng.exponential(1.0 / rate))
            tl = 8 if i % 2 else long_len          # half short, half long
            reqs.append(Request(
                uid=i,
                tokens=rng.integers(0, cfg.vocab, (tl,)).astype(np.int32),
                max_new=max_new, arrival_time=t))
        return reqs

    for adm in (FifoPolicy(), ShortestPromptFirst(),
                TtftDeadline(deadline_s=0.2, prefill_s_per_tok=2e-4)):
        reqs = workload()
        tok_s, results, _ = _serve_engine(
            cfg, params, policy, reqs, n_slots, max_len, chunk,
            prefill_mode="chunked", p_chunk=p_chunk, admission_policy=adm)
        # TtftDeadline EXPIRES hopeless requests now (they report inf
        # ttft) — aggregate latency over completed results only, and
        # surface the expiry count so the row stays honest about it
        ok = [r for r in results if r.ok]
        short = [r.ttft for r in ok if len(reqs[r.uid].tokens) == 8]
        ttft = [r.ttft for r in ok]
        derived = (f"tok_s={tok_s:.0f} "
                   f"p99_ttft_ms={np.percentile(ttft, 99) * 1e3:.1f} "
                   f"short_p99_ttft_ms={np.percentile(short, 99) * 1e3:.1f} "
                   f"n_req={n_req} n_ok={len(ok)} slots={n_slots}")
        csv.add(f"serving/admission/{adm.name}", 1e6 / tok_s, derived,
                unit="us_per_tok")


# ---------------------------------------------------------------------------
# fault tolerance (ISSUE-6): seeded chaos + overload shedding
# ---------------------------------------------------------------------------

def run_faults(csv: Csv):
    """Seeded fault injection rides the bench: one serve per fault class.

    A fault-free reference serve pins the expected token streams; each
    fault class (nan logits, KV bit-flip, delay) then replays the SAME
    workload with one seeded fault at chunk 2 and the row asserts the
    ISSUE-6 containment contract before reporting: the victim finishes
    FAILED with a prefix of its reference stream, every healthy request
    stays bit-identical, and a pure-latency fault corrupts nothing.
    Goodput counts completed-OK tokens only — the quantity a shedding/
    quarantine policy is supposed to protect.
    """
    cfg = SERVE_CFG
    n_slots, chunk, prompt = 2, 4, 8
    n_req, max_new = 4, (12 if _quick() else 24)
    max_len = prompt + max_new + 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    rng = np.random.default_rng(3)
    toks = [rng.integers(0, cfg.vocab, (prompt,)).astype(np.int32)
            for _ in range(n_req)]

    def serve(plan):
        # fresh engine per scenario: a KV-flip mutates device state, and
        # the containment claim is about one serve, not engine reuse
        # (compiled programs are shared across engines, so this is cheap)
        eng = ContinuousEngine(cfg, params, policy, n_slots=n_slots,
                               max_len=max_len, chunk=chunk,
                               kv_integrity=True)
        eng.serve([Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                           max_new=1)])
        t0 = time.time()
        results = eng.serve(
            [Request(uid=i, tokens=toks[i], max_new=max_new)
             for i in range(n_req)], fault_plan=plan)
        return {r.uid: r for r in results}, time.time() - t0

    ref, _ = serve(None)
    scenarios = {
        "nan_logits": Fault(kind="nan_logits", chunk=2, uid=1),
        "kv_flip": Fault(kind="kv_flip", chunk=2, uid=1),
        "delay": Fault(kind="delay", chunk=2, seconds=0.05),
    }
    for kind, fault in scenarios.items():
        res, wall = serve(FaultPlan(faults=(fault,), seed=7))
        for uid, r in res.items():
            want = ref[uid].tokens
            if kind != "delay" and uid == fault.uid:
                if r.status != Status.FAILED:
                    raise AssertionError(
                        f"{kind}: victim uid={uid} not FAILED ({r.status})")
                if not np.array_equal(r.tokens, want[:len(r.tokens)]):
                    raise AssertionError(
                        f"{kind}: victim partial is not a prefix of the "
                        f"fault-free stream (uid={uid})")
            else:
                if r.status != Status.OK or not np.array_equal(r.tokens,
                                                               want):
                    raise AssertionError(
                        f"{kind}: healthy uid={uid} perturbed "
                        f"(status={r.status})")
        good = sum(r.n_generated for r in res.values() if r.ok)
        n_failed = sum(1 for r in res.values()
                       if r.status == Status.FAILED)
        derived = (f"goodput_tok_s={good / wall:.0f} n_failed={n_failed} "
                   f"n_req={n_req} contained=True")
        csv.add(f"serving/faults/{kind}", wall / max(good, 1) * 1e6,
                derived, unit="us_per_tok")


def run_overload(csv: Csv):
    """Burst overload against a bounded queue: one row per shedding policy.

    The whole burst lands before the first chunk completes, so the
    backlog is maximal and the ``max_queue`` bound must bite.  Each row
    reports goodput (completed-OK tok/s), shed rate, deadline-hit rate
    and the degraded count — the observable envelope ISSUE-6 asks for:
    overload degrades *boundedly* (reject-new / drop-oldest hold the
    queue at the bound; degrade serves everyone at a capped budget)
    instead of growing latency without limit.
    """
    cfg = SERVE_CFG
    n_slots, chunk, prompt = 2, 4, 8
    max_queue, max_new = 2, 16
    n_req = 8 if _quick() else 12
    max_len = prompt + max_new + 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    rng = np.random.default_rng(11)
    toks = [rng.integers(0, cfg.vocab, (prompt,)).astype(np.int32)
            for _ in range(n_req)]

    for shed in (RejectNew(), DropOldest(),
                 DegradeOverBudget(max_new_cap=4)):
        eng = ContinuousEngine(cfg, params, policy, n_slots=n_slots,
                               max_len=max_len, chunk=chunk,
                               max_queue=max_queue, shedding=shed)
        eng.serve([Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                           max_new=1)])
        t0 = time.time()
        results = eng.serve(
            [Request(uid=i, tokens=toks[i], max_new=max_new,
                     arrival_time=i * 1e-4, deadline_s=30.0)
             for i in range(n_req)])
        wall = time.time() - t0
        ok = [r for r in results if r.ok]
        n_shed = sum(1 for r in results if r.status == Status.SHED)
        n_deg = sum(1 for r in results if r.degraded and r.ok)
        goodput = sum(r.n_generated for r in ok) / wall
        derived = (f"goodput_tok_s={goodput:.0f} "
                   f"shed_rate={n_shed / n_req:.2f} "
                   f"deadline_hit_rate={len(ok) / n_req:.2f} "
                   f"degraded={n_deg} n_req={n_req} "
                   f"max_queue={max_queue} slots={n_slots}")
        csv.add(f"serving/overload/{shed.name}", 1e6 / max(goodput, 1e-9),
                derived, unit="us_per_tok")


# ---------------------------------------------------------------------------
# preempt/resume (ISSUE-7): interactive-overtakes-batch, priced
# ---------------------------------------------------------------------------

def run_preemption(csv: Csv):
    """Priority preemption vs wait-your-turn on the same workload.

    Two batch requests occupy both slots when a high-priority interactive
    request arrives.  Per-chunk delay faults pin the batch chunk cadence
    (the tiny CPU model would otherwise drain a batch slot in
    milliseconds and nothing would ever need to yield).  Without a
    preemption policy the interactive request waits for a batch slot to
    finish; with ``PriorityPreemption`` the lowest-priority slot suspends
    to a snapshot and yields at the next chunk boundary.  The row asserts
    the DESIGN.md §12 contract before reporting: preempt + resume events
    fired, the interactive request finished before its victim, and every
    stream — victim included — is bit-identical to the no-preemption run
    (preemption costs a pause, never lost work).
    """
    cfg = SERVE_CFG
    n_slots, chunk, prompt = 2, 4, 8
    batch_new = 12 if _quick() else 24
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    rng = np.random.default_rng(5)
    toks = [rng.integers(0, cfg.vocab, (prompt,)).astype(np.int32)
            for _ in range(3)]

    def mk():
        return [Request(uid=0, tokens=toks[0], max_new=batch_new,
                        priority=0),
                Request(uid=1, tokens=toks[1], max_new=batch_new,
                        priority=0),
                Request(uid=2, tokens=toks[2], max_new=4,
                        priority=5, arrival_time=0.01)]

    plan = FaultPlan(faults=tuple(
        Fault(kind="delay", chunk=k, seconds=0.02)
        for k in range(batch_new // chunk)))

    msgs = []
    handler = logging.Handler()
    handler.emit = lambda rec: msgs.append(rec.getMessage())
    log = logging.getLogger("repro.serving")
    log.addHandler(handler)
    old_level = log.level
    log.setLevel(logging.INFO)
    runs = {}
    try:
        for label, preempt in [("no-preempt", None),
                               ("priority-preempt", PriorityPreemption())]:
            eng = ContinuousEngine(
                cfg, params, policy, n_slots=n_slots,
                max_len=prompt + batch_new + 8, chunk=chunk,
                admission_policy=PriorityAdmission(), preemption=preempt)
            # warm prefill/decode AND the snapshot extract/restore pair (a
            # suspend compiles both) so no jit lands in the timed serve
            warm = {"n": 0}

            def warm_cb(engine, sched):
                if warm["n"] == 0:
                    engine.suspend(-1)
                warm["n"] += 1

            eng.serve([Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                               max_new=2 * chunk)], progress_cb=warm_cb)
            msgs.clear()
            t0 = time.time()
            results = eng.serve(mk(), fault_plan=plan)
            wall = time.time() - t0
            events = [e for e in (parse_event(m) for m in msgs) if e]
            runs[label] = ({r.uid: r for r in results}, wall, events)
    finally:
        log.removeHandler(handler)
        log.setLevel(old_level)

    ref, _, _ = runs["no-preempt"]
    got, _, events = runs["priority-preempt"]
    kinds = [e["event"] for e in events]
    if "preempt" not in kinds or "resume" not in kinds:
        raise AssertionError(f"no preemption occurred: {kinds}")
    victim = next(e["uid"] for e in events if e["event"] == "preempt")
    order = [e["uid"] for e in events if e["event"] == "finish"]
    if order.index(2) >= order.index(victim):
        raise AssertionError(
            f"interactive request did not overtake victim {victim}: {order}")
    for uid, want in ref.items():
        r = got[uid]
        if r.status != Status.OK or not np.array_equal(r.tokens, want.tokens):
            raise AssertionError(
                f"preemption perturbed uid={uid} (status={r.status})")

    ref_ttft = ref[2].ttft
    for label, (res, wall, evs) in runs.items():
        toks_out = sum(r.n_generated for r in res.values())
        ttft_ms = res[2].ttft * 1e3
        derived = (f"tok_s={toks_out / wall:.0f} "
                   f"interactive_ttft_ms={ttft_ms:.1f} slots={n_slots}")
        if label == "priority-preempt":
            n_pre = sum(1 for e in evs if e["event"] == "preempt")
            derived += (f" ttft_improvement={ref_ttft / res[2].ttft:.2f}x"
                        f" n_preempted={n_pre} bit_identical=True")
        csv.add(f"serving/preemption/{label}", 1e6 / (toks_out / wall),
                derived, unit="us_per_tok")


def run_p_chunk_auto(csv: Csv):
    """The p_chunk="auto" warmup sweep, reported as rows.

    One row per candidate (measured lane-chunk dispatch time) plus the
    decode-chunk stall unit and the chosen value — the backend-specific
    tradeoff ROADMAP wants re-measured on TPU, captured per run.
    """
    cfg = SERVE_CFG
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    cands = (8, 16) if _quick() else (8, 16, 32, 64)
    eng = ContinuousEngine(cfg, params, policy, n_slots=4, max_len=256,
                           chunk=16, prefill_mode="chunked",
                           p_chunk="auto", p_chunk_candidates=cands)
    for p, s in eng.p_chunk_sweep.items():
        derived = (f"lane_tok_s={p / s:.0f}"
                   f"{' chosen=True' if p == eng.p_chunk else ''}")
        csv.add(f"serving/p_chunk_auto/{p}", s * 1e6, derived,
                unit="us_per_chunk")


# ---------------------------------------------------------------------------
# sharded continuous serving (ISSUE-5): slot axis over a 'data' mesh
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import json, sys, time
import numpy as np
import jax
from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.models.common import ModelConfig
from repro.serving import ContinuousEngine, Request
from repro.serving.sharded import ShardedContinuousEngine
from repro.launch.mesh import make_serving_mesh

quick, n_slots, chunk, p_chunk = json.loads(sys.argv[1])
cfg = ModelConfig(name="serve-lm", family="dense", n_layers=1, d_model=64,
                  n_heads=1, n_kv_heads=1, d_ff=256, vocab=256, remat=False)
n_req = 12 if quick else 32
max_new_choices = (8, 16, 48) if quick else (16, 32, 64, 128)
prompt_lens, rate = (8, 16), 200.0
max_len = max(prompt_lens) + max(max_new_choices) + 8
rng = np.random.default_rng(0)
reqs, t = [], 0.0
for i in range(n_req):
    t += float(rng.exponential(1.0 / rate))
    tl = int(rng.choice(prompt_lens))
    reqs.append(dict(uid=i,
                     tokens=rng.integers(0, cfg.vocab, (tl,))
                     .astype(np.int32),
                     max_new=int(rng.choice(max_new_choices)),
                     arrival_time=t))
params = init_params(cfg, jax.random.PRNGKey(0))
policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")

def serve(shards):
    kw = dict(n_slots=n_slots, max_len=max_len, chunk=chunk,
              prefill_mode="chunked", p_chunk=p_chunk)
    if shards == 1:
        eng = ContinuousEngine(cfg, params, policy, **kw)
    else:
        eng = ShardedContinuousEngine(cfg, params, policy,
                                      make_serving_mesh(shards), **kw)
    # warm the fixed-shape programs (decode chunk + both lane variants)
    eng.serve([Request(uid=-1, tokens=np.zeros((p_chunk + 8,), np.int32),
                       max_new=1)])
    t0 = time.time()
    results = eng.serve([Request(**r) for r in reqs])
    wall = time.time() - t0
    return results, wall

ref = None
for shards in (1, 2, 4):
    results, wall = serve(shards)
    toks = {r.uid: r.tokens for r in results}
    if ref is None:
        ref = toks
    else:       # the sharded mesh must not perturb a single token
        for uid, want in ref.items():
            if not np.array_equal(toks[uid], want):
                raise AssertionError(
                    f"sharded ({shards}) diverged from unsharded "
                    f"(uid={uid})")
    useful = sum(r.n_generated for r in results)
    ttft = [r.ttft for r in results]
    print("ROW " + json.dumps({
        "shards": shards, "tok_s": useful / wall,
        "p50_ttft_ms": float(np.percentile(ttft, 50)) * 1e3,
        "p99_ttft_ms": float(np.percentile(ttft, 99)) * 1e3,
        "n_req": n_req, "slots": n_slots}))
print("SHARDED_BENCH_OK")
"""


def _run_host_devices(script: str, n_devices: int, tag: str,
                      *argv: str) -> str:
    """Run a multi-device bench script in a child on ``n_devices`` forced
    host CPU devices; returns its stdout (which must print ``tag``).

    The child is pinned to the CPU: a chip belongs to one process, and
    this one already holds JAX.  On a TPU backend the host-device
    simulation measures nothing the chip would, so it is refused — the
    sharded engine runs in-process there (``chip_smoke.py --chips 4``).
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{tag}: this bench simulates {n_devices} devices on the host "
            f"CPU in a child process; on a TPU run the sharded engine "
            f"in-process on the real devices (chip_smoke.py --chips 4)")
    # APPEND the forced-device flag: the child rows must run under the
    # same compiler flags as every other row in the summary
    flags = (os.environ.get("XLA_FLAGS", "")
             + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env = {**os.environ, "XLA_FLAGS": flags, "PYTHONPATH": "src",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", script, *argv],
        env=env, capture_output=True, text=True, timeout=1200,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if tag not in out.stdout:
        raise AssertionError(f"{tag}: bench subprocess failed:\n"
                             f"{out.stdout}\n{out.stderr}")
    return out.stdout


def run_sharded(csv: Csv):
    """Slot-sharded vs unsharded continuous serving, 1/2/4 shards.

    Runs in a subprocess with 4 forced host CPU devices (this process
    must keep one device).  The script re-serves the SAME Poisson mixed-length
    workload at each shard count and raises if any sharded token stream
    diverges from the unsharded engine — the sharded bitwise oracle rides
    the bench exactly like the chunked-prefill one does.

    CPU caveat (same spirit as DESIGN.md §9): the forced host devices
    serialize onto one machine, so shard counts cannot show wall-clock
    SCALING here — these rows price the shard_map dispatch overhead and
    pin the oracle; the S-way throughput claim is a TPU measurement
    (DESIGN.md §10).
    """
    quick = _quick()
    n_slots, chunk, p_chunk = 4, (8 if quick else 16), 8
    out = _run_host_devices(_SHARDED_SCRIPT, 4, "SHARDED_BENCH_OK",
                            json.dumps([quick, n_slots, chunk, p_chunk]))
    for line in out.splitlines():
        if not line.startswith("ROW "):
            continue
        row = json.loads(line[4:])
        derived = (f"tok_s={row['tok_s']:.0f} "
                   f"p50_ttft_ms={row['p50_ttft_ms']:.1f} "
                   f"p99_ttft_ms={row['p99_ttft_ms']:.1f} "
                   f"n_req={row['n_req']} slots={row['slots']} "
                   f"p_chunk={p_chunk} bit_identical=True")
        csv.add(f"serving/sharded/{row['shards']}shard",
                1e6 / row["tok_s"], derived, unit="us_per_tok")


# ---------------------------------------------------------------------------
# shard drain / live migration (ISSUE-7): shard_down vs healthy serving
# ---------------------------------------------------------------------------

_DRAIN_SCRIPT = r"""
import json, logging, sys, time
import numpy as np
import jax
from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.models.common import ModelConfig
from repro.serving import (ContinuousEngine, Fault, FaultPlan, Request,
                           parse_event)
from repro.serving.sharded import ShardedContinuousEngine
from repro.launch.mesh import make_serving_mesh

cfg = ModelConfig(name="serve-lm", family="dense", n_layers=1, d_model=64,
                  n_heads=1, n_kv_heads=1, d_ff=256, vocab=256, remat=False)
n_slots, chunk, prompt, victim = 8, 4, 8, 1
max_news = [16, 18, 12, 14, 16, 10]
max_len = prompt + max(max_news) + 8
params = init_params(cfg, jax.random.PRNGKey(0))
policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
kw = dict(n_slots=n_slots, max_len=max_len, chunk=chunk,
          prefill_mode="whole")

msgs = []
h = logging.Handler()
h.emit = lambda rec: msgs.append(rec.getMessage())
log = logging.getLogger("repro.serving")
log.addHandler(h)
log.setLevel(logging.INFO)

def mk():
    rng = np.random.default_rng(0)
    return [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab, (prompt,))
                    .astype(np.int32),
                    max_new=m, arrival_time=0.0 if i < 4 else 0.02)
            for i, m in enumerate(max_news)]

def serve_sharded(plan=None):
    eng = ShardedContinuousEngine(cfg, params, policy,
                                  make_serving_mesh(2), **kw)
    eng.serve([Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                       max_new=chunk)])
    msgs.clear()
    t0 = time.time()
    results = eng.serve(mk(), fault_plan=plan)
    wall = time.time() - t0
    evs = [e for e in (parse_event(m) for m in msgs) if e]
    return {r.uid: r for r in results}, wall, evs

ref = {r.uid: r.tokens for r in ContinuousEngine(
    cfg, params, policy, **kw).serve(mk())}
plan = FaultPlan(faults=(Fault(kind="shard_down", chunk=1, shard=victim),))
healthy, wall_h, _ = serve_sharded()
serve_sharded(plan)       # warm the migration snapshot/restore programs
drained, wall_d, evs = serve_sharded(plan)

for label, got in [("no-drain", healthy), ("shard-down", drained)]:
    for uid, want in ref.items():
        assert got[uid].status == "OK", (label, uid, got[uid].status)
        if not np.array_equal(got[uid].tokens, want):
            raise AssertionError(
                f"{label}: uid={uid} diverged from unsharded run")
kinds = [e["event"] for e in evs]
assert "drain" in kinds and "migrate" in kinds, kinds
assert any(e["event"] == "fault" and e["kind"] == "shard_down"
           for e in evs)
di = next(i for i, e in enumerate(evs) if e["event"] == "drain")
for e in evs[di + 1:]:
    if e["event"] in ("admit", "prefill-start"):
        assert e.get("shard") != victim, e
n_mig = sum(1 for e in evs if e["event"] == "migrate")
for label, got, wall in [("no-drain", healthy, wall_h),
                         ("shard-down", drained, wall_d)]:
    useful = sum(r.n_generated for r in got.values())
    row = {"label": label, "tok_s": useful / wall,
           "n_req": len(max_news), "slots": n_slots}
    if label == "shard-down":
        row["n_migrated"] = n_mig
        row["overhead"] = wall_d / wall_h
    print("ROW " + json.dumps(row))
print("DRAIN_BENCH_OK")
"""


def run_drain(csv: Csv):
    """Live shard drain under a 2-shard mesh, vs the same traffic healthy.

    A ``shard_down`` fault at chunk 1 drains shard 1 mid-serve: its live
    DECODING slots snapshot and migrate onto free healthy slots and the
    scheduler stops routing to it.  The subprocess (2 forced host
    devices) asserts the full §12 contract before any row is written —
    every stream including the migrated ones bit-identical to the
    UNSHARDED no-fault run, drain + migrate events journaled, zero
    admissions to the drained shard afterward.  The shard-down row
    prices the migration pause against the healthy run; same CPU caveat
    as ``run_sharded`` (overheads are real, scaling is not).
    """
    out = _run_host_devices(_DRAIN_SCRIPT, 2, "DRAIN_BENCH_OK")
    for line in out.splitlines():
        if not line.startswith("ROW "):
            continue
        row = json.loads(line[4:])
        derived = (f"tok_s={row['tok_s']:.0f} n_req={row['n_req']} "
                   f"slots={row['slots']} shards=2")
        if row["label"] == "shard-down":
            derived += (f" n_migrated={row['n_migrated']}"
                        f" drain_overhead={row['overhead']:.2f}x"
                        f" bit_identical=True")
        csv.add(f"serving/drain/{row['label']}", 1e6 / row["tok_s"],
                derived, unit="us_per_tok")


# ---------------------------------------------------------------------------
# paged KV cache (ISSUE-9): concurrency at a fixed KV HBM budget
# ---------------------------------------------------------------------------

def _kv_bytes(cache):
    """(dense_rows, pool, block_table) byte split of a cache's KV leaves."""
    dense = pool = table = 0

    def tally(name, leaf):
        nonlocal dense, pool, table
        if name == "block":
            table += leaf.nbytes
        elif name.startswith("pool_"):
            pool += leaf.nbytes
        else:
            dense += leaf.nbytes

    for name, v in cache["layers"].items():
        if isinstance(v, dict):          # grouped layers (hybrid families)
            for leaf_name, leaf in v.items():
                tally(leaf_name, leaf)
        else:
            tally(name, v)
    return dense, pool, table


def run_paged(csv: Csv):
    """Paged vs fixed-slot serving at the SAME KV memory budget.

    The ISSUE-9 tentpole measurement.  The dense engine preallocates
    ``n_slots * max_len`` KV rows whether requests use them or not, so
    its concurrency is slot-bound long before it is memory-bound; the
    paged engine backs the same row budget with a page pool and admits
    on actual page demand.  Two workloads, both bitwise-asserted against
    the dense engine before any row lands:

    - ``uniform``: independent short requests (2 pages each) — the pool
      backs >=2x the dense engine's concurrent in-flight requests.
    - ``shared-prefix``: every prompt extends one registered 4-page
      prefix, so a claimant costs ONE fresh page — >=4x concurrency.

    The footprint gate rides the bench: the pool's KV leaves must not
    exceed the dense engine's (the block table is the only overhead,
    reported per row).  Same CPU caveat as the other scenarios —
    concurrency and footprint are structural wins (they transfer to TPU
    directly); wall-clock tok/s here prices host dispatch, not HBM.
    """
    from repro.serving import PagedContinuousEngine

    cfg = SERVE_CFG
    dense_slots, max_len, chunk, page_size = 4, 128, 4, 16
    budget_rows = dense_slots * max_len              # the fixed KV budget
    n_pages = budget_rows // page_size               # incl. the null page
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
    rng = np.random.default_rng(9)

    def uniform_reqs():
        n = 12 if _quick() else 16
        return [Request(uid=i,
                        tokens=rng.integers(0, cfg.vocab, (16,))
                        .astype(np.int32),
                        max_new=16) for i in range(n)]

    def shared_reqs():
        # the 4x gate needs >= 4 * dense_slots CONCURRENT claimants, so
        # this workload does not shrink under NXFP_BENCH_QUICK
        n = 20
        prefix = rng.integers(0, cfg.vocab, (64,)).astype(np.int32)
        reqs = [Request(uid=0, tokens=prefix.copy(), max_new=4)]
        for i in range(1, n + 1):       # claimants arrive once registered
            tail = rng.integers(0, cfg.vocab, (4,)).astype(np.int32)
            reqs.append(Request(uid=i, tokens=np.concatenate([prefix, tail]),
                                max_new=12, arrival_time=0.05))
        return reqs

    def serve(eng, reqs):
        eng.serve([Request(uid=-1 - i, tokens=np.zeros((t,), np.int32),
                           max_new=1)
                   for i, t in enumerate(sorted({len(r.tokens)
                                                 for r in reqs}))])
        peak = {"v": 0}

        def cb(engine, sched):
            peak["v"] = max(peak["v"], len(sched.active))

        t0 = time.time()
        results = eng.serve(reqs, progress_cb=cb)
        wall = time.time() - t0
        return {r.uid: r for r in results}, wall, peak["v"]

    for scenario, mk, mult in [("uniform", uniform_reqs, 2),
                               ("shared-prefix", shared_reqs, 4)]:
        reqs = mk()
        dense_eng = ContinuousEngine(cfg, params, policy,
                                     n_slots=dense_slots, max_len=max_len,
                                     chunk=chunk)
        ref, d_wall, d_peak = serve(dense_eng, reqs)
        # same row budget, 3-5x the slots: pages, not slots, gate admission
        paged_eng = PagedContinuousEngine(
            cfg, params, policy, n_slots=len(reqs) + 1, max_len=max_len,
            chunk=chunk, page_size=page_size, n_pages=n_pages,
            prefix_sharing=(scenario == "shared-prefix"))
        got, p_wall, p_peak = serve(paged_eng, reqs)
        for uid, want in ref.items():    # §14: paged == dense, bitwise
            if not np.array_equal(got[uid].tokens, want.tokens):
                raise AssertionError(
                    f"paged ({scenario}) diverged from dense (uid={uid})")
        d_bytes, _, _ = _kv_bytes(dense_eng.cache)
        _, p_bytes, t_bytes = _kv_bytes(paged_eng.cache)
        if p_bytes > d_bytes:            # the footprint gate
            raise AssertionError(
                f"paged pool KV bytes {p_bytes} exceed the dense budget "
                f"{d_bytes} ({scenario})")
        if p_peak < mult * d_peak:       # the concurrency gate
            raise AssertionError(
                f"paged in-flight peak {p_peak} < {mult}x dense peak "
                f"{d_peak} at the same KV budget ({scenario})")
        st = paged_eng.pool_stats()[0]
        paged_eng.pool.assert_empty()
        for label, res, wall, peak in [("dense-slots", ref, d_wall, d_peak),
                                       ("paged", got, p_wall, p_peak)]:
            tok_s = sum(r.n_generated for r in res.values()) / wall
            derived = (f"tok_s={tok_s:.0f} peak_in_flight={peak} "
                       f"n_req={len(reqs)} kv_budget_rows={budget_rows}")
            if label == "paged":
                derived += (f" concurrency_x={p_peak / max(d_peak, 1):.1f}x"
                            f" pool_kv_bytes={p_bytes}"
                            f" dense_kv_bytes={d_bytes}"
                            f" table_bytes={t_bytes}"
                            f" page_hwm={st['high_watermark']}"
                            f" prefix_hits={st['prefix_hits']}"
                            f" bit_identical=True")
            csv.add(f"serving/paged/{scenario}/{label}", 1e6 / tok_s,
                    derived, unit="us_per_tok")


# ---------------------------------------------------------------------------
# quantized x quantized prefill (ISSUE-10): recycled-weight TTFT + tiers
# ---------------------------------------------------------------------------

def run_prefill_qq(csv: Csv):
    """Quantized-activation prefill vs dense-activation prefill on the
    SAME NxFP4 product — long prompts through the chunked lane.

    The §15 XLA mechanics under test: the dense-act baseline prefills
    bf16 x dequant(W), re-dequantizing the packed weights inside EVERY
    lane-chunk dispatch (per GEMM per layer); the quantized-act tier
    prefills against its recycled dense weights — ONE dequant at engine
    build, amortized over every admission — so long-prompt TTFT prices
    exactly the per-chunk dequant the recycling removes.  Gate: >=1.3x
    mean TTFT on this dequant-dominated config.  Asserted in-bench
    before any row lands: the quantized-act serve is deterministic
    (two serves, identical bytes), and the act_fmt prefill logits stay
    within the documented §15 bound of the dense-act logits.
    """
    from repro.models import prefill as _prefill
    cfg = SPEC_BENCH_CFG
    n_slots, chunk = 2, 4
    if _quick():
        n_req, prompt, p_chunk, max_new = 4, 160, 8, 4
    else:
        n_req, prompt, p_chunk, max_new = 6, 320, 16, 4
    max_len = prompt + max_new + 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        (prompt,)).astype(np.int32),
                    max_new=max_new, arrival_time=0.0)
            for i in range(n_req)]
    kw = dict(n_slots=n_slots, max_len=max_len, chunk=chunk,
              prefill_mode="chunked", p_chunk=p_chunk, warn_compile=False)
    base = ContinuousEngine(cfg, params, QuantPolicy("nxfp4", "nxfp4"),
                            **kw)
    qq = TieredContinuousEngine(
        cfg, params, {"economy": TierSpec("nxfp4", "nxfp4", "amxfp4")},
        **kw)
    warm = [Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                    max_new=1)]
    for eng in (base, qq):
        eng.serve(warm)

    # §15 error bound: act_fmt logits vs dense-act logits, same weights
    probe = {"tokens": reqs[0].tokens[None]}
    ref, _ = _prefill(cfg, params, probe, max_len, None)
    got, _ = _prefill(cfg, params, probe, max_len, None, act_fmt="amxfp4")
    ref32 = np.asarray(ref, np.float32)
    rel = float(np.abs(np.asarray(got, np.float32) - ref32).max()
                / (np.abs(ref32).max() + 1e-9))
    # the §15 budget: per-GEMM direct-cast error is <=0.25 of each
    # block's max, so scale-normalized logit error stays under one
    # 4-bit quantum of the logit scale (measured ~0.19 on this config)
    if rel > 0.25:
        raise AssertionError(
            f"amxfp4 prefill logits off dense-act by {rel:.3f} (>0.25)")

    t0 = time.time()
    res_b = base.serve(reqs)
    wall_b = time.time() - t0
    t0 = time.time()
    res_q = qq.serve(reqs)
    wall_q = time.time() - t0
    res_q2 = qq.serve(reqs)            # determinism: same bytes twice
    tok_q = {r.uid: r.tokens for r in res_q}
    for r in res_q2:
        if not np.array_equal(r.tokens, tok_q[r.uid]):
            raise AssertionError(
                f"quantized-act serve is nondeterministic (uid={r.uid})")

    ttft_b = float(np.mean([r.ttft for r in res_b]))
    ttft_q = float(np.mean([r.ttft for r in res_q]))
    ratio = ttft_b / ttft_q
    for label, res, wall, ttft in [("dense-act", res_b, wall_b, ttft_b),
                                   ("quantized-act", res_q, wall_q,
                                    ttft_q)]:
        tok_s = sum(r.n_generated for r in res) / wall
        derived = (f"mean_ttft_ms={ttft * 1e3:.1f} tok_s={tok_s:.0f} "
                   f"prompt={prompt} p_chunk={p_chunk} n_req={n_req} "
                   f"slots={n_slots} weights=nxfp4")
        if label == "quantized-act":
            derived += (f" act_fmt=amxfp4 ttft_speedup={ratio:.2f}x "
                        f"logit_rel_err={rel:.4f} deterministic=True")
        csv.add(f"serving/prefill_qq/{label}", ttft * 1e6, derived,
                unit="us_ttft")
    if ratio < 1.3:
        raise AssertionError(
            f"quantized-act prefill TTFT speedup {ratio:.2f}x < 1.3x")


def run_tiers(csv: Csv):
    """Per-slot serving tiers (§15): mixed premium/standard/economy
    traffic on ONE engine, plus the degraded-KV rung.

    Asserted in-bench: the premium rider's streams are bit-identical to
    a plain dense engine serving the same workload (the dense tier IS
    the pre-tier engine), and under a forced pool watermark the degrade
    sweep repacks resident KV mid-decode with every request finishing OK
    and flagged degraded.
    """
    cfg = SPEC_BENCH_CFG
    n_slots, chunk, prompt = 3, 4, 32
    n_req = 6 if _quick() else 9
    max_new = 16
    max_len = prompt + max_new + 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    names = ["premium", "standard", "economy"]
    reqs = [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        (prompt,)).astype(np.int32),
                    max_new=max_new, arrival_time=0.0, tier=names[i % 3])
            for i in range(n_req)]
    eng = TieredContinuousEngine(cfg, params, default_tiers(),
                                 default_tier="standard",
                                 n_slots=n_slots, max_len=max_len,
                                 chunk=chunk, warn_compile=False)
    eng.serve([Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                       max_new=1, tier=t) for t in names])
    t0 = time.time()
    results = eng.serve(reqs)
    wall = time.time() - t0

    dense = ContinuousEngine(cfg, params, QuantPolicy(None, None),
                             n_slots=n_slots, max_len=max_len, chunk=chunk,
                             warn_compile=False)
    dense.serve([Request(uid=-1, tokens=np.zeros((prompt,), np.int32),
                         max_new=1)])
    ref = {r.uid: r.tokens for r in dense.serve(reqs)}
    for r in results:
        if r.uid % 3 == 0 and not np.array_equal(r.tokens, ref[r.uid]):
            raise AssertionError(
                f"premium tier diverged from the dense engine "
                f"(uid={r.uid})")
    by_tier = {t: [r for r in results if r.uid % 3 == i]
               for i, t in enumerate(names)}
    tok_s = sum(r.n_generated for r in results) / wall
    for t in names:
        ttft = float(np.mean([r.ttft for r in by_tier[t]])) * 1e3
        spec = eng.tiers[t]
        derived = (f"mean_ttft_ms={ttft:.1f} n_req={len(by_tier[t])} "
                   f"weight_fmt={spec.weight_fmt} kv_fmt={spec.kv_fmt} "
                   f"act_fmt={spec.act_fmt} agg_tok_s={tok_s:.0f}")
        if t == "premium":
            derived += " bit_identical_vs_dense=True"
        csv.add(f"serving/tiers/{t}", 1e6 / tok_s, derived,
                unit="us_per_tok")

    # degraded-KV rung: forced watermark repacks resident premium KV
    records = []

    class _Cap(logging.Handler):
        def emit(self, rec):
            e = parse_event(rec.getMessage())
            if e:
                records.append(e)

    h = _Cap()
    log = logging.getLogger("repro.serving.scheduler")
    log.addHandler(h)
    old = log.level
    log.setLevel(logging.INFO)
    try:
        deng = TieredContinuousEngine(
            cfg, params,
            {"premium": TierSpec(None, None, None),
             "cheap": TierSpec(None, "nxfp4", None)},
            default_tier="premium", degrade_kv_to="cheap",
            shedding=DegradeOverBudget(max_new_cap=None,
                                       pool_watermark=0.05),
            n_slots=2, max_len=max_len, chunk=chunk, warn_compile=False)
        dres = deng.serve([dataclasses.replace(r, tier=None)
                           for r in reqs[:4]])
    finally:
        log.removeHandler(h)
        log.setLevel(old)
    repacks = [e for e in records if e.get("event") == "kv-repack"]
    n_deg = sum(1 for r in dres if r.degraded)
    if not repacks or not all(r.ok for r in dres):
        raise AssertionError(
            f"degrade rung: {len(repacks)} repacks, "
            f"statuses={[r.status for r in dres]}")
    csv.add("serving/tiers/degrade-kv", 0.0,
            f"repacks={len(repacks)} degraded={n_deg} "
            f"n_req={len(dres)} watermark=0.05 dst=nxfp4 all_ok=True",
            unit="count")


def run(csv: Csv):
    run_loops(csv)
    run_paged(csv)
    run_speculative(csv)
    run_continuous(csv)
    run_longprompt(csv)
    run_prefill_qq(csv)
    run_tiers(csv)
    run_admission_policies(csv)
    run_faults(csv)
    run_overload(csv)
    run_preemption(csv)
    run_p_chunk_auto(csv)
    run_sharded(csv)
    run_drain(csv)


def main():
    csv = Csv()
    run(csv)
    return csv


if __name__ == "__main__":
    main()
