"""The serve loop's own measurement (``serving.events.Loop``): one
``iteration`` record per loop iteration with its phase times and counters,
nothing built above INFO, the journal's sequence untouched, the compile
counter, and the named programs and ``serve.*`` spans in a profiler trace.
"""
import dataclasses
import json
import logging

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.serving import ContinuousEngine, Request, events, parse_event, \
    replay
from repro.serving.events import COUNTERS, PHASES

NO_QUANT = QuantPolicy(weight_fmt=None, kv_fmt=None)


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3_8b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def danube():
    cfg = get_smoke_config("h2o_danube_3_4b")      # sliding_window=32
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _req(cfg, uid, t, max_new, arrival=0.0):
    toks = np.random.default_rng(uid).integers(0, cfg.vocab, (t,))
    return Request(uid=uid, tokens=toks.astype(np.int32), max_new=max_new,
                   arrival_time=arrival)


def _serve(eng, reqs, level=logging.INFO):
    """Serve with the scheduler logger at ``level``; returns (event dicts,
    progress_cb calls)."""
    got, calls = [], []

    class Keep(logging.Handler):
        def emit(self, rec):
            got.append(rec.getMessage())

    log = logging.getLogger("repro.serving.scheduler")
    old, h = log.level, Keep()
    log.setLevel(level)
    log.addHandler(h)
    try:
        eng.serve(reqs, progress_cb=lambda e, s: calls.append(1))
    finally:
        log.removeHandler(h)
        log.setLevel(old)
    return [e for e in map(parse_event, got) if e], len(calls)


def _iterations(evs):
    return [e for e in evs if e["event"] == "iteration"]


# Hand counts, (live, steps, rows, lane_tokens) per iteration.  A decode
# step j (1..steps) of a slot at position p (prompt + tokens generated)
# reads p + j valid rows, at most the sliding window.
CASES = {
    # llama, chunked, 2 slots, chunk 4, lane 8: uid 0 (prompt 6, 8 new)
    # is armed in iteration 0 and decodes from 6 then 10; uid 1 (prompt
    # 8, 4 new) is armed in iteration 1 and decodes from 8; the last
    # pass finds no work
    "chunked": ("llama", dict(prefill_mode="chunked", p_chunk=8),
                [(0, 6, 8), (1, 8, 4)],
                [(1, 4, 7 + 8 + 9 + 10, 6),
                 (2, 4, (11 + 12 + 13 + 14) + (9 + 10 + 11 + 12), 8),
                 (0, 0, 0, 0)]),
    # the same requests admitted whole: both in iteration 0
    "whole": ("llama", dict(prefill_mode="whole"),
              [(0, 6, 8), (1, 8, 4)],
              [(2, 4, (7 + 8 + 9 + 10) + (9 + 10 + 11 + 12), 6 + 8),
               (1, 4, 11 + 12 + 13 + 14, 0),
               (0, 0, 0, 0)]),
    # danube (32-row ring), 1 slot, chunk 8, lane 16: a 28-token prompt
    # takes two lane chunks, then decodes from 28 (29..32, then capped)
    # and from 36 (every read capped at the window)
    "ring": ("danube", dict(prefill_mode="chunked", p_chunk=16, chunk=8,
                            n_slots=1),
             [(0, 28, 12)],
             [(0, 0, 0, 16),
              (1, 8, 29 + 30 + 31 + 32 * 5, 12),
              (1, 8, 32 * 8, 0),
              (0, 0, 0, 0)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_record_per_iteration_with_hand_counts(case, request):
    model, kw, reqs, want = CASES[case]
    cfg, params = request.getfixturevalue(model)
    kw = {"n_slots": 2, "max_len": 64, "chunk": 4, **kw}
    eng = ContinuousEngine(cfg, params, NO_QUANT, **kw)
    evs, n_cb = _serve(eng, [_req(cfg, *r) for r in reqs])
    recs = _iterations(evs)
    assert [r["i"] for r in recs] == list(range(len(recs)))
    assert sum(1 for r in recs if r["steps"]) == n_cb
    for r in recs:
        assert "seq" not in r
        assert all(r[f"{p}_ms"] >= 0 for p in PHASES)
        assert r["compiles"] >= 0
    got = [tuple(r[k] for k in COUNTERS) for r in recs]
    assert got == want


def test_no_records_and_no_serialization_above_info(llama, monkeypatch):
    cfg, params = llama
    eng = ContinuousEngine(cfg, params, NO_QUANT, n_slots=2, max_len=64,
                           chunk=4, prefill_mode="chunked", p_chunk=8)

    def refuse(*a, **k):
        raise AssertionError("serialized a record nobody listens to")

    monkeypatch.setattr(events.json, "dumps", refuse)
    evs, n_cb = _serve(eng, [_req(cfg, 0, 6, 8)], level=logging.WARNING)
    assert evs == [] and n_cb == 2
    assert eng.journal.seq == 3         # prefill-start, -done and finish


def test_records_leave_the_journal_sequence_alone(llama):
    cfg, params = llama
    kw = dict(n_slots=2, max_len=64, chunk=4, prefill_mode="chunked",
              p_chunk=8)
    reqs = [_req(cfg, 0, 6, 8), _req(cfg, 1, 8, 4), _req(cfg, 2, 12, 5)]
    quiet = ContinuousEngine(cfg, params, NO_QUANT, **kw)
    _serve(quiet, reqs, level=logging.WARNING)
    eng = ContinuousEngine(cfg, params, NO_QUANT, **kw)
    evs, _ = _serve(eng, reqs)
    seqs = [e["seq"] for e in evs if "seq" in e]
    assert seqs == list(range(len(seqs))) == list(range(eng.journal.seq))
    assert eng.journal.seq == quiet.journal.seq
    assert _iterations(evs)
    ordered, gaps = replay([json.dumps(e) for e in evs])
    assert gaps == []
    assert [e["seq"] for e in ordered if "seq" in e] == seqs


def test_compiles_counted_cold_and_zero_warm(llama):
    cfg, params = llama
    # a configuration no other test builds, so its programs are cold
    cold = dataclasses.replace(cfg, name=cfg.name + "-compile-count")
    eng = ContinuousEngine(cold, params, NO_QUANT, n_slots=2, max_len=64,
                           chunk=4, prefill_mode="chunked", p_chunk=8)
    reqs = [_req(cfg, 0, 6, 8), _req(cfg, 1, 8, 4)]
    first, _ = _serve(eng, reqs)
    assert sum(r["compiles"] for r in _iterations(first)) > 0
    again, _ = _serve(eng, reqs)
    assert [r["compiles"] for r in _iterations(again)] == [0] * len(
        _iterations(again))


def test_trace_names_the_programs_and_carries_every_span(llama, tmp_path):
    from bench import trace

    cfg, params = llama
    eng = ContinuousEngine(cfg, params, NO_QUANT, n_slots=2, max_len=64,
                           chunk=4, prefill_mode="chunked", p_chunk=8)
    # uid 1 arrives after uid 0 is done, so the loop sleeps in between
    reqs = [_req(cfg, 0, 6, 4), _req(cfg, 1, 12, 4, arrival=0.3)]
    _serve(eng, reqs)                               # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, reqs)
    finally:
        jax.profiler.stop_trace()
    evs = trace.load(str(tmp_path))
    programs = trace.reduce(evs, window_s=1.0).programs
    for name in ("jit_decode_chunk", "jit_lane_chunk"):
        assert any(p.startswith(name) for p in programs), sorted(programs)
    spans = {}
    for e in evs:
        if e.name.startswith("serve."):
            spans.setdefault(e.name, []).append(e.stats)
    assert sorted(spans) == sorted(f"serve.{p}" for p in PHASES)
    assert all("i" in s for v in spans.values() for s in v)
    lane = spans["serve.lane"]
    assert {int(s["uid"]) for s in lane} == {0, 1}
    assert all({"offset", "n_valid", "final"} <= set(s) for s in lane)
