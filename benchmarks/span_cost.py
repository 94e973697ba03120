"""Host cost of the serve loop's spans and ``iteration`` records.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m benchmarks.span_cost

Three states of ``serving.events.Loop``: the scheduler logger above INFO
(spans only: one ``TraceAnnotation`` and one ``perf_counter`` pair each,
no record), at INFO with a handler that parses every record as the chip
benchmark's does, and at INFO with the JAX profiler recording.  Part 1
times one decode iteration's instrumentation alone -- the calls the serve
loop makes, with the chunk's counters taken over 32 decoding slots -- as
microseconds per iteration.  Part 2 serves a tiny model through
``ContinuousEngine.serve`` in each state and prints its wall time per
loop iteration, for scale.  Host times of the machine it runs on.
"""
from __future__ import annotations

import json
import logging
import statistics
import tempfile
import time
from types import SimpleNamespace

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core.qtensor import QuantPolicy
from repro.models import init_params
from repro.serving import ContinuousEngine, Request
from repro.serving.events import Loop

LOGGER = "repro.serving.scheduler"


class Parse(logging.Handler):
    """Parses every record, as the chip benchmark's event log does."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.n = 0

    def emit(self, rec):
        json.loads(rec.getMessage())
        self.n += 1


class State:
    """One instrumentation state, entered around a measurement."""

    def __init__(self, name: str):
        self.name = name
        self.log = logging.getLogger(LOGGER)
        self.handler = Parse()

    def __enter__(self):
        self.old = (self.log.level, self.log.propagate)
        self.log.propagate = False
        if self.name != "off":
            self.log.setLevel(logging.INFO)
            self.log.addHandler(self.handler)
        else:
            self.log.setLevel(logging.WARNING)
        if self.name == "profiler":
            self.dir = tempfile.mkdtemp(prefix="span_cost_")
            jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        if self.name == "profiler":
            jax.profiler.stop_trace()
        self.log.removeHandler(self.handler)
        self.log.setLevel(self.old[0])
        self.log.propagate = self.old[1]


def one_iteration(loop: Loop, engine, sched) -> None:
    """The instrumentation calls of one decode iteration of the serve
    loop, with empty phases (``engine``/``sched`` feed the counters)."""
    loop.begin()
    with loop.span("serve.lifecycle"):
        pass
    with loop.span("serve.lane", uid=7, offset=1024, n_valid=512,
                   final=True):
        loop.add(lane_tokens=512)
        with loop.span("serve.lane_wait"):
            pass
    if loop.recording:
        ContinuousEngine._count_chunk(engine, sched)
    for phase in ("upload", "dispatch", "wait", "harvest", "harvest"):
        with loop.span(f"serve.{phase}"):
            pass
    loop.end()


def part1(n: int = 20000) -> dict:
    slots = 32
    loop = Loop(logging.getLogger(LOGGER))
    engine = SimpleNamespace(
        _live=np.ones(slots, bool), _done=np.zeros(slots, bool),
        _n_gen=np.arange(slots, dtype=np.int32), _has_attn_kv=True,
        cfg=SimpleNamespace(sliding_window=4096), _loop=loop,
        _chunk_horizon=lambda: 4)
    sched = SimpleNamespace(active={s: SimpleNamespace(
        tokens=np.zeros(1000 + s, np.int32)) for s in range(slots)})
    out = {}
    for name in ("off", "info", "profiler"):
        with State(name):
            for _ in range(200):
                one_iteration(loop, engine, sched)
            t0 = time.perf_counter()
            for _ in range(n):
                one_iteration(loop, engine, sched)
            out[name] = 1e6 * (time.perf_counter() - t0) / n
    return out


def part2(repeats: int = 5) -> dict:
    cfg = get_smoke_config("llama3_8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params,
                           QuantPolicy(weight_fmt=None, kv_fmt=None),
                           n_slots=4, max_len=128, chunk=1,
                           prefill_mode="chunked", p_chunk=16)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, tokens=rng.integers(0, cfg.vocab, (24,))
                    .astype(np.int32), max_new=48) for i in range(8)]
    eng.serve(reqs)                                 # compile
    out = {}
    for name in ("off", "info", "profiler"):
        per = []
        for _ in range(repeats):
            passes = []
            with State(name):
                t0 = time.perf_counter()
                eng.serve(reqs, progress_cb=lambda e, s: passes.append(1))
                per.append(1e6 * (time.perf_counter() - t0) / len(passes))
        out[name] = statistics.median(per)
    return out


def main() -> None:
    print("instrumentation of one decode iteration (us):",
          json.dumps({k: round(v, 2) for k, v in part1().items()}))
    print("tiny serve, wall time per decode iteration (us):",
          json.dumps({k: round(v, 1) for k, v in part2().items()}))


if __name__ == "__main__":
    main()
