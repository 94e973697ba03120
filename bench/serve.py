"""Drive the engine through one measured window, on the harness's clock.

The harness stamps the engine's own JSONL events (``prefill-start``,
``prefill-done``, ``finish``) as its logging handler receives them, and
reads the slots after every decode chunk through ``serve(progress_cb=)``.
Every time below is ``time.perf_counter()`` seconds after the instant just
before ``serve()`` was called; a request is due at that instant plus its
``arrival``.

The window opens with the serve.  How it closes (``traffic["window"]``):
``stop``: ``drain`` (no request arrives after the window; those sent run to
their end, for at most ``drain_s`` more seconds, then the rest is
cancelled) or ``cancel`` (everything still queued or running is cancelled
when the window closes).
"""
from __future__ import annotations

import json
import logging
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

SCHED_LOGGER = "repro.serving.scheduler"


class EventLog(logging.Handler):
    """Keeps each JSONL event record with the harness clock's reading."""

    def __init__(self, clock: Callable[[], float]):
        super().__init__(logging.INFO)
        self.clock = clock
        self.events: List[Tuple[float, dict]] = []

    def emit(self, record):
        msg = record.getMessage()
        if not msg.startswith("{"):
            return
        try:
            ev = json.loads(msg)
        except ValueError:
            return
        if isinstance(ev, dict) and "event" in ev:
            self.events.append((self.clock(), ev))


@dataclass
class Window:
    seconds: float
    start: float = 0.0                  # window opens (harness clock)
    end: float = 0.0                    # window closes
    serve_s: float = 0.0                # the whole serve() call
    # one row per decode chunk: (time, tokens emitted so far, slots that
    # decoded in that chunk)
    samples: List[Tuple[float, int, int]] = field(default_factory=list)
    events: List[Tuple[float, dict]] = field(default_factory=list)
    results: list = field(default_factory=list)
    due: Dict[int, float] = field(default_factory=dict)
    n_slots: int = 0

    def tokens_at(self, t: float) -> float:
        """Tokens emitted by time ``t``, linear between decode chunks."""
        if not self.samples:
            return 0.0
        ts = [0.0] + [s[0] for s in self.samples]
        ns = [0.0] + [float(s[1]) for s in self.samples]
        return float(np.interp(t, ts, ns))

    def chunks_in(self, t0: float, t1: float):
        return [s for s in self.samples if t0 < s[0] <= t1]

    def times(self, kind: str) -> Dict[int, float]:
        """uid -> harness time of its first ``kind`` event."""
        out: Dict[int, float] = {}
        for t, ev in self.events:
            if ev["event"] == kind and "uid" in ev:
                out.setdefault(int(ev["uid"]), t)
        return out

    def finishes(self) -> Dict[int, Tuple[float, int, str]]:
        return {int(ev["uid"]): (t, int(ev.get("n", 0)), ev.get("status"))
                for t, ev in self.events if ev["event"] == "finish"}


def drive(eng, requests, window: dict, seconds: float,
          on_tick: Optional[Callable[[float], None]] = None) -> Window:
    """Serve ``requests`` (the program's ``Request`` objects, arrivals set)
    through ``eng`` for one window; ``on_tick(t)`` runs after
    every decode chunk (the traced run starts and stops the profiler
    there)."""
    w = Window(seconds=seconds, end=seconds, n_slots=eng.n_slots)
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0  # noqa: E731
    log = EventLog(clock)
    logger = logging.getLogger(SCHED_LOGGER)
    old = (logger.level, logger.propagate)
    logger.setLevel(logging.INFO)
    logger.propagate = False
    logger.addHandler(log)
    uids = [r.uid for r in requests]
    w.due = {r.uid: r.arrival_time for r in requests}
    stop = window["stop"]
    state = {"closed": False, "finished": 0}

    def cb(engine, sched):
        t = clock()
        done = [r for r in engine._results]
        tokens = sum(r.n_generated for r in done) + sum(
            len(st["out"]) for st in engine._state.values())
        # slots that decoded in this chunk: those still decoding plus
        # those the chunk finished
        decoded = len(engine._state) + len(done) - state["finished"]
        state["finished"] = len(done)
        w.samples.append((t, tokens, decoded))
        if on_tick is not None:
            on_tick(t)
        limit = w.end + (window.get("drain_s", 0) if stop == "drain" else 0)
        if t >= limit and not state["closed"]:
            state["closed"] = True
            for u in uids:
                engine.cancel(u)

    try:
        w.results = eng.serve(requests, progress_cb=cb)
    finally:
        logger.removeHandler(log)
        logger.setLevel(old[0])
        logger.propagate = old[1]
    w.serve_s = clock()
    w.events = log.events
    return w


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    k = max(0, int(np.ceil(q / 100.0 * len(v))) - 1)
    return float(v[k])
