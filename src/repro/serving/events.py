"""Structured JSONL serving events on the standard ``repro.serving`` loggers.

Scheduler/engine lifecycle transitions — admit, prefill start/done,
finish, shed, expire, cancel, degrade, quarantine, requeue, fault — are
logged as ONE ``json.dumps`` object per record, so a serving run (and in
particular a fault-injection run, DESIGN.md §11) leaves a machine-
parseable postmortem trail behind the ordinary logging tree: handlers,
filters and levels keep working unchanged, and human-oriented messages
(compile warnings, autotune summaries) coexist on the same loggers.
``parse_event`` is the read side: feed it captured log messages and it
returns the event dicts, skipping the human text.

``Journal`` makes the stream a RECOVERY LOG: one monotonic per-engine
sequence number stamped on every record.  A replayed journal with a
hole in its sequence is a journal that lost records (crashed writer,
dropped shipment) — ``replay`` surfaces the gaps instead of silently
reordering around them, and ``checkpoint``/``restore`` carry the
cursor across processes so post-restore events extend the same
sequence.

``Loop`` is the serve loop's own measurement (DESIGN.md §16): ``span``
marks each phase of a loop iteration as a host span on the profiler's
clock (``jax.profiler.TraceAnnotation``, so it lands in the same trace as
the device work) and adds its self time to the iteration; at the end of
each iteration one ``iteration`` record gives the phase times and the
iteration's counters.  Iteration records carry no ``seq``: they are
measurements, not part of the recovery log.  With the logger above INFO
and no profiler running, a span costs one idle ``TraceAnnotation`` and
no record is built.
"""
from __future__ import annotations

import contextlib
import json
import logging
import time
from typing import Iterable, List, Optional, Tuple

from jax import monitoring
from jax.profiler import TraceAnnotation

__all__ = ["emit", "parse_event", "Journal", "replay", "EVENT_KINDS",
           "Loop", "PHASES", "COUNTERS", "compile_count"]

# Every kind the engine/scheduler emit today.  Recovery kinds (suspend
# through restore) are what journal replay reconstructs an engine's
# request placement from.  Memory kinds (pool / cow-break / prefix-hit)
# are the paged-KV observability records (DESIGN.md §14): page-pool
# occupancy + high watermark at every allocation/release edge, shared-
# page copy-on-write breaks, and shared-prefix admission hits.
# ``kv-repack`` is the tiered engine's degraded-KV rung (DESIGN.md §15):
# a resident slot's cache re-quantized into the cheap tier's arena.
# ``iteration`` is the serve loop's per-iteration measurement (``Loop``).
EVENT_KINDS = ("admit", "prefill-start", "prefill-done", "degrade",
               "shed", "expire", "cancel", "fault", "quarantine",
               "requeue", "finish", "suspend", "resume", "preempt",
               "migrate", "drain", "checkpoint", "restore", "spec-k",
               "pool", "cow-break", "prefix-hit", "kv-repack", "iteration")


def emit(logger, event: str, **fields) -> None:
    """Log one structured JSONL event record at INFO on ``logger``.

    The record is ``{"event": <event>, **fields}`` serialized as a single
    JSON object (sorted keys, None-valued fields dropped — absent beats
    null for grep-ability).  Numpy scalars coerce through ``float``.
    Nothing is serialized when ``logger`` is not enabled for INFO.
    """
    if not logger.isEnabledFor(logging.INFO):
        return
    rec = {"event": event}
    rec.update({k: v for k, v in fields.items() if v is not None})
    logger.info("%s", json.dumps(rec, sort_keys=True, default=float))


def parse_event(message: str) -> Optional[dict]:
    """Parse one logged message back into its event dict.

    Returns None for anything that is not a JSONL event record — the
    serving loggers intentionally carry human-oriented text too, so the
    postmortem reader filters rather than asserts.
    """
    if not message.lstrip().startswith("{"):
        return None
    try:
        obj = json.loads(message)
    except ValueError:
        return None
    return obj if isinstance(obj, dict) and "event" in obj else None


class Journal:
    """Monotonic sequence numbers over ``emit`` — the engine's event log.

    One Journal per engine; the engine and its scheduler share it so
    every record (including scheduler-side degrades) lands in ONE total
    order.  ``seq`` is the next number to stamp; a checkpoint persists
    it and ``restore`` resumes from it, so a post-crash journal reads as
    a single continuous sequence (re-used numbers from the lost tail
    dedupe on replay; true losses show up as gaps).
    """

    def __init__(self, start: int = 0):
        self.seq = int(start)

    def emit(self, logger, event: str, **fields) -> None:
        emit(logger, event, seq=self.seq, **fields)
        self.seq += 1


def replay(messages: Iterable[str]) -> Tuple[List[dict], List[int]]:
    """Reconstruct an ordered journal from captured log messages.

    Returns ``(events, gaps)``: sequenced events sorted by ``seq``
    (duplicates collapse — a restore re-issues the numbers of records
    emitted after the last checkpoint), followed by any un-sequenced
    records, and the list of missing sequence numbers between the
    lowest and highest observed.  A non-empty ``gaps`` means the
    recovery log lost records and replay-derived state is suspect.
    """
    evs = [e for e in (parse_event(m) for m in messages) if e is not None]
    by_seq = {}
    rest = []
    for e in evs:
        if isinstance(e.get("seq"), int):
            by_seq.setdefault(e["seq"], e)
        else:
            rest.append(e)
    ordered = [by_seq[s] for s in sorted(by_seq)]
    gaps: List[int] = []
    if by_seq:
        lo, hi = min(by_seq), max(by_seq)
        gaps = [s for s in range(lo, hi + 1) if s not in by_seq]
    return ordered + rest, gaps


# The serve loop's phases: span ``serve.<phase>`` adds its self time to
# the iteration record's ``<phase>_ms``.
PHASES = ("lifecycle", "lane", "lane_wait", "upload", "dispatch", "wait",
          "harvest", "sleep")
# The iteration record's counters (besides ``compiles``): slots that
# decoded in the chunk, its decode steps, the valid K/V rows its attention
# read, and the prompt tokens of the lane chunk dispatched.
COUNTERS = ("live", "steps", "rows", "lane_tokens")

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")
_compiles = [0, False]          # programs traced or compiled; listening


def _on_compile(event: str, duration: float, **kwargs) -> None:
    if event in _COMPILE_EVENTS:
        _compiles[0] += 1


def compile_count() -> int:
    """Programs traced or compiled in this process since the first call
    (one ``jax.monitoring`` listener, registered once per process)."""
    if not _compiles[1]:
        monitoring.register_event_duration_secs_listener(_on_compile)
        _compiles[1] = True
    return _compiles[0]


class Loop:
    """Spans and the per-iteration ``iteration`` record of one serve loop.

    ``begin()`` opens iteration ``i`` (0, 1, ... per ``reset``), ``span``
    marks its phases, ``add`` counts, and ``end()`` logs the record on
    ``logger`` at INFO.  Spans nest (a lane span holds the first-token
    wait); each phase gets its self time, so the phases of a record are
    disjoint.  Every span carries ``i`` as an argument in the trace.
    """

    def __init__(self, logger):
        self.logger = logger
        self.i = -1
        self._phases = None     # phase -> seconds; None: no record
        self._counts = {}
        self._inner = 0.0       # seconds of child spans of the open span
        self._compiles = 0

    @property
    def recording(self) -> bool:
        """Whether the open iteration builds a record."""
        return self._phases is not None

    def reset(self) -> None:
        self.i = -1

    def begin(self) -> None:
        self.i += 1
        if not self.logger.isEnabledFor(logging.INFO):
            self._phases = None
            return
        self._phases = dict.fromkeys(PHASES, 0.0)
        self._counts = dict.fromkeys(COUNTERS, 0)
        self._inner = 0.0
        self._compiles = compile_count()

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Host span ``name`` (``serve.<phase>``) with ``i`` and ``args``."""
        with TraceAnnotation(name, i=self.i, **args):
            if self._phases is None:
                yield
                return
            t0, outer, self._inner = time.perf_counter(), self._inner, 0.0
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                phase = name.rpartition(".")[2]
                self._phases[phase] += dt - self._inner
                self._inner = outer + dt

    def add(self, **counts) -> None:
        """Add to the open iteration's counters (no-op unless recording)."""
        if self._phases is not None:
            for k, v in counts.items():
                self._counts[k] += int(v)

    def end(self) -> None:
        """Log the open iteration's record and close it."""
        if self._phases is None:
            return
        emit(self.logger, "iteration", i=self.i,
             **{f"{p}_ms": 1e3 * s for p, s in self._phases.items()},
             **self._counts, compiles=compile_count() - self._compiles)
        self._phases = None
