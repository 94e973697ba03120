"""Profiler trace capture and its reduction to device metrics.

``capture`` records a JAX profiler trace into a directory; ``load`` reads
the ``.xplane.pb`` it wrote (``jax.profiler.ProfileData``, nothing else)
into flat events; ``reduce`` turns them into what the metric readers need.

Which events are device work: on a TPU, the events of the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane are the operations, and those of its
``XLA Modules`` line are whole programs.  On the CPU backend (the tests)
operations run on host threads and carry an ``hlo_op`` stat, with their
program in the ``hlo_module`` stat.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass
class Ev:
    plane: str
    line: str
    name: str
    start: float            # seconds
    dur: float              # seconds
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(trace_dir: str) -> List[Ev]:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    evs: List[Ev] = []
    for path in files:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    try:
                        stats = dict(e.stats)
                    except Exception:           # unreadable stat values
                        stats = {}
                    evs.append(Ev(plane.name, line.name, e.name,
                                  e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                  stats))
    return evs


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def device_ops(evs: List[Ev]) -> Dict[str, List[Ev]]:
    """Device -> its operations (leaf device work), in start order."""
    out: Dict[str, List[Ev]] = defaultdict(list)
    for e in evs:
        if _is_device_plane(e.plane):
            if e.line == "XLA Ops":
                out[e.plane].append(e)
        elif "hlo_op" in e.stats and e.dur > 0:
            out[f"/cpu:{e.stats.get('device_ordinal', 0)}"].append(e)
    for v in out.values():
        v.sort(key=lambda e: e.start)
    return dict(out)


def program_name(name: str) -> str:
    """A program's name as the trace gives it: the jitted function's name
    and, in parentheses, the compiled program's fingerprint."""
    return name.strip()


def device_modules(evs: List[Ev]) -> Dict[str, List[Ev]]:
    """Device -> its whole-program executions.  On the CPU backend each
    program's span is made from its operations' ``hlo_module`` stat."""
    out: Dict[str, List[Ev]] = defaultdict(list)
    for e in evs:
        if _is_device_plane(e.plane) and e.line == "XLA Modules":
            out[e.plane].append(e)
    if out:
        return dict(out)
    for dev, ops in device_ops(evs).items():
        spans: Dict[Tuple[str, object], List[float]] = {}
        for e in ops:
            key = (e.stats.get("hlo_module", "?"), e.stats.get("run_id"))
            s = spans.setdefault(key, [e.start, e.end, 0.0])
            s[0], s[1] = min(s[0], e.start), max(s[1], e.end)
        out[dev] = [Ev(dev, "modules", k[0], s[0], s[1] - s[0])
                    for k, s in spans.items()]
    return dict(out)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as disjoint sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_s(intervals) -> float:
    return sum(e - s for s, e in merge(list(intervals)))


@dataclass
class Reduction:
    window_s: float                          # traced window, host clock
    busy_s: float                            # mean over devices
    n_devices: int
    op_s: Dict[str, float]                   # leaf op -> device seconds
    op_calls: Dict[str, List[Ev]]            # op name -> its events
    programs: Dict[str, Tuple[float, int]]   # program -> (seconds, runs)
    gaps: List[Tuple[str, float]]            # longest idle gaps, by host
    devices: List[str]

    def calls(self, kernel: str) -> List[Ev]:
        """Calls of the Pallas kernel ``kernel``: the TPU trace names each
        op by its HLO text, whose instruction is named after the kernel
        (``%nxfp_matmul.34 = f32[8,8192] custom-call(...)``)."""
        pat = re.compile(rf"^%?{re.escape(kernel)}(\.\d+)?\s*=")
        return [e for name, evs in self.op_calls.items() if pat.match(name)
                for e in evs]

    def program(self, name: str) -> Tuple[float, int]:
        """(device seconds, runs) of the program ``name`` (as the trace
        names it, fingerprint included), per device."""
        s, n = self.programs.get(name, (0.0, 0))
        return s / self.n_devices, n // max(self.n_devices, 1)


_MATMUL = re.compile(r"=\s*f32\[(\d+),(\d+)\][^ ]*\s+custom-call\("
                     r"bf16\[(\d+),(\d+),(\d+)\]")


def matmul_shape(ev: Ev):
    """(m, k, n) of an ``nxfp_matmul`` call from its op text: the output
    is f32[m, n] and the first operand the activations' plane view
    bf16[planes, m, k / planes].  None where the text has no such shapes."""
    g = _MATMUL.search(ev.name)
    if g is None:
        return None
    m, n, planes, m2, kp = (int(x) for x in g.groups())
    return (m, planes * kp, n) if m == m2 else None


def _host_label(evs: List[Ev], s: float, e: float) -> str:
    """Name of the host event that covers most of the gap ``[s, e]``."""
    best, cover = "no host event", 0.0
    for h in evs:
        c = min(h.end, e) - max(h.start, s)
        if c > cover:
            best, cover = h.name, c
    return best


def clip(evs: List[Ev], span: str):
    """The events inside the host span named ``span`` and its length; all
    of them and None where the trace has no such span."""
    marks = [e for e in evs if e.name == span]
    if not marks:
        return evs, None
    s, e = marks[0].start, marks[0].end
    return [x for x in evs if x.start >= s and x.end <= e], e - s


def leaves(ops: List[Ev]) -> List[Ev]:
    """The ops that enclose no other op (a while loop's event spans its
    body's ops; only the body's count as work of their own)."""
    out, stack = [], []
    for e in sorted(ops, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            top, parent = stack.pop()
            if not parent:
                out.append(top)
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] = True
        stack.append([e, False])
    out += [top for top, parent in stack if not parent]
    return out


def reduce(evs: List[Ev], window_s: float, n_gaps: int = 10,
           span: Optional[str] = None) -> Reduction:
    """``window_s``: the traced window's length; with ``span``, only the
    events inside that host span count and its length is the window."""
    if span is not None:
        evs, span_s = clip(evs, span)
        window_s = span_s if span_s is not None else window_s
    ops = device_ops(evs)
    if not ops:
        raise ValueError("the trace holds no device operations")
    busy = [union_s((e.start, e.end) for e in v) for v in ops.values()]
    op_s: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, List[Ev]] = defaultdict(list)
    for v in ops.values():
        for e in leaves(v):
            op_s[e.name] += e.dur
        for e in v:
            op_calls[e.name].append(e)
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for v in device_modules(evs).values():
        for e in v:
            p = programs[program_name(e.name)]
            p[0] += e.dur
            p[1] += 1
    first = sorted(ops)[0]
    merged = merge([(e.start, e.end) for e in ops[first]])
    host = [e for e in evs if e.plane.startswith("/host") and e.dur > 0
            and "hlo_op" not in e.stats and e.name != span]
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)
    labelled = [(_host_label(host, s, e), g) for g, s, e in gaps[:n_gaps]]
    return Reduction(window_s=window_s, busy_s=sum(busy) / len(busy),
                     n_devices=len(ops), op_s=dict(op_s),
                     op_calls=dict(op_calls),
                     programs={k: (v[0], int(v[1]))
                               for k, v in programs.items()},
                     gaps=labelled, devices=sorted(ops))


def breakdown(red: Reduction, n: int = 10, width: int = 160) -> dict:
    """The leaf device ops that took most time (their HLO text cut to
    ``width``) and the longest idle gaps, by what the host was doing."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k[:width], v] for k, v in ops],
            "idle_gaps": [[k[:width], v] for k, v in red.gaps[:n]]}


def longest_program(evs: List[Ev]) -> Optional[str]:
    """Name of the program that ran longest in a trace (the probe's)."""
    best, dur = None, 0.0
    for v in device_modules(evs).values():
        for e in v:
            if e.dur > dur:
                best, dur = program_name(e.name), e.dur
    return best


WINDOW_SPAN = "bench_traced_window"


class Capture:
    """Trace a steady part of the window: start the profiler at
    ``start_s`` of the window, open a host span ``WINDOW_SPAN`` at the next
    decode chunk (so the profiler's own start-up lies outside it), and
    close it and stop ``length_s`` later.  Checked after each decode chunk;
    the reduction keeps only what falls inside the span.  Stopping the
    profiler holds the serve loop for tens of seconds while it collects
    the trace, so the harness reads its host-clock metrics of a traced run
    from the part of the window before ``t_started``."""

    def __init__(self, trace_dir: str, start_s: float, length_s: float):
        self.dir, self.start_s, self.length_s = trace_dir, start_s, length_s
        self.state = "idle"
        self.t_started = None               # profiler start, window clock
        self.t_on_window = 0.0              # span start, window clock
        self.window_s = 0.0                 # span length, host clock
        self._span = self._t0 = None

    def tick(self, t: float) -> None:
        import time
        import jax
        if self.state == "idle" and t >= self.start_s:
            jax.profiler.start_trace(self.dir)
            self.state, self.t_started = "started", t
        elif self.state == "started":
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
            self._t0, self.t_on_window = time.perf_counter(), t
            self.state = "open"
        elif self.state == "open" and t >= self.t_on_window + self.length_s:
            self.finish()

    def finish(self) -> None:
        import time
        import jax
        if self.state == "open":
            self.window_s = time.perf_counter() - self._t0
            self._span.__exit__(None, None, None)
        if self.state in ("started", "open"):
            jax.profiler.stop_trace()
        self.state = "done"
