"""Run one benchmark cell on the chip and print its result as JSON.

  python3 bench/run.py --workload danube3-4b.chat --seed 7 --seconds 30 \
      --trace 0

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``, which names the model's family and its
reference) served under a traffic mix (``bench/traffic/<traffic>.json``),
which also fixes the engine's deployment: the serving engine by its class
name and its settings.  Each run:

 1. refuses to run unless JAX finds a TPU with as many chips as the cell
    asks for (exit 2, no result);
 2. builds the configuration with NxFP4 weights made on the device from
    ``--seed`` and turns on JAX's persistent compile cache inside the
    checkout;
 3. warms up the cell's own programs (one lane chunk with and without the
    head, the first-token tail, one decode chunk, the slot reset);
 4. serves the seeded traffic through the engine's ``serve`` for
    ``--seconds`` (``bench/serve.py``); with ``--trace 1`` the profiler
    records a short steady part of that window;
 5. frees the engine and compares a seeded sample of the served requests
    with the plain reference (``bench/reference``);
 6. prints, as its last line of standard output, one JSON object: the
    cell's end-to-end metrics (``--trace 0``) or per-layer metrics
    (``--trace 1``), the device, and the numbers compared with their
    limits (also the last lines on standard error).

Per-request times and the engine's own ``ttft``/``queue_delay`` go to
``chiprun_out/bench/<workload>.<seed>.<trace>.jsonl`` beside the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
for _p in (CHECKOUT / "src", CHECKOUT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import serve as serving  # noqa: E402
from bench import spec as specs  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench import traffic as traffics  # noqa: E402
from bench.peaks import peaks  # noqa: E402

OUT_DIR = CHECKOUT / "chiprun_out" / "bench"


class Context:
    """What a per-layer metric reader may read (``bench/metrics``)."""

    def __init__(self, cell, window, red, capture, device_kind, peak_bytes,
                 prompt_len):
        self.cell = cell
        self.prompt_len = prompt_len        # uid -> prompt tokens
        self.model = cell.config["model"]
        self.traffic = cell.traffic
        self.window = window
        self.trace = red                    # trace.Reduction or None
        self.capture = capture              # trace.Capture or None
        self.peaks = peaks(device_kind) if red is not None else None
        self.peak_bytes = peak_bytes
        self.programs = {}          # "decode"/"lane" -> trace names

    def untraced_until(self) -> float:
        """Harness time up to which the serve ran with the profiler off."""
        c = self.capture
        if c is None or c.t_started is None:
            return self.window.end
        return min(self.window.end, self.window.start + c.t_started)

    def trace_span(self):
        """(start, end) of the traced window on the harness clock."""
        c = self.capture
        t0 = self.window.start + c.t_on_window
        return t0, t0 + self.trace.window_s


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{devices[0].platform!r}); refusing to run")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:n]


def model_config(cell):
    """The program's ``ModelConfig`` from every key of the configuration's
    ``model`` that it has (``family`` among them); ``dtype`` by name."""
    import dataclasses
    import jax.numpy as jnp
    from repro.models.common import ModelConfig
    m = cell.config["model"]
    names = {f.name for f in dataclasses.fields(ModelConfig)} - {"name",
                                                                 "dtype"}
    return ModelConfig(name=cell.config["name"],
                       dtype=getattr(jnp, m.get("dtype", "bfloat16")),
                       **{k: v for k, v in m.items() if k in names})


def engine_class(cell):
    import repro.serving
    return getattr(repro.serving, cell.traffic["engine"]["class"])


def build_params(cell, seed: int, devices):
    """(cfg, policy, params, mesh): the configuration's weights made on
    the device from ``seed``.  An engine that takes a ``mesh`` gets a 1-D
    ``('data',)`` mesh over the cell's chips, the weights replicated."""
    import inspect
    import jax
    from repro.core.qtensor import QuantPolicy
    from repro.models import init_cast_params
    cfg = model_config(cell)
    m = cell.config["model"]
    policy = QuantPolicy(weight_fmt=m["weight_fmt"], kv_fmt=m["kv_fmt"])
    mesh = None
    if "mesh" in inspect.signature(engine_class(cell)).parameters:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(len(devices))
    params = init_cast_params(cfg, cell.reference().model_key(seed), policy,
                              mesh=mesh)
    jax.block_until_ready(params)
    return cfg, policy, params, mesh


def make_engine(cell, cfg, policy, params, mesh, **overrides):
    """The traffic file's engine (``engine.class`` with the rest of
    ``engine`` as its settings, ``overrides`` on top)."""
    e = {k: v for k, v in cell.traffic["engine"].items() if k != "class"}
    e.update(overrides)
    extra = {} if mesh is None else {"mesh": mesh}
    return engine_class(cell)(cfg, params, policy, warn_compile=False,
                              **extra, **e)


def warm(eng) -> None:
    """Compile every program the window dispatches: a prompt one token
    longer than a lane chunk runs a chunk without and one with the head
    (where the lane holds two chunks; else the window's prompts all fit
    one chunk, which has the head), the first-token tail, a decode chunk
    and the slot reset."""
    from repro.serving import Request
    import numpy as np
    n = min(eng.p_chunk + 1, eng.max_len - 2)
    eng.serve([Request(uid=-1, tokens=np.zeros((n,), np.int32), max_new=2)])


def probe_programs(eng, trace_dir: str) -> dict:
    """The trace names of the decode-chunk and lane programs.

    The engine jits ``functools.partial`` objects, which the trace names
    ``jit__unknown(<fingerprint>)``; each program is told apart by its
    fingerprint, read here from a trace of one call of each, alone, on the
    engine's own state (outputs dropped).  The lane has a program for a
    prompt's last chunk (with the head) and, where the lane holds more
    than one chunk, one for the chunks before it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def lane(with_head):
        return lambda: eng._lane_fn(
            eng.params, jnp.zeros((1, eng.p_chunk), jnp.int32), eng.cache,
            eng.lane, jnp.int32(0), jnp.int32(0), jnp.int32(eng.p_chunk),
            with_head=with_head, wrapped=False)

    calls = {"decode": [lambda: eng._chunk_jit(
        eng.params, *eng.chunk_args(np.zeros((eng.n_slots,), bool)),
        n_steps=eng.chunk, greedy=True)], "lane": [lane(True)]}
    if min(eng.p_chunk + 1, eng.max_len - 2) > eng.p_chunk:
        calls["lane"].append(lane(False))
    names = {}
    for key, fns in calls.items():
        names[key] = []
        for i, fn in enumerate(fns):
            d = os.path.join(trace_dir, f"probe_{key}_{i}")
            jax.block_until_ready(fn())
            jax.profiler.start_trace(d)
            jax.block_until_ready(fn())
            jax.profiler.stop_trace()
            names[key].append(tracing.longest_program(tracing.load(d)))
            shutil.rmtree(d, ignore_errors=True)
    if set(names["decode"]) & set(names["lane"]):
        return {}       # the trace does not tell the programs apart
    return names


def requests(cell, seed: int, seconds: float, vocab: int):
    from repro.serving import Request
    return [Request(uid=d.uid, tokens=d.prompt, max_new=d.max_new,
                    arrival_time=d.arrival)
            for d in traffics.generate(cell.traffic, vocab, seed, seconds)]


def _stat(values, which: str) -> float:
    """``mean`` or ``p<q>``, the q-th percentile (nearest rank)."""
    if which == "mean":
        return statistics.fmean(values)
    return serving.percentile(values, int(which[1:]))


def end_to_end(cell, w, setup_s: float) -> dict:
    """The cell's end-to-end metrics from the window (harness clock).
    ``ttft_<stat>_ms`` and ``tpot_<stat>_ms`` are the mean (``mean``) or
    the q-th percentile (``p<q>``) over every request sent in the
    window."""
    out = {}
    first, fin = w.times("prefill-done"), w.finishes()
    end = w.serve_s
    for m in cell.end_to_end:
        name = m["name"]
        tail = re.fullmatch(r"(ttft|tpot)_(mean|p\d+)_ms", name)
        if name == "setup_s":
            out[name] = setup_s
        elif name == "output_tok_s":
            out[name] = (w.tokens_at(w.end) - w.tokens_at(w.start)) \
                / w.seconds
        elif tail and tail.group(1) == "ttft":
            # a request that never got its first token counts with the
            # time to the end of the serve, a bound below its latency
            ttft = [first.get(u, end) - d for u, d in w.due.items()]
            out[name] = 1e3 * _stat(ttft, tail.group(2))
        elif tail:
            # a request cut by the drain counts its gaps until the cut
            tpot = []
            for u, d in w.due.items():
                t_last, n, _ = fin.get(u, (end, 0, None))
                t_first = first.get(u, d)
                tpot.append((t_last - t_first) / max(n - 1, 1))
            out[name] = 1e3 * _stat(tpot, tail.group(2))
    return out


def counts(w) -> tuple:
    """(attempted, failed): requests sent in the window; those that did
    not end OK (the drain's cut, a quarantine) or never started."""
    status = {r.uid: r.status for r in w.results}
    started = w.times("prefill-start")
    if any(d > 0 for d in w.due.values()):
        sent = [u for u, d in w.due.items() if d < w.seconds]
        failed = [u for u in sent if status.get(u) != "OK"]
        return len(sent), len(failed)
    sent = [u for u in w.due if u in started]
    failed = [u for u in sent if status.get(u) == "FAILED"]
    return len(sent), len(failed)


def sample(cell, w, seed: int, eng_prompts: dict):
    """A seeded sample of the served requests, the longest among them, of
    about ``sample_tokens`` served tokens in at most ``batch`` requests."""
    c = cell.traffic["correct"]
    done = [r for r in w.results if r.n_generated >= 1]
    if not done:
        return []
    done.sort(key=lambda r: (-r.n_generated, r.uid))
    rng = traffics.rng_for(seed, "sample")
    rest = done[1:]
    order = [done[0]] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for r in order:
        if len(out) == c["batch"] or n >= c["sample_tokens"]:
            break
        out.append((eng_prompts[r.uid], r.tokens))
        n += r.n_generated
    return out


def write_requests(cell, seed, trace, w) -> None:
    """Per-request times, the harness's beside the engine's own."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / f"{cell.name}.{seed}.{trace}.jsonl"
    start, first, fin = (w.times("prefill-start"), w.times("prefill-done"),
                         w.finishes())
    with open(path, "w") as f:
        for r in w.results:
            d = w.due.get(r.uid)
            f.write(json.dumps({
                "uid": r.uid, "status": r.status, "n": r.n_generated,
                "due": d, "admit": start.get(r.uid),
                "first": first.get(r.uid), "last": fin.get(r.uid, (None,))[0],
                "harness_ttft": (first[r.uid] - d) if r.uid in first else None,
                "engine_ttft": r.ttft, "harness_queue":
                (start[r.uid] - d) if r.uid in start else None,
                "engine_queue_delay": r.queue_delay}) + "\n")
        f.write(json.dumps({"samples": w.samples, "window":
                            [w.start, w.end], "serve_s": w.serve_s}) + "\n")


def setup(cell, seed: int, seconds: float, devices):
    """Build, warm up and make the traffic: everything ``setup_s`` times.
    Returns the engine and the requests."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cfg, policy, params, mesh = build_params(cell, seed, devices)
    eng = make_engine(cell, cfg, policy, params, mesh)
    warm(eng)
    return eng, requests(cell, seed, seconds, cfg.vocab)


def check(cell, seed: int, w, prompts: dict, control: bool = False,
          witness: bool = False):
    """Compare a seeded sample of the served requests with the reference.
    Every number of ``limits/<cell>.json`` is held to its limit.  With
    ``control`` the numbers held to the limits are the control's (the
    reference in the next precision down put in the program's place), so
    a sound limit makes the run not correct; the program's own readings
    are then returned beside them.  ``witness`` adds the readings of the
    reference rounded to bfloat16.  Returns (correct, {number: {value,
    limit}}, the reference's readings)."""
    seqs = sample(cell, w, seed, prompts)
    if not seqs:
        return False, {"served_requests": {"value": 0, "limit": 1}}, {}
    c = cell.traffic["correct"]
    streams = ("control",) * control + ("bf16", "bf16_all") * witness
    got = cell.reference().widest_gaps(
        cell.config["model"], seed, seqs, seq_len=c["seq_len"],
        batch=c["batch"], streams=streams)
    got["requests"] = len(seqs)
    judged = "control_" if control else ""
    checks = {k: {"value": got.pop(judged + k), "limit": v}
              for k, v in specs.limits(cell).items()}
    return all(x["value"] <= x["limit"] for x in checks.values()), checks, got


def run_cell(cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, control: bool = False,
             witness: bool = False) -> dict:
    """Everything after the look for a chip; returns the result object."""
    import jax
    eng, reqs = setup(cell, seed, seconds, devices)
    prompts = {r.uid: r.tokens for r in reqs}
    setup_s = time.perf_counter() - t_start

    capture, tmp, programs = None, None, {}
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        programs = probe_programs(eng, tmp)
        tr = cell.traffic["trace"]
        capture = tracing.Capture(tmp, tr["start_s"], tr["length_s"])
    w = serving.drive(eng, reqs, cell.traffic["window"], seconds,
                      on_tick=capture.tick if capture else None)
    if capture:
        capture.finish()
    jax.block_until_ready(eng.cache)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    kind = devices[0].device_kind
    attempted, failed = counts(w)
    write_requests(cell, seed, int(trace), w)
    del eng
    gc.collect()

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        evs = tracing.load(tmp)
        red = tracing.reduce(evs, capture.window_s, span=tracing.WINDOW_SPAN)
        del evs
        shutil.rmtree(tmp, ignore_errors=True)
        ctx = Context(cell, w, red, capture, kind, peak,
                      {u: len(t) for u, t in prompts.items()})
        ctx.programs = programs
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device = {"busy_s": red.busy_s, "window_s": red.window_s}
        result["breakdown"] = tracing.breakdown(red)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in end_to_end(cell, w, setup_s).items()}
        device = {}
    result["metrics"] = metrics
    result["device"] = {"platform": devices[0].platform, "kind": kind,
                        "count": len(devices), "memory_peak_bytes": peak,
                        **device}
    ok, checks, got = check(cell, seed, w, prompts, control, witness)
    result["correct"] = ok
    result["reference"] = got
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="hold the lower-precision control, in the "
                         "program's place, to the limits (calibration "
                         "only; not part of a benchmark run)")
    ap.add_argument("--witness", type=int, choices=(0, 1), default=0,
                    help="also read the reference rounded to bfloat16, "
                         "matmul inputs and every activation (calibration "
                         "only)")
    args = ap.parse_args(argv)
    cell = specs.load_cell(args.workload)
    devices = require_chips(cell.chips)
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   T_START, control=bool(args.control),
                   witness=bool(args.witness))
    judged = "control " if args.control else ""
    for k, v in res["checks"].items():
        print(f"check {judged}{k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
