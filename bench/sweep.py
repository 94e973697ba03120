"""Find a serving cell's deployment settings and its knee, once, on the chip.

  python3 bench/sweep.py --workload danube3-4b.chat --seed 5 \
      --chunks 4,8,16 --p-chunks 512,1024 --rates 0.5,1,1.5,2 --seconds 20

Builds the configuration once.  For each candidate decode chunk length
(``chunk``), prefill-lane chunk (``p_chunk``) and slot count it times the
two programs alone, warm, on the engine's own cache (host clock around
``block_until_ready``, median of five).  Then, for the first chunk pair
at the cell's own slot count, it serves the cell's traffic at each offered
rate for ``--seconds`` and prints the window's completed requests, output
tokens per second, the end-to-end metrics, and the median queue wait of
the window's first and last thirds of arrivals (a request never admitted
counts its wait until the serve ended): a queue that grows through the
window is past the knee.  One JSON
line per measurement on standard output; nothing here is a benchmark
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
for _p in (CHECKOUT / "src", CHECKOUT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run, serve, spec  # noqa: E402


def timed(fn, n: int = 5) -> float:
    import jax
    jax.block_until_ready(fn())
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return statistics.median(out)


def time_programs(eng) -> dict:
    import jax.numpy as jnp
    import numpy as np
    args = eng.chunk_args(np.zeros((eng.n_slots,), bool))
    dec = timed(lambda: eng._chunk_jit(eng.params, *args, n_steps=eng.chunk,
                                       greedy=True))
    toks = jnp.zeros((1, eng.p_chunk), jnp.int32)
    lane = timed(lambda: eng._lane_fn(
        eng.params, toks, eng.cache, eng.lane, jnp.int32(0), jnp.int32(0),
        jnp.int32(eng.p_chunk), with_head=False, wrapped=False))
    return {"decode_chunk_ms": dec * 1e3,
            "decode_step_ms": dec * 1e3 / eng.chunk, "lane_chunk_ms":
            lane * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--chunks", default="")
    ap.add_argument("--p-chunks", default="")
    ap.add_argument("--slots", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--drain", type=float, default=None,
                    help="seconds the requests sent may run on after the "
                         "window (default: the traffic file's)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = run.require_chips(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    built = run.build_params(cell, args.seed, devices)
    e0 = cell.traffic["engine"]
    chunks = [int(c) for c in args.chunks.split(",") if c] or [e0["chunk"]]
    pch = [int(c) for c in args.p_chunks.split(",") if c] or [e0["p_chunk"]]
    slots = [int(c) for c in args.slots.split(",") if c] or [e0["n_slots"]]
    first = None
    for b in slots:
        for c in chunks:
            for p in pch:
                eng = run.make_engine(cell, *built, n_slots=b, chunk=c,
                                      p_chunk=p)
                t = time_programs(eng)
                print(json.dumps({"slots": b, "chunk": c, "p_chunk": p, **t}),
                      flush=True)
                if first is None:
                    first = (c, p)
                del eng
                gc.collect()
    rates = [float(r) for r in args.rates.split(",") if r]
    if not rates:
        return 0
    c, p = first
    eng = run.make_engine(cell, *built, chunk=c, p_chunk=p)
    run.warm(eng)
    window = dict(cell.traffic["window"])
    if args.drain is not None:
        window.update(stop="drain", drain_s=args.drain)
    for rate in rates:
        tc = copy.deepcopy(cell)
        tc.traffic["arrivals"]["rate_per_s"] = rate
        reqs = run.requests(tc, args.seed, args.seconds, built[0].vocab)
        w = serve.drive(eng, reqs, window, args.seconds)
        e2e = run.end_to_end(tc, w, 0.0)
        start = w.times("prefill-start")
        arr = sorted(w.due.items(), key=lambda kv: kv[1])
        third = max(len(arr) // 3, 1)

        def wait(group):
            return statistics.median(start.get(u, w.serve_s) - d
                                     for u, d in group)
        ok = sum(1 for r in w.results if r.status == "OK")
        print(json.dumps({"rate": rate, "chunk": c, "p_chunk": p,
                          "sent": len(reqs), "ok": ok,
                          "serve_s": w.serve_s,
                          "wait_first_third_s": wait(arr[:third]),
                          "wait_last_third_s": wait(arr[-third:]),
                          **{k: v for k, v in e2e.items()
                             if k != "setup_s"}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
