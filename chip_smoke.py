"""Serve Llama-3-8B at full width on a TPU through the continuous engine.

The quickest proof that the serving path runs on the chip: NxFP4 weights
and NxFP4 KV cache, Pallas kernels compiled for Mosaic, the published
``configs/llama3_8b.py`` widths (32 layers, d_model 4096, 32/8 heads,
d_ff 14336, vocab 128256).  Weights are random, made from ``--seed`` one
layer at a time (``models.init_cast_params``), so no float32 copy of the
layer stack ever exists on the device.

  python chip_smoke.py             # one chip: 8 slots serve 8 requests
  python chip_smoke.py --chips 4   # slot-sharded engine on 4 chips,
                                   # token for token vs the one-chip engine

Progress lines go to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits nonzero, without that line, when JAX finds no TPU or
any phase fails.  Every time it prints is this run's, on the device named
in its first line.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.llama3_8b import CONFIG  # noqa: E402
from repro.core.qtensor import (QTensor, QuantPolicy,  # noqa: E402
                                tree_footprint_bytes)
from repro.kernels import ops  # noqa: E402
from repro.kernels.ref import qmatmul_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.models import init_cast_params  # noqa: E402
from repro.serving import ContinuousEngine, Request  # noqa: E402
from repro.serving.sharded import ShardedContinuousEngine  # noqa: E402

POLICY = QuantPolicy(weight_fmt="nxfp4", kv_fmt="nxfp4")
N_SLOTS, MAX_LEN, P_CHUNK, MAX_NEW = 8, 2048, 256, 32
PROMPT_LENS = (64, 1024)
# Pallas GEMM vs the XLA reference, as max|diff| / max|ref|: both decode
# to the same bf16 operands and accumulate in f32, so only the summation
# order differs (1.6e-7 on a TPU v5e at seed 0); 1e-3 allows that alone.
LAYER_TOL = 1e-3
GiB = 2 ** 30


def log(msg: str) -> None:
    print(msg, flush=True)


def mem(dev, key: str = "bytes_in_use") -> int:
    return dev.memory_stats()[key]


def make_requests(cfg, n: int, seed: int):
    """``n`` greedy requests, prompt lengths uniform in PROMPT_LENS."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=n)
    return [Request(uid=i, tokens=rng.integers(0, cfg.vocab, size=int(t))
                    .astype(np.int32), max_new=MAX_NEW)
            for i, t in enumerate(lens)]


def build_params(cfg, seed: int, mesh=None):
    t0 = time.perf_counter()
    params = init_cast_params(cfg, jax.random.PRNGKey(seed), POLICY,
                              mesh=mesh)
    jax.block_until_ready(params)
    layers = tree_footprint_bytes(params["layers"])
    n_layer = cfg.param_count() - 2 * cfg.vocab * cfg.d_model
    log(f"weights: layer stack {layers / GiB:.3f} GiB "
        f"({layers * 8 / n_layer:.3f} bits/param), all weights "
        f"{tree_footprint_bytes(params) / GiB:.3f} GiB with dense bf16 "
        f"embedding and head; built in {time.perf_counter() - t0:.1f} s "
        f"(this run)")
    return params


def check_layer(params, seed: int) -> None:
    """One real layer's Pallas GEMM vs the XLA reference, on the chip."""
    w = jax.tree.map(lambda a: a[0], params["layers"]["mlp_w1"])
    assert isinstance(w, QTensor)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (N_SLOTS, w.packed.shape[1] * 32), jnp.bfloat16)
    got = ops.qmatmul(x, w, impl="pallas")
    want = qmatmul_ref(x, w.packed, w.meta, w.fmt)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    log(f"layer check: layer 0 mlp_w1 {tuple(x.shape)} x "
        f"{w.shape[-2:]} nxfp_matmul_pallas vs qmatmul_ref: "
        f"max|diff|/max|ref| = {err:.3e} (tolerance {LAYER_TOL:.0e})")
    if not err <= LAYER_TOL:
        raise AssertionError(f"layer check {err:.3e} > {LAYER_TOL:.0e}")


def serve(eng, reqs):
    """Serve ``reqs``; returns ({uid: tokens}, wall seconds, tokens)."""
    t0 = time.perf_counter()
    results = eng.serve(reqs)
    wall = time.perf_counter() - t0
    bad = [(r.uid, r.status) for r in results
           if not r.ok or r.n_generated != MAX_NEW]
    if bad:
        raise AssertionError(f"requests not served in full: {bad}")
    return ({r.uid: np.asarray(r.tokens) for r in results}, wall,
            sum(r.n_generated for r in results))


def warm(eng, label: str) -> None:
    """Compile every program a serve dispatches (both lane variants,
    the first-token finish, the decode chunk, the eviction reset)."""
    t0 = time.perf_counter()
    eng.serve([Request(uid=-1, tokens=np.zeros((P_CHUNK + 8,), np.int32),
                       max_new=2)])
    log(f"{label}: compile + warm-up {time.perf_counter() - t0:.1f} s "
        f"(this run)")


def same_streams(a, b, what: str) -> None:
    diff = [u for u in a if not np.array_equal(a[u], b[u])]
    if diff or a.keys() != b.keys():
        raise AssertionError(f"{what}: streams differ for uids {diff}")
    log(f"{what}: all {len(a)} streams identical token for token")


def decode_custom_calls(eng) -> int:
    """``tpu_custom_call`` count in the compiled decode-chunk program."""
    args = eng.chunk_args(np.zeros((eng.n_slots,), bool))
    hlo = eng._chunk_jit.lower(eng.params, *args, n_steps=eng.chunk,
                               greedy=True).compile().as_text()
    return hlo.count("tpu_custom_call")


def time_warm(fn, n: int = 5):
    """Sorted wall seconds of ``n`` warm calls of ``fn``, each waited on."""
    jax.block_until_ready(fn())
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return sorted(out)


def time_programs(eng) -> None:
    """The two programs a serve loop dispatches, each alone and warm, on
    the engine's own params and cache (results discarded).  Both kernels
    read every context row whatever the slot lengths, so an idle batch
    costs what a full one does."""
    args = eng.chunk_args(np.zeros((eng.n_slots,), bool))
    t = time_warm(lambda: eng._chunk_jit(eng.params, *args,
                                         n_steps=eng.chunk, greedy=True))
    log(f"decode chunk alone ({eng.n_slots} rows x {eng.chunk} steps, "
        f"{MAX_LEN} context rows): median {t[2] * 1e3:.1f} ms = "
        f"{t[2] / eng.chunk * 1e3:.2f} ms per step, 5 calls "
        f"{t[0] * 1e3:.1f}-{t[-1] * 1e3:.1f} ms (this run)")
    toks = jnp.zeros((1, P_CHUNK), jnp.int32)
    t = time_warm(lambda: eng._lane_fn(
        eng.params, toks, eng.cache, eng.lane, jnp.int32(0), jnp.int32(0),
        jnp.int32(P_CHUNK), with_head=False, wrapped=False))
    log(f"prefill-lane chunk alone (1 x {P_CHUNK} tokens, no head): median "
        f"{t[2] * 1e3:.1f} ms, 5 calls {t[0] * 1e3:.1f}-{t[-1] * 1e3:.1f} "
        f"ms (this run)")


def engine(cfg, params, n_slots: int, mesh=None):
    kw = dict(n_slots=n_slots, max_len=MAX_LEN, prefill_mode="chunked",
              p_chunk=P_CHUNK)
    if mesh is None:
        return ContinuousEngine(cfg, params, POLICY, **kw)
    return ShardedContinuousEngine(cfg, params, POLICY, mesh, **kw)


def one_chip(cfg, seed: int) -> None:
    params = build_params(cfg, seed)
    check_layer(params, seed)
    eng = engine(cfg, params, N_SLOTS)
    warm(eng, "engine")
    n = decode_custom_calls(eng)
    log(f"decode chunk program: {n} tpu_custom_call")
    if n <= 0:
        raise AssertionError("decode program runs no Pallas kernel")
    reqs = make_requests(cfg, N_SLOTS, seed)
    log(f"requests: {len(reqs)} prompts of "
        f"{sorted(len(r.tokens) for r in reqs)} tokens, max_new {MAX_NEW}")
    first, wall, toks = serve(eng, reqs)
    log(f"serve 1: {toks} tokens in {wall:.2f} s = {toks / wall:.1f} "
        f"tok/s end to end, prefill included (this run)")
    second, wall, toks = serve(eng, make_requests(cfg, N_SLOTS, seed))
    log(f"serve 2: {toks} tokens in {wall:.2f} s = {toks / wall:.1f} "
        f"tok/s end to end, prefill included (this run)")
    same_streams(first, second, "serve 1 vs serve 2")
    kv = tree_footprint_bytes(eng.cache)
    peak = mem(jax.devices()[0], "peak_bytes_in_use")
    log(f"KV cache + slot state: {kv / GiB:.3f} GiB; peak_bytes_in_use "
        f"{peak / GiB:.3f} GiB")
    time_programs(eng)


def four_chips(cfg, seed: int, n_chips: int) -> None:
    n_slots = 4 * n_chips
    reqs = make_requests(cfg, n_slots, seed)
    # the one-chip engine runs the per-shard slot count, so both sides
    # decode the same (slots_per_shard)-row programs
    params = build_params(cfg, seed)
    eng = engine(cfg, params, n_slots // n_chips)
    warm(eng, "one-chip engine")
    ref, wall, toks = serve(eng, reqs)
    log(f"one-chip engine: {toks} tokens in {wall:.2f} s (this run)")
    del eng, params
    gc.collect()
    log(f"device 0 after freeing the one-chip engine: "
        f"{mem(jax.devices()[0]) / GiB:.3f} GiB")

    mesh = make_serving_mesh(n_chips)
    params = build_params(cfg, seed, mesh)
    eng = engine(cfg, params, n_slots, mesh)
    warm(eng, f"{n_chips}-chip sharded engine")
    got, wall, toks = serve(eng, make_requests(cfg, n_slots, seed))
    log(f"{n_chips}-chip sharded engine: {toks} tokens in {wall:.2f} s "
        f"(this run)")
    same_streams(ref, got, f"{n_chips}-chip sharded vs one-chip")
    used = [mem(d) for d in mesh.devices.flat]
    log("bytes_in_use per device: "
        + ", ".join(f"{u / GiB:.3f} GiB" for u in used))
    if max(used) > 1.1 * min(used):
        raise AssertionError(f"uneven placement across the mesh: {used}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the slot-sharded path and the "
                         "one-chip engine it is compared with")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {enable_compile_cache()}")
    cfg = CONFIG
    log(f"model: {cfg.name} {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}; weights {POLICY.weight_fmt}, KV {POLICY.kv_fmt}")
    if args.chips == 1:
        one_chip(cfg, args.seed)
    else:
        four_chips(cfg, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
