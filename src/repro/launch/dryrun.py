import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function (train_step with AdamW +
remat + microbatching; prefill; or cached decode with direct-cast NxFP
weights and KV) against abstract inputs (ShapeDtypeStruct — nothing is
allocated), compiles it for the production mesh, and records:

  - memory_analysis(): per-device bytes (proves / disproves HBM fit)
  - cost_analysis(): HLO flops + bytes accessed
  - collective_bytes: parsed from the post-SPMD HLO text (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute), with
    ring-algorithm wire factors per op

Outputs one JSON per cell under results/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_405b \
      --shape decode_32k --mesh pod       # 16x16
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import (collective_stats as hlo_collectives,
                                       dot_flops, while_trip_counts)

from repro.configs import (ARCH_IDS, SHAPES, get_config, input_specs,
                           shape_supported)
from repro.core.qtensor import QuantPolicy, direct_cast_tree
from repro.launch.mesh import make_production_mesh
from repro.models import init_cache_specs, init_params
from repro.optim.adamw import AdamW, cosine_schedule
from repro.sharding import (batch_specs, cache_specs, params_specs,
                            shard_friendly_config, to_shardings)
from repro.sharding.ctx import activation_sharding
from repro.train.state import init_state
from repro.train.step import (make_decode_step, make_prefill_step,
                              make_train_step)

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def lower_cell(arch: str, shape: str, mesh, *, quantized: bool = True,
               n_micro: int = 8, fsdp="auto",
               grad_compress: str = "nxfp8", compress_mode: str = "shard_map",
               kv_fmt: str = "nxfp4",
               weight_fmt: str = "nxfp4", seed: int = 0):
    """Lower + compile one cell. Returns result dict."""
    tp = mesh.shape.get("model", 1)
    cfg, in_specs_d = input_specs(arch, shape)
    cfg = shard_friendly_config(cfg, tp)
    kind = SHAPES[shape]["kind"]
    key = jax.random.PRNGKey(seed)
    if fsdp == "auto":
        # FSDP weight sharding costs GSPMD reshard pathologies in the
        # backward (see EXPERIMENTS.md §Perf); enable it only when f32
        # params+grads per TP shard would exceed half of v5e HBM. Serving
        # (quantized, fwd-only) keeps 2-D sharding for the big models too.
        n = get_config(arch).param_count()
        if kind == "train":
            fsdp = (2 * 4 * n / tp) > 8 * 2 ** 30
        else:
            bpv = 0.6 if quantized else 2.0
            fsdp = (bpv * n / tp) > 8 * 2 ** 30
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    act_ctx = activation_sharding(dp_axes, dp_size)
    t0 = time.time()

    if kind == "train":
        cfg = dataclasses.replace(cfg, remat=True)
        abs_params = jax.eval_shape(lambda: init_params(cfg, key))
        optimizer = AdamW(lr=cosine_schedule(3e-4, 100, 10000),
                          moment_dtype=jnp.float32)
        abs_state = jax.eval_shape(lambda: init_state(abs_params, optimizer))
        # gradient compression across pods: the in-graph shard_map path is
        # preferred; "simulated" keeps the wire-format numerics but lets
        # GSPMD place the (dense) collective — used where this XLA build's
        # PartitionGather CHECK-crashes inside pod subgroups (DESIGN.md).
        compress_mesh = mesh if compress_mode == "shard_map" else None
        train_step, info = make_train_step(
            cfg, optimizer, n_microbatches=n_micro, mesh=compress_mesh,
            grad_compress=(grad_compress if "pod" in mesh.shape and
                           compress_mode != "off" else None))
        p_specs = params_specs(cfg, abs_params, mesh, fsdp=fsdp)
        zero_specs = params_specs(cfg, abs_params, mesh, fsdp=True)  # ZeRO-1
        from repro.optim.adamw import AdamWState
        from repro.sharding.rules import P
        state_specs = type(abs_state)(
            p_specs, AdamWState(P(), zero_specs, zero_specs), P())
        b_specs = batch_specs(mesh, in_specs_d)
        with mesh, act_ctx:
            jitted = jax.jit(
                train_step,
                in_shardings=(to_shardings(mesh, state_specs),
                              to_shardings(mesh, b_specs)),
            )
            lowered = jitted.lower(abs_state, in_specs_d)
            compiled = lowered.compile()
        extra = {"compress_mode": info["compress_mode"],
                 "n_microbatches": n_micro, "fsdp": fsdp}

    elif kind == "prefill":
        policy = QuantPolicy(weight_fmt=weight_fmt if quantized else None,
                             kv_fmt=kv_fmt if quantized else None)
        abs_params = jax.eval_shape(lambda: init_params(cfg, key))
        if quantized:
            abs_params = jax.eval_shape(
                lambda p: direct_cast_tree(p, policy), abs_params)
        max_len = SHAPES[shape]["seq_len"]
        step = make_prefill_step(cfg, max_len,
                                 kv_fmt if quantized else None)
        p_specs = params_specs(cfg, abs_params, mesh, fsdp=fsdp)
        b_specs = batch_specs(mesh, in_specs_d)
        with mesh, act_ctx:
            jitted = jax.jit(step, in_shardings=(
                to_shardings(mesh, p_specs), to_shardings(mesh, b_specs)))
            lowered = jitted.lower(abs_params, in_specs_d)
            compiled = lowered.compile()
        extra = {"quantized": quantized, "kv_fmt": kv_fmt, "fsdp": fsdp}

    else:  # decode
        # weight-stationary decode: batch-replicated matmul activations so
        # 2-D-sharded packed weights are never gathered (§Perf: -99.5%
        # collective on llama3-405B/decode_32k; memory-bound as intended)
        import repro.kernels.ops as _ops
        _ops.REPLICATED_ACT_MATMUL = True
        policy = QuantPolicy(weight_fmt=weight_fmt if quantized else None,
                             kv_fmt=kv_fmt if quantized else None)
        abs_params = jax.eval_shape(lambda: init_params(cfg, key))
        if quantized:
            abs_params = jax.eval_shape(
                lambda p: direct_cast_tree(p, policy), abs_params)
        max_len = SHAPES[shape]["seq_len"]
        b = SHAPES[shape]["global_batch"]
        abs_cache = init_cache_specs(cfg, b, max_len,
                                     kv_fmt if quantized else None)
        step = make_decode_step(cfg, kv_fmt if quantized else None)
        p_specs = params_specs(cfg, abs_params, mesh, fsdp=fsdp)
        c_specs = cache_specs(mesh, abs_cache)
        b_specs = batch_specs(mesh, in_specs_d)
        with mesh, act_ctx:
            jitted = jax.jit(step, in_shardings=(
                to_shardings(mesh, p_specs),
                to_shardings(mesh, b_specs["tokens"]),
                to_shardings(mesh, c_specs)))
            lowered = jitted.lower(abs_params, in_specs_d["tokens"],
                                   abs_cache)
            compiled = lowered.compile()
        extra = {"quantized": quantized, "kv_fmt": kv_fmt, "fsdp": fsdp}

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    hlo = compiled.as_text()
    n_dev = int(np.prod(list(mesh.shape.values())))
    coll = hlo_collectives(hlo, n_dev)

    result = {
        "arch": arch, "shape": shape,
        "mesh": dict(mesh.shape), "devices": n_dev,
        "kind": kind, "compile_seconds": round(compile_s, 1),
        "memory": {
            k: int(getattr(mem, k, 0)) for k in
            ["temp_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes"]
        },
        "cost": {k: float(v) for k, v in dict(cost).items()
                 if isinstance(v, (int, float)) and (
                     "flops" in k or "bytes" in k or "transcendentals" in k)},
        # loop-aware (trip-count-multiplied) per-device quantities
        "collectives": coll,
        "hlo_dot_flops": dot_flops(hlo),
        "loops": {"while_trip_counts": while_trip_counts(hlo)},
        **extra,
    }
    mdl = get_config(arch)
    result["model"] = {"params": mdl.param_count(),
                       "active_params": mdl.active_param_count()}
    return result


def run_one(arch: str, shape: str, mesh_name: str, *, baseline: bool,
            n_micro: int, fsdp, compress_mode: str,
            out: "Path | None") -> str:
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    tag = f"{arch}__{shape}__{mesh_name}" + ("__fp16base" if baseline else "")
    out_path = Path(out) if out else RESULTS / f"{tag}.json"
    res = lower_cell(arch, shape, mesh, quantized=not baseline,
                     n_micro=n_micro, fsdp=fsdp,
                     compress_mode=compress_mode)
    out_path.write_text(json.dumps(res, indent=1))
    mem_gb = res["memory"]["argument_size_in_bytes"] / 2 ** 30
    tmp_gb = res["memory"]["temp_size_in_bytes"] / 2 ** 30
    print(f"OK   {tag}: compile={res['compile_seconds']}s "
          f"args={mem_gb:.2f}GiB temp={tmp_gb:.2f}GiB "
          f"dot_flops={res['hlo_dot_flops']:.3e} "
          f"compress={res.get('compress_mode', '-')}")
    return tag


def _cell_subprocess(arch, shape, mesh_name, baseline, n_micro, fsdp,
                     compress_mode) -> int:
    """Isolate each cell: an XLA CHECK-abort must not kill the sweep.

    The child is pinned to the CPU: its 512 placeholder devices are host
    devices, and a chip belongs to one process at a time.
    """
    import subprocess
    import sys
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--mesh", mesh_name,
           "--n-micro", str(n_micro), "--compress-mode", compress_mode]
    if baseline:
        cmd.append("--baseline")
    if fsdp is False:
        cmd.append("--no-fsdp")
    r = subprocess.run(cmd, timeout=3000,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return r.returncode


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="lower serving cells WITHOUT quantization")
    ap.add_argument("--n-micro", type=int, default=8)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--compress-mode", default="shard_map",
                    choices=["shard_map", "simulated", "off"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    if not args.all:
        # single-cell mode (also the subprocess entry point)
        run_one(args.arch, args.shape or "train_4k", args.mesh,
                baseline=args.baseline, n_micro=args.n_micro,
                fsdp=(False if args.no_fsdp else "auto"),
                compress_mode=args.compress_mode,
                out=args.out)
        return

    shapes = [args.shape] if args.shape else list(SHAPES)
    failures = []
    for arch in ARCH_IDS[:10]:
        for shape in shapes:
            cfg = get_config(arch)
            if not shape_supported(cfg, shape):
                print(f"SKIP {arch} x {shape}: full attention at 500k "
                      f"(see DESIGN.md)", flush=True)
                continue
            rc = _cell_subprocess(arch, shape, args.mesh, args.baseline,
                                  args.n_micro,
                                  (False if args.no_fsdp else "auto"),
                                  args.compress_mode)
            if rc != 0 and shape == "train_4k" and args.mesh == "multipod" \
                    and args.compress_mode == "shard_map":
                print(f"RETRY {arch} x {shape}: shard_map compression hit "
                      f"the XLA PartitionGather bug; falling back to "
                      f"simulated wire format", flush=True)
                rc = _cell_subprocess(arch, shape, args.mesh, args.baseline,
                                      args.n_micro,
                                      (False if args.no_fsdp else "auto"),
                                      "simulated")
            if rc != 0:
                failures.append(f"{arch}__{shape}")
                print(f"FAIL {arch}__{shape}__{args.mesh} rc={rc}",
                      flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: "
                         + ", ".join(failures))


if __name__ == "__main__":
    main()
