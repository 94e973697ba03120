"""The correctness check of a run, driven on the CPU at a tiny size.

The harness's look for a chip is skipped; the rest of a run -- set-up, the
window through ``ContinuousEngine.serve``, the sample and the reference --
is the code the chip runs.  The served tokens must pass; the reference in
the next precision down (the control), held to the same limit in the
program's place, and the program with its timed path broken underneath
must fail.

The tiny model's logits spread far less than a full-width one's, so the
limit on the mean gap, the number the cells compare, is its own here:
1e-4.  The program reads 0 at this seed (the CPU path agrees with the
reference token for token), the control 3.0e-4 (its widest gap 0.0067).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, serve, spec

BENCH = Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 77
SECONDS = 3.0
LIMIT = 1e-4
MODEL = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
         "n_kv_heads": 2, "head_dim": 0, "d_ff": 128, "vocab": 256,
         "rope_theta": 10000.0, "norm_eps": 1e-5, "weight_fmt": "nxfp4", "kv_fmt": "nxfp4"}
TRAFFIC = {"arrivals": {"kind": "poisson", "rate_per_s": 4.0},
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 4, "max": 48},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16},
           "engine": {"class": "ContinuousEngine", "prefill_mode": "chunked",
                      "n_slots": 4, "max_len": 64, "chunk": 4, "p_chunk": 16},
           "window": {"stop": "drain", "drain_s": 30},
           "trace": {"start_s": 0.2, "length_s": 0.5},
           "correct": {"sample_tokens": 40, "batch": 4, "seq_len": 64}}


def tiny_cell(d: Path, engine: str = "ContinuousEngine", chips: int = 1):
    """The tiny cell, its files written under ``d``; the engine by class
    name, as a traffic file names it."""
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir()
    (d / "reference").symlink_to(BENCH / "reference")
    (d / "metrics").symlink_to(BENCH / "metrics")
    (d / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "reference": "dense_gqa", "model": MODEL}))
    traffic = {**TRAFFIC, "engine": {**TRAFFIC["engine"], "class": engine}}
    (d / "traffic" / "chat.json").write_text(json.dumps(traffic))
    (d / "limits" / "tiny.chat.json").write_text(json.dumps(
        {"mean_gap": {"limit": LIMIT}}))
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                        "traffic": "chat", "chips": chips}]
    return spec.load_cell("tiny.chat", d, bm)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    cell = tiny_cell(tmp_path_factory.mktemp("bench"))
    eng, reqs = run.setup(cell, SEED, SECONDS, jax.devices()[:1])
    return cell, eng, reqs


def serve_and_check(tiny, control=False, witness=False):
    cell, eng, reqs = tiny
    w = serve.drive(eng, reqs, cell.traffic["window"], SECONDS)
    return run.check(cell, SEED, w, {r.uid: r.tokens for r in reqs},
                     control, witness)


def test_served_tokens_pass_and_the_control_fails(tiny):
    ok, checks, got = serve_and_check(tiny)
    assert ok, checks
    assert checks["mean_gap"]["value"] <= LIMIT
    assert got["tokens"] >= 30 and got["requests"] >= 2
    # the control, held to the same limit in the program's place
    ok, checks, got = serve_and_check(tiny, control=True, witness=True)
    assert not ok and checks["mean_gap"]["value"] > LIMIT
    assert got["mean_gap"] <= LIMIT        # the program's own, beside it
    # the reference rounded to bfloat16 is read beside them, not judged
    for n in ("bf16", "bf16_all"):
        assert 0 <= got[f"{n}_mean_gap"] < checks["mean_gap"]["value"]


def test_an_altered_token_fails(tiny, monkeypatch):
    eng = tiny[1]
    real = eng._dispatch_chunk

    def altered(poison):
        emitted, finite = real(poison)
        return (np.asarray(emitted) + 1) % MODEL["vocab"], finite

    monkeypatch.setattr(eng, "_dispatch_chunk", altered)
    ok, checks, _ = serve_and_check(tiny)
    assert not ok and checks["mean_gap"]["value"] > LIMIT


def test_a_step_that_keeps_its_state_fails(tiny, monkeypatch):
    eng = tiny[1]
    real = eng._chunk_jit

    def frozen(params, tok, cache, keys, *args, **kw):
        out = real(params, tok, cache, keys, *args, **kw)
        # the decode chunk hands back the state it was given (entering
        # token, cache, keys); only its emissions and counters move on
        return (out[0], tok, jax.tree.map(jnp.copy, cache), keys) + out[4:]

    monkeypatch.setattr(eng, "_chunk_jit", frozen)
    ok, checks, _ = serve_and_check(tiny)
    assert not ok and checks["mean_gap"]["value"] > LIMIT


SHARDED = """
import json, sys
from pathlib import Path
import jax
from bench import run, serve
from bench.tests.test_bench_correct import SECONDS, SEED, tiny_cell
cell = tiny_cell(Path(sys.argv[1]), "ShardedContinuousEngine", chips=2)
eng, reqs = run.setup(cell, SEED, SECONDS, jax.devices()[:2])
w = serve.drive(eng, reqs, cell.traffic["window"], SECONDS)
ok, checks, got = run.check(cell, SEED, w, {r.uid: r.tokens for r in reqs})
print(json.dumps({"engine": type(eng).__name__, "shards": eng.n_shards,
                  "ok": ok, "checks": checks, "tokens": got["tokens"]}))
"""


def test_a_sharded_engine_is_named_by_the_traffic_file(tmp_path):
    """A cell on more chips needs only files: the traffic file names the
    slot-sharded engine, and the harness gives it a mesh over the cell's
    chips (two host devices here, in a process of their own)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(BENCH.parent / "src"),
                                           str(BENCH.parent)]))
    r = subprocess.run([sys.executable, "-c", SHARDED, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["engine"] == "ShardedContinuousEngine" and got["shards"] == 2
    assert got["ok"] and got["tokens"] >= 30, got
