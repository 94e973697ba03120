"""The harness finds every piece by name, and refuses to run off a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"


def test_benchmark_json_pieces_exist_by_name():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert "engine" in cell.traffic
        assert all(v > 0 for v in spec.limits(cell).values())
        assert callable(cell.reference().widest_gaps)
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)


def test_a_new_cell_is_found_without_editing_a_file(tmp_path):
    for d in ("configs", "traffic", "metrics", "limits"):
        (tmp_path / d).mkdir()
    shutil.copytree(BENCH / "reference", tmp_path / "reference")
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "reference": "dense_gqa", "model": {"n_layers": 1}}))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps(
        {"arrivals": {"kind": "backlog", "requests": 3},
         "engine": {"n_slots": 2}}))
    (tmp_path / "metrics" / "toy_count.x.py").write_text(
        "def read(ctx):\n    return ctx['n'] * 2\n")
    (tmp_path / "limits" / "toy.burst.json").write_text(json.dumps(
        {"widest_gap": {"limit": 0.5}}))
    bm = {"workloads": [{"name": "toy.burst", "config": "toy",
                         "traffic": "burst", "chips": 1}],
          "end_to_end": [{"name": "setup_s"}],
          "per_layer": [{"name": "toy_count.x", "workloads": ["toy.burst"]},
                        {"name": "elsewhere", "workloads": ["other"]}]}
    cell = spec.load_cell("toy.burst", tmp_path, bm)
    assert cell.traffic["arrivals"]["requests"] == 3
    assert [m["name"] for m in cell.per_layer] == ["toy_count.x"]
    assert cell.reader("toy_count.x")({"n": 21}) == 42
    assert spec.limits(cell) == {"widest_gap": 0.5}
    with pytest.raises(KeyError):
        spec.load_cell("toy.other", tmp_path, bm)


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_refuses_to_run_off_a_tpu():
    r = _run(REPO, "--workload", "danube3-4b.chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_fails_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path, "--workload", "danube3-4b.chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_end_to_end_takes_means_and_percentiles_over_every_request():
    from bench import run, serve
    w = serve.Window(seconds=10.0, end=10.0, serve_s=20.0)
    w.due = {0: 0.0, 1: 2.0, 2: 4.0, 3: 6.0}
    # first tokens at 1, 4 and 5 s; request 3 never got one (counts to
    # the serve's end, 20 s); last tokens give 0.5, 0.25 and 1 s a token
    w.events = [(1.0, {"event": "prefill-done", "uid": 0}),
                (4.0, {"event": "prefill-done", "uid": 1}),
                (5.0, {"event": "prefill-done", "uid": 2}),
                (3.0, {"event": "finish", "uid": 0, "n": 5}),
                (5.0, {"event": "finish", "uid": 1, "n": 5}),
                (8.0, {"event": "finish", "uid": 2, "n": 4})]
    w.samples = [(5.0, 40, 4), (10.0, 100, 4)]
    names = ["ttft_mean_ms", "ttft_p50_ms", "tpot_p50_ms", "tpot_mean_ms",
             "output_tok_s", "setup_s"]
    cell = spec.Cell(name="x", chips=1, config={}, traffic={},
                     end_to_end=[{"name": n} for n in names], per_layer=[],
                     bench_dir=BENCH)
    got = run.end_to_end(cell, w, 12.5)
    ttft = [1.0, 2.0, 1.0, 14.0]
    assert got["ttft_mean_ms"] == pytest.approx(1e3 * sum(ttft) / 4)
    assert got["ttft_p50_ms"] == pytest.approx(1e3 * 1.0)
    # request 3: no first token, cut at the serve's end: (20 - 6) / 1
    tpot = [0.5, 0.25, 1.0, 14.0]
    assert got["tpot_p50_ms"] == pytest.approx(1e3 * 0.5)
    assert got["tpot_mean_ms"] == pytest.approx(1e3 * sum(tpot) / 4)
    assert got["output_tok_s"] == pytest.approx(10.0)
    assert got["setup_s"] == 12.5
