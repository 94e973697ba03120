"""Benchmark runner — one entry per paper table/figure + kernels + serving.

Prints ``name,us_per_call,derived`` CSV (one row per measurement) and
writes a machine-readable ``BENCH_summary.json`` at the repo root
(per-benchmark key -> {value, unit, variant}) so the perf trajectory is
comparable across PRs; CI uploads it as an artifact.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig8,table1
  PYTHONPATH=src python -m benchmarks.run --quick --only kernels,serving
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache

from .common import Csv

_SUITES = ["fig3", "fig8", "table1", "fig9", "fig10", "fig11", "fig12",
           "kernels", "serving", "roofline"]

SUMMARY_PATH = Path(__file__).resolve().parents[1] / "BENCH_summary.json"


def write_summary(csv: Csv, path: Path = SUMMARY_PATH) -> None:
    """Snapshot the collected rows as {name: {value, unit, variant}}."""
    summary = {name: {"value": us, "unit": unit, "variant": derived}
               for name, us, derived, unit in csv.rows}
    path.write_text(json.dumps(summary, indent=1, sort_keys=True) + "\n")
    print(f"[run] wrote {len(summary)} rows to {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(_SUITES))
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: shrink benchmark shapes (sets "
                         "NXFP_BENCH_QUICK=1 for suites that honor it)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.quick:
        os.environ["NXFP_BENCH_QUICK"] = "1"
    only = args.only.split(",") if args.only else _SUITES

    csv = Csv()
    print("name,us_per_call,derived")
    failures = []
    for suite in only:
        try:
            if suite == "fig3":
                from . import fig3_profile as m
            elif suite == "fig8":
                from . import fig8_quant_error as m
            elif suite == "table1":
                from . import table1_perplexity as m
            elif suite == "fig9":
                from . import fig9_tradeoff as m
            elif suite == "fig10":
                from . import fig10_accuracy as m
            elif suite == "fig11":
                from . import fig11_remap_sweep as m
            elif suite == "fig12":
                from . import fig12_blocksize as m
            elif suite == "kernels":
                from . import kernels_bench as m
            elif suite == "serving":
                from . import serving_bench as m
            elif suite == "roofline":
                from . import roofline as m
                m.main(csv)
                continue
            else:
                raise ValueError(suite)
            m.run(csv)
        except Exception as e:  # keep going; report at the end
            failures.append((suite, repr(e)))
            traceback.print_exc()
    write_summary(csv)
    if failures:
        print(f"FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
