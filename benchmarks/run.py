"""Benchmark suite entry point: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (and an aggregate at the end).

  PYTHONPATH=src python -m benchmarks.run [--only job,lsqb,...] [--smoke]

--smoke shrinks every suite to CI scale (tiny inputs, one repeat) so the
whole run finishes in seconds-to-a-minute instead of tens of minutes.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache

SUITES = [
    "job",
    "lsqb",
    "colt",
    "vectorization",
    "robustness",
    "kernels",
    "join_perf",
    "serving",
    "streaming",
]

# per-suite kwargs for --smoke (every run() signature differs)
SMOKE_ARGS: dict[str, dict] = {
    "job": dict(scale=0.02, repeats=1),
    "lsqb": dict(sfs=(0.03,), repeats=1),
    "colt": dict(scale=0.02, repeats=1),
    "vectorization": dict(scale=0.005, repeats=1),
    "robustness": dict(scale=0.02, repeats=1),
    "kernels": dict(repeats=1),
    "join_perf": dict(smoke=True, repeats=1),
    "serving": dict(smoke=True, repeats=1),
    "streaming": dict(smoke=True, repeats=1),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--smoke", action="store_true", help="CI scale: tiny inputs, one repeat")
    args = ap.parse_args()
    enable_compile_cache()
    picks = args.only.split(",") if args.only else SUITES
    all_rows = []
    for name in picks:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        print(f"# --- {name} ---", file=sys.stderr, flush=True)
        rows = mod.run(**(SMOKE_ARGS.get(name, {}) if args.smoke else {}))
        print(f"# {name}: {time.time() - t0:.1f}s", file=sys.stderr, flush=True)
        all_rows.extend(rows)
    os.makedirs("benchmarks/results", exist_ok=True)
    emit(all_rows, path="benchmarks/results/latest.csv")


if __name__ == "__main__":
    main()
