"""Benchmark driver: one function per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (plus per-benchmark summary blocks).

Fast benches (overhead, kernels) always run and their rows are persisted
to results/bench/BENCH_arrival.json (appending one entry per run, so the
arrival-path perf trajectory accumulates across PRs; histories from the
legacy repo-root location are carried forward automatically); the
paper-reproduction training benches run with reduced budgets by default
(pass --full for the paper-scale budgets used in EXPERIMENTS.md).
``benchmarks.check_regression`` gates the latest entries against
committed baselines (``make bench-check``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Canonical location: results/ (one place for CI artifacts + local runs).
BENCH_DIR = os.environ.get("REPRO_BENCH_DIR",
                           os.path.join(_ROOT, "results", "bench"))
BENCH_JSON = os.path.join(BENCH_DIR, "BENCH_arrival.json")
BENCH_RUNTIME_JSON = os.path.join(BENCH_DIR, "BENCH_runtime.json")
BENCH_SCALE_JSON = os.path.join(BENCH_DIR, "BENCH_scale.json")
# Pre-PR-3 location (repo root): read-only fallback so accumulated
# histories carry forward without symlinks.
_LEGACY = {BENCH_JSON: os.path.join(_ROOT, "BENCH_arrival.json"),
           BENCH_RUNTIME_JSON: os.path.join(_ROOT, "BENCH_runtime.json")}


def _load_history(path) -> list:
    for candidate in (path, _LEGACY.get(path, "")):
        if candidate and os.path.exists(candidate):
            try:
                with open(candidate) as f:
                    return json.load(f)
            except (json.JSONDecodeError, OSError):
                return []
    return []


def _persist(rows, path=BENCH_JSON) -> None:
    history = _load_history(path)
    history.append({"unix_time": time.time(), "rows": rows})
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1)
    os.replace(tmp, path)
    print(f"# persisted {len(rows)} rows -> {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale budgets (slow)")
    ap.add_argument("--skip-training", action="store_true",
                    help="only micro-benchmarks")
    ap.add_argument("--runtime", action="store_true",
                    help="wall-clock runtime benchmark (simulator vs "
                         "threaded ConcurrentRuntime) -> BENCH_runtime.json")
    ap.add_argument("--scale", action="store_true",
                    help="batched-arrival scale benchmark (launch "
                         "contracts, N in {64,1k,10k} bookkeeping, "
                         "transfer probe) -> BENCH_scale.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.scale:
        from benchmarks import bench_scale
        print("name,us_per_call,derived")
        rows = bench_scale.run()
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        _persist(rows, BENCH_SCALE_JSON)
        return

    if args.runtime:
        from benchmarks import bench_runtime
        outer, inner = (24, 8) if args.full else (12, 3)
        print("name,us_per_call,derived")
        rows = bench_runtime.run(outer, inner)
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        print("\n" + bench_runtime.summarize(rows))
        _persist(rows, BENCH_RUNTIME_JSON)
        return

    print("name,us_per_call,derived")
    from benchmarks import bench_kernels, bench_overhead
    micro = bench_overhead.run() + bench_kernels.run()
    for r in micro:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    _persist(micro)
    sys.stdout.flush()

    if args.skip_training:
        return

    outer, inner = (60, 15) if args.full else (24, 6)
    t0 = time.time()
    from benchmarks import (bench_convergence, bench_drop_stale,
                            bench_language, bench_pace_table)

    print(f"\n# Fig.2 convergence (outer={outer} inner={inner})")
    print(bench_convergence.summarize(bench_convergence.run(outer, inner)))
    sys.stdout.flush()

    print(f"\n# Table 1 pace sweep")
    cfgs = bench_pace_table.PACE_CONFIGS if args.full else \
        bench_pace_table.PACE_CONFIGS[:4]
    print(bench_pace_table.summarize(
        bench_pace_table.run(outer, inner, cfgs), cfgs))
    sys.stdout.flush()

    print(f"\n# Fig.3 per-language")
    print(bench_language.summarize(bench_language.run(outer, inner)))
    sys.stdout.flush()

    print(f"\n# Fig.8 drop-stale ablation")
    print(bench_drop_stale.summarize(bench_drop_stale.run(
        outer if args.full else 16, inner)))
    print(f"\n# total bench wall time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
