"""Benchmark harness — one module per paper table/figure.

  table1       — sample complexity (iterations-to-ε) per aggregator/α/m
  aggregators  — per-iteration per-machine work (wall time) incl. kernels
  filtering    — Claim 3.5 detection latency / false-positive behaviour
  lower_bound  — Theorems 5.4/5.5 distinguishing-success curves
  scenarios    — dynamic-adversary campaigns (one-jit grid) → BENCH_scenarios.json
  train        — scan-vs-loop driver wall-clock + LM train campaigns → BENCH_train.json
  roofline     — deliverable (g) table from the dry-run records

Prints ``name,us_per_call,derived`` CSV.  Select suites with
``python -m benchmarks.run [suite ...]``; default runs all.
"""
import sys

from repro.launch.compile_cache import enable_compile_cache


SUITES = ["table1", "aggregators", "filtering", "lower_bound", "ablation",
          "scenarios", "train", "roofline"]


def main() -> None:
    selected = sys.argv[1:] or SUITES
    print("name,us_per_call,derived")
    for suite in selected:
        if suite not in SUITES:
            raise SystemExit(f"unknown suite {suite!r}; have {SUITES}")
        mod = __import__(f"benchmarks.bench_{suite}", fromlist=["main"])
        mod.main()


if __name__ == "__main__":
    enable_compile_cache()
    main()
