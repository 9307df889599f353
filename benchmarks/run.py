"""Benchmark harness: one function per paper table/figure plus measured perf.

Prints ``name,us_per_call,derived`` CSV (per repo convention). Reduced-scale
defaults run on CPU in minutes; EXPERIMENTS.md records the scale-up knobs.
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import paper_experiments as paper
    from benchmarks import perf

    benches = [
        paper.bench_placement_regression,
        paper.bench_stagein_regression,
        paper.bench_link_timeseries,
        paper.bench_posterior_inference,
        paper.bench_validation_table,
        paper.bench_scheduler_gain,
        perf.bench_engine_throughput,
        perf.bench_engine_leap,
        perf.bench_presimulate_rate,
        perf.bench_chunked_attention,
        perf.bench_mlstm_chunked,
        perf.bench_classifier_scoring,
    ]
    print("name,us_per_call,derived")
    failures = 0
    for bench in benches:
        try:
            name, us, derived = bench()
            print(f"{name},{us:.0f},{derived:.6g}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{bench.__name__},FAILED,{type(e).__name__}: {e}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
