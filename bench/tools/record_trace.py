#!/usr/bin/env python3
"""Record a cell's traced window as events, the fixture of the trace test.

    python bench/tools/record_trace.py --workload wlcg-prod.presim-leap \
        --seconds 0.2 --seed 1 --out bench/tests/data/trace_<name>.json

Runs the cell's set-up and a traced window as ``run.py --trace 1`` does,
and writes the events the reduction reads (device operations and
programs, the window, the generator's host spans) with the names of the
trace's device lines. Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    jax = bench_run._setup_jax()
    from harness import manifest
    from harness import trace as trace_lib

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 3
    cell = manifest.find_cell(args.workload)
    tracer = trace_lib.Tracer(bench_run.TRACE_DIR, True)
    gen = manifest.generator(cell).Generator(cell, args.seed, tracer)
    gen.setup(args.seconds)
    with tracer.window():
        gen.window(args.seconds, time.perf_counter)
    events = tracer.events(gen.spans)
    reduced = trace_lib.reduce(events, devices=range(cell.chips))
    rec = {"workload": args.workload, "chips": cell.chips,
           "device_kind": jax.devices()[0].device_kind, "device_lines": tracer.lines,
           "ops_in_window": reduced.n_ops, "events": trace_lib.to_json(events)}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f)
    print(json.dumps({"events": len(events), "ops_in_window": reduced.n_ops,
                      "window_s": reduced.window_s, "busy_s": reduced.busy_s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
