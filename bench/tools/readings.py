#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

    python bench/tools/readings.py --workload wlcg-prod.presim-leap --seconds 5 \
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--out x.json]

For each seed: the cell's set-up and a window of ``--seconds`` at its own
size and load, then the comparison with the reference, as ``run.py``
makes it. For the control seeds the comparison is made a second time with
the control in the program's place: the reference computed in bfloat16,
the precision below the configurations' float32. The lower reading of a
number is the largest that sound runs give; the upper, the smallest that
the control gives. Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import math
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="traffic key=value (a JSON value) for these readings, e.g. "
                         "check_tuples=4096 to compare more tuples of the window; "
                         "config.<block>.<key>=value sets a configuration key")
    args = ap.parse_args()
    jax = bench_run._setup_jax()
    from harness import manifest
    from harness import trace as trace_lib

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 3
    cell = manifest.find_cell(args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        if k.startswith("config."):
            *blocks, last = k.split(".")[1:]
            target = cell.config
            for b in blocks:
                target = target[b]
            target[last] = json.loads(v)
        else:
            cell.traffic[k] = json.loads(v)
    module = manifest.generator(cell)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    records = []
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        gen = module.Generator(cell, seed, trace_lib.Tracer("", False))
        t0 = time.perf_counter()
        gen.setup(args.seconds)
        setup_s = time.perf_counter() - t0
        win = gen.window(args.seconds, time.perf_counter)
        gen.release()
        rec = {"seed": seed, "setup_s": setup_s, "metrics": win["metrics"],
               "info": win["info"]}
        sound = gen.check()
        rec["sound"] = dict(sound["values"], missing=sound["missing"], failed=sound["failed"],
                            units=sound["units"], **sound.get("info", {}))
        if seed in control:
            ctl = gen.check(control=True)
            rec["control"] = dict(ctl["values"], units=ctl["units"], **ctl.get("info", {}))
        records.append(rec)
        print(json.dumps(rec), flush=True)
    lower = {}
    upper = {}
    for rec in records:
        for k, v in rec["sound"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in rec.get("control", {}).items():
            upper[k] = min(upper.get(k, math.inf), v)
    summary = {"lower": lower, "upper": upper}
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"records": records, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
