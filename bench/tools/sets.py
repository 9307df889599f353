#!/usr/bin/env python3
"""Run a cell several times, one process per run, and report the spreads.

    python bench/tools/sets.py --workload wlcg-prod.presim-leap --seconds 20 \
        --seeds 11,12,13 --sets 2 [--trace-seeds 21] [--out x.json]

Each set runs ``bench/run.py`` once per seed, in order, and the sets repeat
the same seeds. Per end-to-end metric and set it prints the median and the
spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median, both over
all runs and leaving out the run farthest from the median. This process
never imports JAX, so each run has the chips to itself.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def one(workload, seed, seconds, trace):
    cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    rec = {"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": time.time() - t0,
           "earlier": lines[:-1], "stderr_tail": p.stderr[-1500:]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        rec["result"] = None
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    runs = []
    for k in range(args.sets):
        for s in seeds:
            r = one(args.workload, s, args.seconds, 0)
            r["set"] = k
            runs.append(r)
            res = r["result"] or {}
            print(json.dumps({"set": k, "seed": s, "rc": r["rc"], "wall_s": round(r["wall_s"], 1),
                              "correct": res.get("correct"),
                              "metrics": {m: v["value"] for m, v in res.get("metrics", {}).items()},
                              "checks": {c: v["value"] for c, v in res.get("checks", {}).items()},
                              "window": r["earlier"][-1] if r["earlier"] else None}),
                  flush=True)
            if r["rc"] != 0 or not res:
                print(r["stderr_tail"], flush=True)
    for s in [int(x) for x in args.trace_seeds.split(",") if x]:
        r = one(args.workload, s, args.seconds, 1)
        r["set"] = "trace"
        runs.append(r)
        print(json.dumps({"trace_seed": s, "rc": r["rc"], "wall_s": round(r["wall_s"], 1),
                          "earlier": r["earlier"], "result": r["result"]}), flush=True)
        if r["rc"] != 0 or not r["result"]:
            print(r["stderr_tail"], flush=True)
    summary = {}
    for k in range(args.sets):
        ok = [r["result"] for r in runs if r["set"] == k and r["result"]]
        names = sorted({m for res in ok for m in res["metrics"]})
        for m in names:
            vals = [res["metrics"][m]["value"] for res in ok if m in res["metrics"]]
            summary.setdefault(m, []).append({
                "median": statistics.median(vals), "spread": spread(vals),
                "spread_trimmed": spread(trimmed(vals)) if len(vals) > 2 else float("nan"),
                "values": vals,
            })
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"runs": runs, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
