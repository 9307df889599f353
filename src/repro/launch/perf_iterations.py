import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimbing driver: re-lower the three chosen cells under each
optimization step and record the roofline-term deltas.

    PYTHONPATH=src python -m repro.launch.perf_iterations --out reports/perf
"""

import argparse
import json

from repro.launch.dryrun import run_cell
from repro.launch.roofline import roofline_terms
from repro.utils import get_logger

log = get_logger("perf")

# (cell, iteration-name, run_cell kwargs) — ordered hypothesis ladder
EXPERIMENTS = [
    # A. dense train cell (most collective-bound dense arch)
    ("qwen2.5-14b", "train_4k", "baseline", {}),
    ("qwen2.5-14b", "train_4k", "hoist_rope", {"opt_flags": ("hoist_rope",)}),
    ("qwen2.5-14b", "train_4k", "hoist+bf16_boundary",
     {"opt_flags": ("hoist_rope", "bf16_boundary")}),
    ("qwen2.5-14b", "train_4k", "hoist+bf16+gqa_grouped",
     {"opt_flags": ("hoist_rope", "bf16_boundary", "gqa_grouped")}),
    ("qwen2.5-14b", "train_4k", "act_pin", {"opt_flags": ("act_pin",)}),
    ("qwen2.5-14b", "train_4k", "act_pin+gqa",
     {"opt_flags": ("act_pin", "gqa_grouped")}),
    # B. MoE train cell (the paper-scale 235B model)
    ("qwen3-moe-235b-a22b", "train_4k", "baseline", {}),
    ("qwen3-moe-235b-a22b", "train_4k", "sort_dispatch",
     {"moe_dispatch": "sort"}),
    ("qwen3-moe-235b-a22b", "train_4k", "sort+act_pin",
     {"moe_dispatch": "sort", "opt_flags": ("act_pin",)}),
    # C. worst MODEL/HLO ratio cell: quadratic one-hot dispatch at 32k
    ("qwen2-moe-a2.7b", "prefill_32k", "baseline", {}),
    ("qwen2-moe-a2.7b", "prefill_32k", "sort_dispatch",
     {"moe_dispatch": "sort"}),
    # D. decode cell: KV sharding strategy
    ("qwen2.5-14b", "decode_32k", "baseline(kv=seq)", {}),
    ("qwen2.5-14b", "decode_32k", "kv=heads", {"kv_strategy": "heads"}),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="reports/perf")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch, shape, name, kw in EXPERIMENTS:
        try:
            rec = run_cell(arch, shape, **kw)
            terms = roofline_terms(rec)
            row = {
                "arch": arch, "shape": shape, "iteration": name,
                "flops": rec["flops_total"],
                "bytes": rec["bytes_accessed_total"],
                "coll_bytes": rec["collective_bytes_per_device"],
                **{k: terms[k] for k in (
                    "compute_s", "memory_s", "collective_s", "dominant",
                    "useful_ratio", "roofline_fraction")},
            }
        except Exception as e:  # noqa: BLE001
            row = {"arch": arch, "shape": shape, "iteration": name,
                   "error": f"{type(e).__name__}: {e}"}
        results.append(row)
        log.info("%s/%s [%s]: %s", arch, shape, name,
                 {k: (f"{v:.3e}" if isinstance(v, float) else v)
                  for k, v in row.items() if k not in ("arch", "shape")})
        with open(os.path.join(args.out, "iterations.json"), "w") as f:
            json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
