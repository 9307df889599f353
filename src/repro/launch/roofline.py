"""Roofline analysis from the dry-run artifacts (single-pod mesh).

Per (arch x shape) cell:

    compute term    = HLO_FLOPs_per_device / PEAK_FLOPS_BF16
    memory term     = HLO_bytes_per_device / HBM_BW
    collective term = collective_bytes_per_device / ICI_BW

plus MODEL_FLOPS (6*N*D dense / 6*N_active*D MoE; 2*N*D prefill; 2*N*B
decode), the useful-compute ratio MODEL_FLOPS/HLO_FLOPs, the dominant term
and a bottleneck note.

    PYTHONPATH=src python -m repro.launch.roofline --reports reports/dryrun \
        --out reports/roofline.md
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, List

import jax

from repro.configs import get_config, list_archs
from repro.launch.mesh import HW
from repro.launch.shapes import SHAPES

__all__ = ["matmul_param_count", "model_flops", "roofline_terms", "build_table"]


def matmul_param_count(arch: str, active_only: bool = False) -> int:
    """Exact parameter count from abstract init (embedding excluded, LM head
    included — the matmul params that enter the 6ND accounting)."""
    from repro.models import model as M

    cfg = get_config(arch)
    params = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    total = sum(
        int(l.size) for l in jax.tree.leaves(params)
    )
    embed = cfg.vocab_size * cfg.d_model
    total -= embed  # lookup is not a matmul
    if cfg.tie_embeddings:
        total += embed  # but the tied head matmul is
    if active_only and cfg.n_experts:
        ffe = cfg.d_ff_expert or cfg.d_ff
        n_moe_layers = sum(1 for k in cfg.layer_kinds if k == "moe")
        inactive = (cfg.n_experts - cfg.n_experts_active) * 3 * cfg.d_model * ffe
        total -= n_moe_layers * inactive
    return int(total)


def model_flops(arch: str, shape_name: str) -> float:
    """Global model FLOPs for the step (6ND train, 2ND prefill, 2NB decode)."""
    shape = SHAPES[shape_name]
    n = matmul_param_count(arch, active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def roofline_terms(record: Dict[str, Any]) -> Dict[str, Any]:
    n_dev = record["n_devices"]
    compute_s = record["flops_total"] / HW.PEAK_FLOPS_BF16
    memory_s = record["bytes_accessed_total"] / HW.HBM_BW
    collective_s = record["collective_bytes_per_device"] / HW.ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(record["arch"], record["shape"]) / n_dev
    useful = mf / max(record["flops_total"], 1e-30)
    bound_s = max(terms.values())
    # roofline fraction: time the useful math would take at peak over the
    # modeled step time
    frac = (mf / HW.PEAK_FLOPS_BF16) / max(bound_s, 1e-30)
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "model_flops_per_device": mf,
        "useful_ratio": useful,
        "roofline_fraction": frac,
        "step_time_bound_s": bound_s,
    }


_NOTES = {
    "compute": "reduce HLO/model FLOP gap: fused attention kernel (softmax "
               "VPU work off the MXU path), drop remat recompute, causal "
               "block skipping",
    "memory": "raise arithmetic intensity: larger per-chip batch, fuse "
              "elementwise chains, bf16 cache/activations, avoid KV "
              "re-materialization",
    "collective": "reshard: more FSDP/less TP, overlap collectives with "
                  "compute (latency-hiding scheduler), bf16/compressed "
                  "gradient all-reduce, all-to-all MoE dispatch",
}


def build_table(report_dir: str, *, multi_pod: bool = False) -> List[Dict[str, Any]]:
    rows = []
    suffix = "mp" if multi_pod else "sp"
    for arch in list_archs():
        for shape in SHAPES:
            path = os.path.join(report_dir, f"{arch}_{shape}_{suffix}.json")
            if not os.path.exists(path):
                continue
            with open(path) as f:
                rec = json.load(f)
            if rec["status"] == "SKIP":
                rows.append({"arch": arch, "shape": shape, "status": "SKIP",
                             "reason": rec["reason"]})
                continue
            if rec["status"] != "OK":
                rows.append({"arch": arch, "shape": shape, "status": "FAIL",
                             "reason": rec.get("error", "?")})
                continue
            terms = roofline_terms(rec)
            rows.append({
                "arch": arch, "shape": shape, "status": "OK",
                **{k: terms[k] for k in (
                    "compute_s", "memory_s", "collective_s", "dominant",
                    "model_flops_per_device", "useful_ratio",
                    "roofline_fraction")},
                "hlo_flops": rec["flops_total"],
                "note": _NOTES[terms["dominant"]],
            })
    return rows


def to_markdown(rows: List[Dict[str, Any]]) -> str:
    lines = [
        "| arch | shape | compute (s) | memory (s) | collective (s) | "
        "dominant | MODEL/HLO flops | roofline frac |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] != "OK":
            lines.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | {r['status']} "
                f"({r['reason'][:60]}…) | — | — |"
            )
            continue
        lines.append(
            "| {arch} | {shape} | {compute_s:.3e} | {memory_s:.3e} | "
            "{collective_s:.3e} | **{dominant}** | {useful_ratio:.2f} | "
            "{roofline_fraction:.3f} |".format(**r)
        )
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reports", default="reports/dryrun")
    ap.add_argument("--out", default="reports/roofline.md")
    ap.add_argument("--json", default="reports/roofline.json")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = build_table(args.reports)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write(to_markdown(rows) + "\n")
    with open(args.json, "w") as f:
        json.dump(rows, f, indent=2)
    print(to_markdown(rows))


if __name__ == "__main__":
    main()
