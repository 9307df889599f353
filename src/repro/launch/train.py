"""Training launcher.

Local (this container): reduced configs on the host devices.
Production: the same entry point under a multi-host runtime — set
``JAX_COORDINATOR`` etc. and the documented XLA flags for collective/compute
overlap (README runbook); the mesh comes from ``make_production_mesh``.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 100 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import json
import os

# Latency-hiding scheduler flags for real TPU runs (harmless on CPU; applied
# only when the user opts in so local runs keep default compile times).
_OVERLAP_FLAGS = (
    " --xla_tpu_enable_async_collective_fusion=true"
    " --xla_tpu_overlap_compute_collective_tc=true"
    " --xla_enable_async_all_gather=true"
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--overlap-flags", action="store_true",
                    help="append the TPU latency-hiding XLA flags")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.overlap_flags:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + _OVERLAP_FLAGS

    from repro.configs import get_config, get_smoke_config
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainerConfig(
        total_steps=args.steps,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=os.path.join(args.checkpoint_dir, cfg.name),
        peak_lr=args.lr,
        grad_accum=args.grad_accum,
        compress_grads=args.compress_grads,
    )
    trainer = Trainer(cfg, tcfg, seq_len=args.seq, global_batch=args.batch)
    out = trainer.run()
    print(json.dumps({
        "arch": cfg.name,
        "final_step": out["final_step"],
        "first_loss": out["losses"][0] if out["losses"] else None,
        "final_loss": out["losses"][-1] if out["losses"] else None,
        "straggler_events": out["straggler_events"],
    }, indent=2))


if __name__ == "__main__":
    main()
