import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and record memory/cost/collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k [--multipod] [--out reports/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod]

The env flag above MUST precede every other import (jax locks the device
count at first init); tests and benches never import this module.
"""

import argparse
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, input_specs, skip_reason
from repro.models import model as M
from repro.parallel import sharding as SH
from repro.train.optimizer import AdamWConfig
from repro.utils import get_logger
from repro.utils.hlo import collective_bytes

log = get_logger("dryrun")


def _shardings(mesh, tree, spec_fn, head_dim=None, **kw):
    specs = SH.sanitize_specs(
        spec_fn(tree, mesh.axis_names, **kw), tree, mesh, head_dim=head_dim
    )
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _replicated(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def _compile_cell(cfg, shape, mesh, opt_cfg, donate: bool, kv_strategy: str = "seq"):
    with mesh:
        if shape.kind == "train":
            state_sds, batch_sds = input_specs(cfg, shape, opt_cfg)
            state_sh = _shardings(mesh, state_sds, SH.tree_specs, head_dim=cfg.hd)
            batch_sh = _shardings(mesh, batch_sds, SH.batch_specs)
            step = M.make_train_step(cfg, opt_cfg)
            # repro: allow[jit-cache] -- AOT path: the jit is .lower()ed immediately and discarded; no live cache outlives this call
            jitted = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, _replicated(mesh, {"m": 0})["m"]),
                donate_argnums=(0,) if donate else (),
            )
            lowered = jitted.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            params_sds, cache_sds, batch_sds = input_specs(cfg, shape, opt_cfg)
            params_sh = _shardings(mesh, params_sds, SH.tree_specs, head_dim=cfg.hd)
            cache_sh = _shardings(mesh, cache_sds, SH.cache_specs,
                                  kv_strategy=kv_strategy)
            batch_sh = _shardings(mesh, batch_sds, SH.batch_specs)
            step = M.make_prefill_step(cfg)
            lg_spec = SH.sanitize_specs(
                P(SH._batch_axes(mesh.axis_names), "model"),
                jax.ShapeDtypeStruct((shape.global_batch, cfg.vocab_size), jnp.float32),
                mesh)
            logits_sh = NamedSharding(mesh, lg_spec)
            # repro: allow[jit-cache] -- AOT path: the jit is .lower()ed immediately and discarded; no live cache outlives this call
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, cache_sh, batch_sh),
                out_shardings=(logits_sh, cache_sh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(params_sds, cache_sds, batch_sds)
        else:  # decode
            params_sds, cache_sds, tok_sds = input_specs(cfg, shape, opt_cfg)
            params_sh = _shardings(mesh, params_sds, SH.tree_specs, head_dim=cfg.hd)
            cache_sh = _shardings(mesh, cache_sds, SH.cache_specs,
                                  kv_strategy=kv_strategy)
            tok_spec = SH.sanitize_specs(
                P(SH._batch_axes(mesh.axis_names)), tok_sds, mesh)
            tok_sh = NamedSharding(mesh, tok_spec)
            step = M.make_serve_step(cfg)
            lg_spec = SH.sanitize_specs(
                P(SH._batch_axes(mesh.axis_names), "model"),
                jax.ShapeDtypeStruct((shape.global_batch, cfg.vocab_size), jnp.float32),
                mesh)
            logits_sh = NamedSharding(mesh, lg_spec)
            # repro: allow[jit-cache] -- AOT path: the jit is .lower()ed immediately and discarded; no live cache outlives this call
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, cache_sh, tok_sh),
                out_shardings=(logits_sh, cache_sh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(params_sds, cache_sds, tok_sds)

        return lowered.compile()


def _cell_metrics(compiled, n_dev: int) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text(), n_dev)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll.total_bytes),
        "coll_ops": float(coll.total_count),
        "coll_detail": {k: dict(v) for k, v in coll.items()},
    }


def _reduced_cfg(cfg, n_units: int):
    """Same family/pattern/tail but only ``n_units`` repetitions, with the
    layer loop *unrolled* — XLA cost analysis counts while-loop bodies once
    independent of trip count, so per-unit costs must come from the
    difference of two unrolled compiles."""
    n_layers = n_units * cfg.pattern_len + len(cfg.tail_blocks)
    enc = min(cfg.encoder_layers, n_units) if cfg.encoder_layers else 0
    return cfg.scaled(n_layers=n_layers, encoder_layers=enc, scan_layers=False)


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    moe_dispatch: Optional[str] = None,
    remat: Optional[bool] = None,
    donate: bool = True,
    window: Optional[int] = None,
    kv_strategy: str = "seq",
    opt_flags: tuple = (),
) -> Dict[str, Any]:
    """Lower+compile one cell; returns the §Dry-run record.

    Loop-body cost correction: XLA's cost analysis counts a while-loop body
    once regardless of trip count, so scanned-layer FLOPs/bytes/collectives
    are extrapolated from compiles at 1 and 2 scan units:
    ``total = f(1) + (n_units - 1) * (f(2) - f(1))``. (Residual caveat: the
    sLSTM time-recurrence is itself a nested scan and stays counted once per
    unit; its per-step cost is negligible at these widths — noted in
    EXPERIMENTS.md.) The full-depth compile provides the memory analysis and
    proves the production graph compiles.
    """
    cfg = get_config(arch)
    if moe_dispatch is not None:
        cfg = cfg.scaled(moe_dispatch=moe_dispatch)
    if remat is not None:
        cfg = cfg.scaled(remat=remat)
    if window is not None:
        cfg = cfg.scaled(window=window)
    if opt_flags:
        cfg = cfg.scaled(opt_flags=tuple(opt_flags))
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "status": "SKIP",
                "reason": reason, "multi_pod": multi_pod}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    opt_cfg = AdamWConfig(lr=1e-4, clip_norm=1.0)

    t0 = time.time()
    compiled_full = _compile_cell(cfg, shape, mesh, opt_cfg, donate, kv_strategy)
    t_compile = time.time() - t0

    n_units = cfg.n_units
    enc_units = cfg.encoder_layers
    if n_units > 1:
        m1 = _cell_metrics(
            _compile_cell(_reduced_cfg(cfg, 1), shape, mesh, opt_cfg, donate,
                          kv_strategy), n_dev
        )
        m2 = _cell_metrics(
            _compile_cell(_reduced_cfg(cfg, 2), shape, mesh, opt_cfg, donate,
                          kv_strategy), n_dev
        )
        scale = {
            # clamp: the 2-unit compile can spend *fewer* collective bytes
            # than the 1-unit one (fusion/CSE noise), which would extrapolate
            # negative — floor every per-unit delta at zero.
            k: m1[k] + (n_units - 1) * max(m2[k] - m1[k], 0.0)
            for k in ("flops", "bytes", "coll_bytes", "coll_ops")
        }
        # encoder stacks scale with the same unit diff ratio only if the
        # encoder scan shrank too; enc handled by same 1->2 diff since both
        # stacks shrink together in _reduced_cfg.
        metrics = scale
        metrics["extrapolated"] = True
        metrics["unit_flops"] = m2["flops"] - m1["flops"]
        metrics["coll_detail"] = m2["coll_detail"]
    else:
        metrics = _cell_metrics(compiled_full, n_dev)
        metrics["extrapolated"] = False

    mem = compiled_full.memory_analysis()
    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "status": "OK",
        "n_devices": n_dev,
        "compile_s": round(t_compile, 1),
        "flops_total": metrics["flops"],
        "bytes_accessed_total": metrics["bytes"],
        "collective_bytes_per_device": metrics["coll_bytes"],
        "collective_ops": metrics["coll_ops"],
        "collectives": metrics.get("coll_detail", {}),
        "extrapolated": metrics["extrapolated"],
    }
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        if hasattr(mem, attr):
            record[f"mem_{attr}"] = int(getattr(mem, attr))
    record["memory_analysis"] = str(mem)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--moe-dispatch", default=None, choices=["onehot", "sort"])
    ap.add_argument("--remat", default=None, choices=["on", "off"])
    ap.add_argument("--kv-strategy", default="seq", choices=["seq", "heads"])
    ap.add_argument("--opt", nargs="*", default=[],
                    help="opt_flags: hoist_rope bf16_boundary gqa_grouped")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multipod]
    remat = None if args.remat is None else (args.remat == "on")

    os.makedirs(args.out, exist_ok=True)
    results = []
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}/{shape}/{'2x16x16' if multi_pod else '16x16'}"
                try:
                    rec = run_cell(
                        arch, shape, multi_pod=multi_pod,
                        moe_dispatch=args.moe_dispatch, remat=remat,
                        kv_strategy=args.kv_strategy,
                        opt_flags=tuple(args.opt),
                    )
                except Exception as e:  # noqa: BLE001 - report and continue
                    rec = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                           "status": "FAIL", "error": f"{type(e).__name__}: {e}"}
                results.append(rec)
                if rec["status"] == "OK":
                    log.info(
                        "%s OK compile=%.0fs flops=%.3e coll=%.3e B/dev mem=%s",
                        tag, rec["compile_s"], rec["flops_total"],
                        rec["collective_bytes_per_device"],
                        rec.get("mem_peak_memory_in_bytes",
                                rec.get("mem_temp_size_in_bytes", "?")),
                    )
                else:
                    log.info("%s %s %s", tag, rec["status"],
                             rec.get("reason", rec.get("error", "")))
                fname = f"{arch}_{shape}_{'mp' if multi_pod else 'sp'}.json"
                with open(os.path.join(args.out, fname), "w") as f:
                    json.dump(rec, f, indent=2)
    n_ok = sum(r["status"] == "OK" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    log.info("dry-run done: %d OK, %d SKIP, %d FAIL", n_ok, n_skip, n_fail)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(results, f, indent=2)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
