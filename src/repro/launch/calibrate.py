"""Calibration launcher (the paper's Section-5 pipeline at configurable
scale). Presimulation runs in vmapped chunks of the per-campaign engine
(``calibration.presimulate``) on the process's default device; it is not
sharded across devices.

    PYTHONPATH=src python -m repro.launch.calibrate --presim 8192 \
        --epochs 120 --mcmc 8000 --validate 64 --replicates 4
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--presim", type=int, default=8192)
    ap.add_argument("--epochs", type=int, default=120)
    ap.add_argument("--batch-size", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--replicates", type=int, default=4)
    ap.add_argument("--mcmc", type=int, default=8000)
    ap.add_argument("--burn-in", type=int, default=1500)
    ap.add_argument("--chains", type=int, default=4)
    ap.add_argument("--validate", type=int, default=64)
    ap.add_argument("--theta-true", type=float, nargs=3,
                    default=[0.02, 36.9, 14.4],
                    help="synthetic ground truth used to generate x_true")
    ap.add_argument("--out", default="reports/calibration.json")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.core.calibration import (
        CalibrationConfig, calibrate, make_theta_mapper,
        simulate_coefficients, validate,
    )
    from repro.core.engine import SimSpec
    from repro.core.workload import compile_campaign, wlcg_production_workload

    grid, camp = wlcg_production_workload(seed=0)
    table = compile_campaign(grid, camp)
    spec = SimSpec.from_table(table, max_ticks=30_000)
    mapper = make_theta_mapper(table, "webdav")
    theta_true = jnp.asarray(args.theta_true)
    x_true = simulate_coefficients(
        spec, mapper(theta_true), jax.random.PRNGKey(42), n_replicates=8
    )

    cfg = CalibrationConfig(
        n_presim=args.presim, epochs=args.epochs, batch_size=args.batch_size,
        lr=args.lr, n_replicates=args.replicates, n_chains=args.chains,
        n_mcmc=args.mcmc, burn_in=args.burn_in, step_size=0.1,
        n_validation=args.validate,
    )
    t0 = time.time()
    result = calibrate(spec, table, x_true, jax.random.PRNGKey(0), cfg)
    val = validate(
        spec, table, result.theta_map, x_true, jax.random.PRNGKey(9),
        n_sims=args.validate, n_replicates=args.replicates,
    )
    # Fig.-5 cornerplot artifact: per-axis histograms, 0.5 quantiles and the
    # posterior covariance (the paper reports these above each histogram)
    samples = np.asarray(result.posterior_samples)
    names = ["overhead", "mu", "sigma"]
    bounds = [(0.0, 0.1), (0.0, 100.0), (0.0, 100.0)]
    cornerplot = {
        "axes": names,
        "median": np.median(samples, axis=0).tolist(),
        "covariance": np.cov(samples.T).tolist(),
        "histograms": {
            n: {
                "counts": np.histogram(samples[:, i], bins=40, range=bounds[i])[0].tolist(),
                "edges": np.histogram(samples[:, i], bins=40, range=bounds[i])[1].tolist(),
            }
            for i, n in enumerate(names)
        },
    }

    report = {
        "x_true": np.asarray(x_true).tolist(),
        "theta_true": args.theta_true,
        "theta_star_marginal": np.asarray(result.theta_star).tolist(),
        "theta_map": np.asarray(result.theta_map).tolist(),
        "accept_rate": float(result.accept_rate),
        "rhat": np.asarray(result.rhat).tolist() if result.rhat is not None else None,
        "posterior_mean": np.asarray(result.posterior_samples.mean(0)).tolist(),
        "posterior_std": np.asarray(result.posterior_samples.std(0)).tolist(),
        "cornerplot": cornerplot,
        "validation_median_coef": val["median_coef"].tolist(),
        "validation_mean_abs_error": val["mean_abs_error"].tolist(),
        "validation_best_sum_error": float(val["sum_error"].min()),
        "wall_s": round(time.time() - t0, 1),
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
