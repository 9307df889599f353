"""Amortized vs per-scenario calibration cost.

The amortized path trains ONE scenario-conditioned AALR classifier over the
whole presimulation fleet and serves every scenario's posterior from it
(conditional MCMC only); the pre-amortized architecture retrains an
unconditional classifier per scenario on that scenario's own tuples. At an
equal tuple budget the two training totals are comparable (same optimizer
steps, and the retrain loop shares one jit trace across same-shaped
scenarios) — the amortized win is the **O(1) trained artifact**: the
marginal cost of serving one more scenario is a conditional MCMC alone,
not a fresh classifier training plus an MCMC, and there is one set of net
weights to persist/ship instead of N.

    PYTHONPATH=src python benchmarks/amortized_calibration.py \
        [--scenarios 8] [--per-scenario 512] [--out BENCH_amortized.json]

    PYTHONPATH=src python benchmarks/amortized_calibration.py --smoke

Emits ``BENCH_amortized.json``: wall clocks for the conditional train, the
per-scenario retrain loop, the conditional MCMC sweep, and
``marginal_scenario_speedup`` (retrain + MCMC vs MCMC alone for one
additional scenario). ``--smoke`` runs tiny budgets through every section
and the assertions without writing JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", type=int, default=8)
    ap.add_argument("--per-scenario", type=int, default=512,
                    help="presim tuples per scenario")
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=512)
    ap.add_argument("--mcmc", type=int, default=2000)
    ap.add_argument("--burn-in", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-ticks", type=int, default=10_000)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny budgets, all sections + assertions, no JSON")
    ap.add_argument("--out", default="BENCH_amortized.json")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.scenarios, args.per_scenario = 3, 64
        args.epochs, args.batch_size = 4, 64
        args.mcmc, args.burn_in, args.max_ticks = 300, 100, 3_000

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import CalibrationConfig, Fleet, PriorBox
    from repro.core import calibration as calibration_lib
    from repro.core.classifier import ClassifierConfig, train_classifier
    from repro.core.scenarios import sample_scenarios

    n = args.scenarios
    fleet = Fleet.from_pairs(
        sample_scenarios(["wlcg-remote", "bursty"], n=n, seed=args.seed),
        max_ticks=args.max_ticks, leap=True,
    )
    prior = PriorBox.paper()
    cfg = CalibrationConfig(
        epochs=args.epochs, batch_size=args.batch_size, lr=3e-4,
        n_chains=2, n_mcmc=args.mcmc, burn_in=args.burn_in,
    )
    x_true = jnp.asarray(
        fleet.coefficients(jnp.array([0.02, 36.9, 14.4]), replicas=2,
                           key=jax.random.PRNGKey(7))
    ).mean(axis=1)  # [N, 3]

    t0 = time.perf_counter()
    theta, x_sim, sid = jax.block_until_ready(
        fleet.presimulate(
            prior, jax.random.PRNGKey(1), args.per_scenario,
            batch=min(64, args.per_scenario), leap=True,
        )
    )
    presim_s = time.perf_counter() - t0

    # amortized: ONE conditional train over all tuples ...
    t0 = time.perf_counter()
    post = calibration_lib.calibrate(
        None, fleet, x_true, jax.random.PRNGKey(2), cfg, prior,
        presim=(theta, x_sim, sid), amortized=True,
    )
    jax.block_until_ready(post.classifier_params)
    train_amortized_s = time.perf_counter() - t0
    # ... then one conditional MCMC per scenario off the shared net
    t0 = time.perf_counter()
    theta_star = np.asarray(post.theta_star_all(jax.random.PRNGKey(3)))
    mcmc_sweep_s = time.perf_counter() - t0
    assert theta_star.shape == (n, 3) and np.isfinite(theta_star).all()

    # baseline: retrain an unconditional classifier per scenario on its own
    # scenario-major slice (identical tuple budget, cfg, and key schedule)
    x_low, x_high = jnp.asarray(cfg.x_low), jnp.asarray(cfg.x_high)
    proj = lambda v: jnp.clip((v - x_low) / (x_high - x_low), 0.0, 1.0)
    clf_cfg = ClassifierConfig(theta_dim=3, x_dim=3, lr=cfg.lr)
    t0 = time.perf_counter()
    for i in range(n):
        rows = slice(i * args.per_scenario, (i + 1) * args.per_scenario)
        params_i, _ = train_classifier(
            jax.random.fold_in(jax.random.PRNGKey(4), i), clf_cfg,
            prior.to_unit(theta[rows]), proj(x_sim[rows]),
            epochs=cfg.epochs, batch_size=min(cfg.batch_size, args.per_scenario),
        )
        jax.block_until_ready(params_i)
    train_per_scenario_s = time.perf_counter() - t0

    # marginal cost of one additional scenario: the amortized posterior pays
    # only its conditional MCMC; the retrain baseline pays a training too
    mcmc_marginal_s = mcmc_sweep_s / n
    retrain_marginal_s = train_per_scenario_s / n + mcmc_marginal_s
    report = {
        "n_scenarios": n,
        "tuples_per_scenario": args.per_scenario,
        "epochs": args.epochs,
        "presim_s": round(presim_s, 3),
        "train_amortized_s": round(train_amortized_s, 3),
        "train_per_scenario_s": round(train_per_scenario_s, 3),
        "mcmc_sweep_s": round(mcmc_sweep_s, 3),
        "marginal_scenario_amortized_s": round(mcmc_marginal_s, 3),
        "marginal_scenario_retrain_s": round(retrain_marginal_s, 3),
        "marginal_scenario_speedup": round(
            retrain_marginal_s / mcmc_marginal_s, 2
        ),
        "classifier_accuracy": round(post.train_accuracy, 4),
    }
    print(json.dumps(report, indent=2))
    if not args.smoke:
        out = os.path.join(os.path.dirname(__file__), "..", args.out)
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {os.path.normpath(out)}")


if __name__ == "__main__":
    main()
