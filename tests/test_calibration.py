"""SBI stack tests: classifier learns ratios on a toy problem; MCMC samples a
known posterior; the full (reduced-scale) calibration recovers theta on the
production workload."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mcmc as mcmc_lib
from repro.core.calibration import (
    CalibrationConfig,
    PriorBox,
    calibrate,
    make_theta_mapper,
    presimulate_bank,
    simulate_coefficients,
    validate,
)
from repro.core.classifier import (
    ClassifierConfig,
    epoch_batch_starts,
    init_classifier,
    train_classifier,
)
from repro.core.engine import SimSpec
from repro.core.fleet import Fleet
from repro.core.scenarios import sample_scenarios
from repro.core.workload import compile_campaign, wlcg_production_workload


def test_classifier_init_topology():
    cfg = ClassifierConfig()
    params = init_classifier(jax.random.PRNGKey(0), cfg)
    # paper: 4 hidden layers x 128 units, 1 output
    assert params["w0"].shape == (6, 128)
    assert params["w1"].shape == (128, 128)
    assert params["w3"].shape == (128, 128)
    assert params["w4"].shape == (128, 1)
    assert len(params) == 10


def test_classifier_learns_toy_dependence():
    """x = theta + noise: the classifier must separate dependent pairs from
    shuffled pairs (accuracy well above chance)."""
    key = jax.random.PRNGKey(0)
    n = 8192
    k1, k2, k3 = jax.random.split(key, 3)
    theta = jax.random.uniform(k1, (n, 3))
    x = theta + 0.05 * jax.random.normal(k2, (n, 3))
    cfg = ClassifierConfig()
    params, metrics = train_classifier(k3, cfg, theta, x, epochs=6, batch_size=1024)
    assert float(metrics.accuracy) > 0.75


def test_mcmc_samples_known_ratio():
    """Plug an analytic 'classifier' into the chain: logit = -||theta - mu||^2
    / (2 s^2) corresponds to a Gaussian posterior around mu; the chain's
    sample mean/std must match."""
    mu = jnp.array([0.6, 0.4, 0.5])
    s = 0.08

    class _FakeParams(dict):
        pass

    # run_chain calls log_ratio(params, theta, x) -> emulate via monkeypatch
    import repro.core.mcmc as m

    orig = m.log_ratio
    try:
        m.log_ratio = lambda p, t, x: -jnp.sum((t - mu) ** 2) / (2 * s * s)
        res = m.run_chain(
            {"w0": jnp.zeros((6, 1))},  # placeholder
            jnp.zeros((3,)),
            jax.random.PRNGKey(1),
            n_samples=6000,
            burn_in=1500,
            step_size=0.12,
        )
    finally:
        m.log_ratio = orig
    samples = np.asarray(res.samples)
    assert 0.2 < float(res.accept_rate) < 0.95
    np.testing.assert_allclose(samples.mean(0), np.asarray(mu), atol=0.03)
    np.testing.assert_allclose(samples.std(0), s, atol=0.03)


def test_posterior_mode():
    samples = jnp.stack(
        [
            jnp.clip(0.3 + 0.05 * jax.random.normal(jax.random.PRNGKey(0), (4000,)), 0, 1),
            jnp.clip(0.7 + 0.05 * jax.random.normal(jax.random.PRNGKey(1), (4000,)), 0, 1),
        ],
        axis=1,
    )
    mode = np.asarray(mcmc_lib.posterior_mode(samples))
    np.testing.assert_allclose(mode, [0.3, 0.7], atol=0.05)


@pytest.mark.slow
def test_end_to_end_calibration_recovers_theta():
    """Reduced-scale paper Section 5: generate x_true from a known theta,
    calibrate, and check theta lands near the truth (mu/sigma especially —
    the paper finds overhead nearly unidentifiable, Fig. 5)."""
    grid, camp = wlcg_production_workload(seed=0)  # the 106-obs workload
    table = compile_campaign(grid, camp)
    spec = SimSpec.from_table(table, max_ticks=30_000)
    mapper = make_theta_mapper(table, "webdav")
    theta_true = jnp.array([0.02, 36.9, 14.4])
    x_true = simulate_coefficients(
        spec, mapper(theta_true), jax.random.PRNGKey(42), n_replicates=8
    )

    cfg = CalibrationConfig(
        n_presim=4096, epochs=120, batch_size=1024, lr=3e-4,
        n_replicates=2, n_chains=4, n_mcmc=6000, burn_in=1200, step_size=0.1,
        n_validation=16,
    )
    result = calibrate(spec, table, x_true, jax.random.PRNGKey(0), cfg)
    theta_map = np.asarray(result.theta_map)
    # mu is the strongly identified parameter (Fig. 5)
    assert abs(theta_map[1] - 36.9) < 25.0, theta_map
    # posterior must concentrate relative to the prior (std_uniform ~ 28.9)
    assert np.asarray(result.posterior_samples)[:, 1].std() < 26.0

    val = validate(
        spec, table, result.theta_map, x_true, jax.random.PRNGKey(9),
        n_sims=16, n_replicates=2,
    )
    # Eq.-6 errors: the dominant coefficients a, b recovered within ~35%
    # at this reduced budget (paper reaches ~5% at 12.7M presims)
    assert val["mean_abs_error"][0] < 0.35, val["mean_abs_error"]
    assert val["mean_abs_error"][1] < 0.50, val["mean_abs_error"]


def test_gelman_rubin_known_value():
    """Closed-form split-R-hat on hand-built chains. With chains whose split
    halves are [0,2,0,2]-patterned (within-var 4/3) and half-chain means
    (1, 1, 6, 6): B = 100/3, var_hat = 28/3, R-hat = sqrt(7). Means
    (1, 1, 2, 2) give var_hat = W = 4/3, R-hat exactly 1."""
    base = np.tile([0.0, 2.0], 4)  # one chain of 8: halves are [0,2,0,2]
    dim0 = np.stack([base, base + 5.0])  # half-chain means 1, 1, 6, 6
    dim1 = np.stack([base, base + 1.0])  # half-chain means 1, 1, 2, 2
    chains = jnp.asarray(np.stack([dim0, dim1], axis=-1))  # [2, 8, 2]
    rhat = np.asarray(mcmc_lib.gelman_rubin(chains))
    np.testing.assert_allclose(rhat, [np.sqrt(7.0), 1.0], rtol=1e-6)


def test_posterior_mode_bimodal():
    """The per-axis mode must pick the taller peak of a bimodal posterior,
    not the (prior-ward) mean."""
    rng = np.random.RandomState(0)
    col0 = np.concatenate(
        [0.25 + 0.02 * rng.standard_normal(3000),
         0.75 + 0.02 * rng.standard_normal(1000)]
    )
    col1 = np.concatenate(
        [0.25 + 0.02 * rng.standard_normal(1000),
         0.75 + 0.02 * rng.standard_normal(3000)]
    )
    samples = jnp.asarray(np.clip(np.stack([col0, col1], axis=1), 0.0, 1.0))
    mode = np.asarray(mcmc_lib.posterior_mode(samples))
    np.testing.assert_allclose(mode, [0.25, 0.75], atol=0.05)
    # the mean would sit between the modes — the estimator must not
    assert abs(float(samples[:, 0].mean()) - mode[0]) > 0.08


def test_epoch_batch_starts_covers_the_tail():
    """``n % batch_size`` tail tuples must train every epoch: the final
    step shifts back to end at n instead of being dropped."""
    np.testing.assert_array_equal(epoch_batch_starts(10, 4), [0, 4, 6])
    np.testing.assert_array_equal(epoch_batch_starts(8, 4), [0, 4])  # legacy
    np.testing.assert_array_equal(epoch_batch_starts(5, 5), [0])
    for n, b in [(10, 4), (1000, 512), (7, 3), (4097, 4096), (512, 512)]:
        starts = epoch_batch_starts(n, b)
        assert len(starts) == -(-n // b), (n, b)
        covered = np.zeros(n, bool)
        for s in starts:
            assert 0 <= s and s + b <= n, (n, b, s)
            covered[s:s + b] = True
        assert covered.all(), (n, b)
    with pytest.raises(ValueError):
        epoch_batch_starts(3, 4)


def test_train_epoch_runs_the_tail_step():
    """The epoch scan takes ceil(n / batch) optimizer steps — observable on
    the AdamW step counter — so the tail minibatch is actually trained."""
    from repro.core.classifier import _train_epoch
    from repro.train.optimizer import AdamWConfig, adamw_init

    cfg = ClassifierConfig(hidden=8, depth=2)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = init_classifier(k1, cfg)
    opt = adamw_init(params, AdamWConfig(lr=1e-3))
    n, b = 10, 4
    theta = jax.random.uniform(k2, (n, 3))
    x = jax.random.uniform(k3, (n, 3))
    ctx = jnp.zeros((n, 0))
    _, opt2, metrics = _train_epoch(
        params, opt, theta, x, ctx, jax.random.PRNGKey(1),
        jnp.asarray(1e-3), batch_size=b,
    )
    assert int(opt2.step) == 3  # ceil(10/4): 2 full steps + the tail step
    assert np.isfinite(float(metrics.loss))


def test_presimulate_bank_scenario_major_layout_and_bucket_parity():
    """Regression pin for the presim layout the amortized training pairs
    contexts by: ``(theta, x_sim, scenario_id)`` is scenario-major
    (scenario i owns rows [i*n_per, (i+1)*n_per)), and the bucketed layout
    reproduces the monolithic scenario_id/theta columns exactly — a silent
    reorder here would mispair contexts and poison the conditional net."""
    pairs = sample_scenarios(["wlcg-remote"], n=4, seed=0)
    mono = Fleet.from_pairs(pairs, max_ticks=6_000, leap=True)
    buck = Fleet.from_pairs(pairs, max_ticks=6_000, n_buckets=2, leap=True)
    prior = PriorBox.paper()
    key = jax.random.PRNGKey(3)
    n_per = 6
    t1, x1, s1 = presimulate_bank(mono, prior, key, n_per, batch=3)
    t2, x2, s2 = presimulate_bank(buck, prior, key, n_per, batch=3)

    assert t1.shape == (4 * n_per, 3) and x1.shape == (4 * n_per, 3)
    np.testing.assert_array_equal(
        np.asarray(s1), np.repeat(np.arange(4, dtype=np.int32), n_per)
    )
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    # same key -> identical prior draws, in the identical scenario-major
    # order, on both layouts
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    # and the simulated coefficients agree across layouts row for row
    np.testing.assert_allclose(
        np.asarray(x1), np.asarray(x2), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("n_replicates", [1, 2])
@pytest.mark.parametrize("leap", [True, False])
def test_presim_chunk_body_folds_the_tick_into_kernel_rows(leap, n_replicates):
    """``presimulate``'s chunk body, ``vmap`` of ``simulate_coefficients``
    on the Pallas tick (interpret mode): every ``vmap`` folds its axis into
    the kernel's rows (the ``kernels.grid_tick.folded`` counter, none made a
    grid axis) and each theta's coefficients equal, bit for bit, its own
    unbatched call's."""
    from repro.kernels import ops
    from repro.utils import spans

    grid, camp = wlcg_production_workload(
        n_waves=3, wave_period_ticks=60, n_observations=12, max_size_mb=600.0,
        link_bandwidth=250.0, bg_update_period=20, seed=0,
    )
    table = compile_campaign(grid, camp)
    spec = SimSpec.from_table(table, max_ticks=5_000)
    mapper = make_theta_mapper(table, "webdav")
    b = 5
    thetas = PriorBox.paper().from_unit(jax.random.uniform(jax.random.PRNGKey(1), (b, 3)))
    keys = jax.random.split(jax.random.PRNGKey(2), b)

    def coefs(theta, key):
        return simulate_coefficients(
            spec, mapper(theta), key, backend="pallas_interpret",
            n_replicates=n_replicates, leap=leap,
        )

    count = lambda snap, name: snap.get(name, spans.Entry(0, 0.0)).count
    before = spans.snapshot()
    chunk = np.asarray(jax.jit(jax.vmap(coefs))(thetas, keys))
    after = spans.snapshot()
    assert count(after, ops.TICK_FOLDED) > count(before, ops.TICK_FOLDED)
    assert count(after, ops.TICK_UNFOLDED) == count(before, ops.TICK_UNFOLDED)
    one = jax.jit(coefs)
    for i in range(b):
        np.testing.assert_array_equal(chunk[i], np.asarray(one(thetas[i], keys[i])))


def test_gelman_rubin_detects_mixing():
    """R-hat ~1 for well-mixed chains, >>1 for disjoint chains."""
    rng = np.random.RandomState(0)
    mixed = jnp.asarray(rng.standard_normal((4, 500, 3)))
    rhat = mcmc_lib.gelman_rubin(mixed)
    assert (np.asarray(rhat) < 1.1).all(), rhat
    # two chains stuck in different modes
    stuck = np.concatenate(
        [rng.standard_normal((2, 500, 3)), 10 + rng.standard_normal((2, 500, 3))]
    )
    rhat_bad = mcmc_lib.gelman_rubin(jnp.asarray(stuck))
    assert (np.asarray(rhat_bad) > 2.0).all(), rhat_bad


def test_adaptive_chain_hits_target_acceptance():
    """Robbins-Monro adaptation lands near the 0.44 target without a
    hand-tuned step size."""
    mu = jnp.array([0.5, 0.5, 0.5])
    s = 0.05
    import repro.core.mcmc as m

    orig = m.log_ratio
    try:
        m.log_ratio = lambda p, t, x: -jnp.sum((t - mu) ** 2) / (2 * s * s)
        res = m.run_chain_adaptive(
            {"w0": jnp.zeros((6, 1))}, jnp.zeros((3,)), jax.random.PRNGKey(0),
            n_samples=4000, burn_in=2000,
        )
    finally:
        m.log_ratio = orig
    assert 0.25 < float(res.accept_rate) < 0.65, float(res.accept_rate)
    samples = np.asarray(res.samples)
    np.testing.assert_allclose(samples.mean(0), np.asarray(mu), atol=0.03)
