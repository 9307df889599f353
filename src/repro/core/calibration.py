"""End-to-end simulator calibration (paper Section 5).

Pipeline:

1. **Presimulate** ``(theta, x_sim)`` tuples: draw theta from the uniform
   prior box (overhead, mu, sigma), run one stochastic simulation of the
   production workload per draw, fit Eq. 1 to the simulated observations —
   x_sim is the coefficient triple (a, b, c). Sharded across the device mesh
   (each device simulates its slice of the batch).
2. **Project** thetas and coefficients onto (0,1).
3. **Train** the AALR classifier.
4. **MCMC** over theta given x_true, extract theta* (per-axis density modes).
5. **Validate**: run stochastic simulations under theta*, fit Eq. 1 per
   simulation, score with the Eq.-6 relative coefficient errors (Table 1).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mcmc as mcmc_lib
from repro.core.classifier import ClassifierConfig, train_classifier
from repro.core.dataset import observations
from repro.core.engine import (
    SimParams,
    SimResult,
    SimSpec,
    simulate,
)
from repro.core.regression import coefficient_error, fit_eq1
from repro.core.workload import (
    LegTable,
    ProfileTag,
    ScenarioBank,
    summary_features,
)
from repro.utils import get_logger

log = get_logger("calibration")

__all__ = [
    "PriorBox",
    "CalibrationConfig",
    "CalibrationResult",
    "AmortizedPosterior",
    "simulate_coefficients",
    "presimulate",
    "presimulate_bank",
    "calibrate",
    "validate",
    "validate_bank",
    "make_theta_mapper",
    "make_bank_theta_mapper",
]


class PriorBox(NamedTuple):
    """Uniform prior bounds over theta = (overhead, mu, sigma) (paper)."""

    low: jax.Array  # [3]
    high: jax.Array  # [3]

    @staticmethod
    def paper() -> "PriorBox":
        return PriorBox(
            low=jnp.array([0.0, 0.0, 0.0], jnp.float32),
            high=jnp.array([0.1, 100.0, 100.0], jnp.float32),
        )

    def to_unit(self, theta: jax.Array) -> jax.Array:
        return (theta - self.low) / (self.high - self.low)

    def from_unit(self, u: jax.Array) -> jax.Array:
        return self.low + u * (self.high - self.low)


@dataclasses.dataclass(frozen=True)
class CalibrationConfig:
    n_presim: int = 65_536  # paper: 12.7M (full scale; CPU default reduced)
    epochs: int = 30  # paper: 263
    batch_size: int = 4096
    lr: float = 1e-4  # paper: ADAM 0.0001
    n_replicates: int = 1  # paper-faithful: single-realization coefficients
    n_chains: int = 8
    n_mcmc: int = 20_000  # paper: 1M (+100k burn-in)
    burn_in: int = 2_000
    step_size: float = 0.05
    n_validation: int = 256  # paper: 16k stochastic validation sims
    use_leap: bool = True  # exact event-leap engine (11x; see §Perf)
    adaptive_mcmc: bool = True  # Robbins-Monro step adaptation in burn-in
    # projection bounds for the coefficient space (x): fixed so that the
    # classifier input normalization is data-independent. Chosen to cover the
    # coefficient ranges produced across the full prior box.
    x_low: Tuple[float, float, float] = (-0.10, -0.10, -0.05)
    x_high: Tuple[float, float, float] = (0.25, 0.20, 0.06)


class CalibrationResult(NamedTuple):
    theta_star: jax.Array  # [3] paper's per-axis marginal modes (phys. units)
    theta_map: jax.Array  # [3] beyond-paper: ratio-argmax MAP estimate
    posterior_samples: jax.Array  # [N, 3] physical units
    accept_rate: jax.Array
    classifier_params: dict
    x_true: jax.Array  # [3]
    rhat: jax.Array = None  # [3] split-R-hat convergence diagnostic
    epoch_loss: jax.Array = None  # [epochs] classifier training curve


@dataclasses.dataclass
class AmortizedPosterior:
    """One scenario-conditioned AALR posterior serving every scenario family.

    Produced by ``calibrate(..., amortized=True)`` /
    ``Fleet.calibrate(amortized=True)``: a single conditional ratio net
    (``log r(x | theta, s)``, trained once over the whole presimulation
    fleet) plus the per-scenario context feature table and the prior. Any
    scenario's posterior is then a (cheap) MCMC over the fixed net — no
    per-scenario retraining. Scenarios are addressed by bank index or name.
    """

    classifier_params: dict
    features: jax.Array  # [N, F] unit-projected scenario context table
    prior: PriorBox
    x_true_unit: jax.Array  # [3] shared or [N, 3] per-scenario observation
    cfg: CalibrationConfig  # MCMC budget knobs for the sampling methods
    scenario_names: Tuple[str, ...]
    train_loss: float = float("nan")
    train_accuracy: float = float("nan")

    @property
    def n_scenarios(self) -> int:
        return int(self.features.shape[0])

    @property
    def n_features(self) -> int:
        return int(self.features.shape[1])

    def _index(self, scenario) -> int:
        if isinstance(scenario, str):
            try:
                return self.scenario_names.index(scenario)
            except ValueError:
                raise KeyError(
                    f"unknown scenario {scenario!r}; known: "
                    f"{list(self.scenario_names)}"
                ) from None
        i = int(scenario)
        if not 0 <= i < self.n_scenarios:
            raise IndexError(
                f"scenario {i} out of range for {self.n_scenarios} scenarios"
            )
        return i

    def _x_unit(self, i: int) -> jax.Array:
        x = jnp.asarray(self.x_true_unit)
        return x[i] if x.ndim == 2 else x

    def mcmc(
        self,
        scenario,
        key: Optional[jax.Array] = None,
        *,
        n_samples: Optional[int] = None,
        burn_in: Optional[int] = None,
    ) -> Tuple[mcmc_lib.MCMCResult, jax.Array]:
        """Raw conditional chains for one scenario: the pooled unit-box
        :class:`~repro.core.mcmc.MCMCResult` plus the split-R-hat vector."""
        i = self._index(scenario)
        key = jax.random.PRNGKey(0) if key is None else key
        cfg = self.cfg
        return mcmc_lib.run_chains(
            self.classifier_params,
            self._x_unit(i),
            key,
            n_chains=cfg.n_chains,
            n_samples=cfg.n_mcmc if n_samples is None else n_samples,
            burn_in=cfg.burn_in if burn_in is None else burn_in,
            step_size=cfg.step_size,
            adaptive=cfg.adaptive_mcmc,
            context=self.features[i],
        )

    def sample(self, scenario, key: Optional[jax.Array] = None, **mcmc_opts) -> jax.Array:
        """Posterior samples for one scenario in physical units ``[S, 3]``."""
        res, _ = self.mcmc(scenario, key, **mcmc_opts)
        return self.prior.from_unit(res.samples)

    def theta_star(self, scenario, key: Optional[jax.Array] = None, **mcmc_opts) -> jax.Array:
        """Per-axis marginal posterior modes (the paper's theta*) for one
        scenario, in physical units ``[3]``."""
        res, rhat = self.mcmc(scenario, key, **mcmc_opts)
        if float(jnp.max(rhat)) > 1.2:
            log.warning(
                "amortized MCMC for scenario %r may not have converged "
                "(max R-hat %.2f) — increase n_mcmc/burn_in",
                scenario, float(jnp.max(rhat)),
            )
        return self.prior.from_unit(mcmc_lib.posterior_mode(res.samples))

    def theta_star_all(self, key: Optional[jax.Array] = None, **mcmc_opts) -> jax.Array:
        """theta* for every scenario of the fleet: ``[N, 3]`` physical units
        (one conditional MCMC per scenario over the same trained net; the
        chain shapes are identical so every scenario after the first reuses
        the jit trace). Feed this matrix straight into ``Fleet.validate``."""
        key = jax.random.PRNGKey(0) if key is None else key
        return jnp.stack(
            [
                self.theta_star(i, jax.random.fold_in(key, i), **mcmc_opts)
                for i in range(self.n_scenarios)
            ]
        )


def _theta_to_params(keep: jax.Array, protocol_mask: jax.Array,
                     link_scale: jax.Array, theta: jax.Array) -> SimParams:
    """Map theta = (overhead, mu, sigma) onto SimParams: the calibrated
    protocol's legs get the inferred overhead; every (valid) link gets the
    inferred background-load moments (the paper calibrates one link).

    One mapper serves both layouts: per-campaign (``keep``/``mask`` = [T],
    ``link_scale`` = ones [L]) and bank-wide (``[N, T]`` / ``[N, L]`` with
    ``link_scale`` = the validity mask, so padded links keep zero moments and
    their — already zero-bandwidth — fair shares stay untouched). On the
    bank-wide layout ``theta`` may also be a **per-scenario** ``[N, 3]``
    matrix (e.g. ``AmortizedPosterior.theta_star_all()``): row ``i`` then
    parameterizes scenario ``i`` alone."""
    theta = jnp.asarray(theta)
    if theta.ndim == 2:
        if protocol_mask.ndim != 2 or theta.shape[0] != protocol_mask.shape[0]:
            raise ValueError(
                f"per-scenario theta {theta.shape} needs a bank-wide mapper "
                f"over {protocol_mask.shape[0] if protocol_mask.ndim == 2 else 1} "
                "scenarios"
            )
        overhead, mu, sigma = theta[:, 0:1], theta[:, 1:2], theta[:, 2:3]
    else:
        overhead, mu, sigma = theta[0], theta[1], theta[2]
    return SimParams(
        keep_frac=jnp.where(protocol_mask, 1.0 - overhead, keep),
        bg_mu=mu * link_scale,
        bg_sigma=sigma * link_scale,
    )


def make_theta_mapper(source, protocol: str = "webdav", *,
                      missing_ok: bool = False):
    """Returns ``f(theta) -> SimParams`` for ``source``: a compiled
    :class:`LegTable` (per-campaign params), a :class:`ScenarioBank`
    (bank-wide stacked params over the unified protocol namespace), or a
    :class:`~repro.core.fleet.Fleet` (its bank).

    An unknown ``protocol`` raises unless ``missing_ok=True``, where the
    overhead mask is all-False (no leg calibrated, background moments still
    apply) — the behavior a protocol-free scenario already gets inside a
    union-namespace bank, which is what lets ``Fleet.stream`` apply one
    theta to chunks whose local namespace lacks the protocol entirely."""
    from repro.core.fleet import Fleet  # deferred: fleet sits above us

    if isinstance(source, Fleet):
        source = source.bank
    if not isinstance(source, (ScenarioBank, LegTable)):
        raise TypeError(
            "make_theta_mapper needs a LegTable, ScenarioBank, or Fleet: "
            f"{type(source)!r}"
        )
    if protocol in source.protocol_names:
        pid = source.protocol_names.index(protocol)
        mask = jnp.asarray(source.protocol_id == pid)
    elif missing_ok:
        mask = jnp.zeros(source.protocol_id.shape, bool)
    else:
        raise ValueError(
            f"protocol {protocol!r} not in {source.protocol_names} "
            "(missing_ok=True maps it to a no-op overhead mask)"
        )
    keep = jnp.asarray(source.keep_frac)
    if isinstance(source, ScenarioBank):
        link_scale = jnp.asarray(source.link_valid, jnp.float32)
    else:
        link_scale = jnp.ones((source.n_links,), jnp.float32)
    return functools.partial(_theta_to_params, keep, mask, link_scale)


def make_bank_theta_mapper(bank: ScenarioBank, protocol: str = "webdav"):
    """Deprecated alias: :func:`make_theta_mapper` now accepts banks (and
    fleets) directly."""
    return make_theta_mapper(bank, protocol)


def _eq1_coefficients(res: SimResult) -> jax.Array:
    """The paper's summary statistic: Eq.-1 OLS coefficients of the remote
    observations of one simulation (padded bank legs carry ``profile=-1``
    and are excluded by the profile filter)."""
    ds = observations(res, ProfileTag.REMOTE)
    # unfinished legs have no defined duration: drop them from the fit
    # explicitly (ds.valid already excludes ~done, but the zero weight is
    # the contract this regression relies on — keep it visible here)
    valid = ds.valid * res.done.astype(ds.valid.dtype)
    return fit_eq1(
        ds.transfer_time, ds.size_mb, ds.conth_mb, ds.conpr_mb, valid
    ).coef


def simulate_coefficients(
    spec: SimSpec,
    params: SimParams,
    key: jax.Array,
    *,
    backend: Optional[str] = None,
    n_replicates: int = 1,
    leap: bool = False,
) -> jax.Array:
    """Stochastic simulation(s) -> Eq.-1 coefficient triple (a, b, c).

    ``n_replicates > 1`` averages the coefficients of independent stochastic
    simulations under the same theta — a lower-variance summary statistic
    that sharpens the posterior at reduced presimulation budgets (the paper
    uses single-realization coefficients at 12.7M-tuple scale; we expose the
    replicate count as a knob and default to the faithful value 1).
    """

    def one(k: jax.Array) -> jax.Array:
        return _eq1_coefficients(simulate(spec, params, k, backend=backend, leap=leap))

    if n_replicates == 1:
        return one(key)
    keys = jax.random.split(key, n_replicates)
    return jnp.mean(jax.vmap(one)(keys), axis=0)


def presimulate(
    spec: SimSpec,
    theta_mapper,
    prior: PriorBox,
    key: jax.Array,
    n: int,
    *,
    backend: Optional[str] = None,
    batch: int = 512,
    n_replicates: int = 1,
    leap: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Draw thetas from the prior and simulate their coefficient triples.

    Returns ``(theta[n,3], x_sim[n,3])``. Executed in jit-batched chunks; on a
    mesh the caller shards ``key``/output batches over devices (see
    ``launch/calibrate.py``).
    """
    # repro: allow[jit-cache] -- intentionally per-call: closes over spec/prior/theta_mapper and is reused across every chunk of one presimulation, then dropped
    @functools.partial(jax.jit, static_argnames=("backend",))
    def _chunk(k, *, backend=backend):
        kt, ks = jax.random.split(k)
        u = jax.random.uniform(kt, (batch, 3))
        thetas = prior.from_unit(u)
        keys = jax.random.split(ks, batch)
        coefs = jax.vmap(
            lambda th, kk: simulate_coefficients(
                spec, theta_mapper(th), kk, backend=backend,
                n_replicates=n_replicates, leap=leap,
            )
        )(thetas, keys)
        return thetas, coefs

    outs_t, outs_x = [], []
    n_chunks = (n + batch - 1) // batch
    for i in range(n_chunks):
        key, sub = jax.random.split(key)
        t, x = _chunk(sub)
        outs_t.append(t)
        outs_x.append(x)
        if (i + 1) % max(n_chunks // 10, 1) == 0:
            log.info("presimulate: %d/%d chunks", i + 1, n_chunks)
    theta = jnp.concatenate(outs_t, axis=0)[:n]
    x = jnp.concatenate(outs_x, axis=0)[:n]
    return theta, x


def _as_fleet(bank_or_fleet):
    """Lift a bare bank into a :class:`~repro.core.fleet.Fleet` (the session
    façade every banked consumer now dispatches through); fleets pass
    through. Imported lazily — fleet sits above this module."""
    from repro.core.fleet import Fleet

    if isinstance(bank_or_fleet, Fleet):
        return bank_or_fleet
    return Fleet(bank_or_fleet)


def presimulate_bank(
    bank: ScenarioBank,
    prior: PriorBox,
    key: jax.Array,
    n_per_scenario: int,
    *,
    protocol: str = "webdav",
    backend: Optional[str] = None,
    batch: int = 128,
    leap: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Presimulate ``(theta, x_sim)`` tuples over **scenario variants**.

    Where :func:`presimulate` varies only theta against one frozen campaign,
    this draws every tuple against a scenario of the bank: the classifier
    then learns a likelihood ratio robust to campaign shape instead of one
    conditioned on a single workload realization. All scenarios and draws run
    through the single banked trace. The Eq.-1 summary statistic regresses
    remote-access observations, so draw the fleet from remote-bearing
    scenario families (scenarios without remote legs produce degenerate
    fits).

    ``bank`` may be a :class:`ScenarioBank`/:class:`BucketedBank` or a
    :class:`~repro.core.fleet.Fleet` (whose run defaults are honored:
    ``leap=None`` resolves to the fleet's ``leap``, which is ``False`` for a
    bare bank); :meth:`Fleet.presimulate` is the façade entry point.

    Returns ``(theta [n, 3], x_sim [n, 3], scenario_id [n] i32)`` with
    ``n = bank.n_scenarios * n_per_scenario``, scenario-major.
    """
    fleet = _as_fleet(bank)
    if leap is None:
        leap = fleet.leap
    bank = fleet.bank
    n_scn = bank.n_scenarios
    pid = bank.protocol_names.index(protocol)
    mask = jnp.asarray(bank.protocol_id == pid)  # [N, T]
    keep = jnp.asarray(bank.keep_frac)  # [N, T]
    link_valid = jnp.asarray(bank.link_valid, jnp.float32)  # [N, L]

    # repro: allow[jit-cache] -- intentionally per-call: closes over the bank's mask/keep tables and is reused across every chunk of one presimulation, then dropped
    @functools.partial(jax.jit, static_argnames=("backend",))
    def _chunk(k, *, backend=backend):
        kt, ks = jax.random.split(k)
        u = jax.random.uniform(kt, (n_scn, batch, 3))
        thetas = prior.from_unit(u)  # independent theta per (scenario, draw)
        keys = jax.random.split(ks, n_scn * batch).reshape(n_scn, batch, 2)
        # per-(scenario, draw) params, honoring the bank padding contract
        # (zero moments on padded links) exactly like make_bank_theta_mapper
        params = SimParams(
            keep_frac=jnp.where(
                mask[:, None, :], 1.0 - thetas[..., 0:1], keep[:, None, :]
            ),
            bg_mu=thetas[..., 1:2] * link_valid[:, None, :],
            bg_sigma=thetas[..., 2:3] * link_valid[:, None, :],
        )
        # dispatch through the fleet (not a pre-extracted monolithic spec): a
        # BucketedBank then runs each warm chunk through its sub-bank traces
        res = fleet.run(params, keys=keys, backend=backend, leap=leap)
        flat = jax.tree.map(
            lambda a: a.reshape((n_scn * batch,) + a.shape[2:]), res
        )
        coefs = jax.vmap(_eq1_coefficients)(flat).reshape(n_scn, batch, 3)
        return thetas, coefs

    outs_t, outs_x = [], []
    n_chunks = (n_per_scenario + batch - 1) // batch
    for i in range(n_chunks):
        key, sub = jax.random.split(key)
        t, x = _chunk(sub)
        outs_t.append(t)
        outs_x.append(x)
        if (i + 1) % max(n_chunks // 10, 1) == 0:
            log.info("presimulate_bank: %d/%d chunks x %d scenarios",
                     i + 1, n_chunks, n_scn)
    theta = jnp.concatenate(outs_t, axis=1)[:, :n_per_scenario]
    x = jnp.concatenate(outs_x, axis=1)[:, :n_per_scenario]
    scenario_id = jnp.repeat(jnp.arange(n_scn, dtype=jnp.int32), n_per_scenario)
    return (
        theta.reshape(-1, 3),
        x.reshape(-1, 3),
        scenario_id,
    )


def validate_bank(
    bank: ScenarioBank,
    theta_star: jax.Array,
    x_true: jax.Array,  # [3] shared or [N, 3] per-scenario references
    key: jax.Array,
    *,
    n_sims: int = 64,
    protocol: str = "webdav",
    backend: Optional[str] = None,
    leap: Optional[bool] = None,
) -> dict:
    """Validation sweep over scenario variants: ``n_sims`` stochastic
    replicas of every scenario under theta*, per-sim Eq.-1 fits, Eq.-6
    errors. ``theta_star`` may be one shared ``[3]`` vector or the
    per-scenario ``[N, 3]`` matrix of ``AmortizedPosterior.theta_star_all()``
    (row ``i`` parameterizes scenario ``i``), mirroring the ``x_true``
    broadcast. The whole (scenario x replica) sweep is one banked batch;
    ``bank`` may be a bank or a :class:`~repro.core.fleet.Fleet`
    (:meth:`Fleet.validate` is the façade entry point). ``leap=None``
    resolves to the fleet's run default; a bare bank keeps the historical
    ``leap=True`` validation default."""
    fleet = _as_fleet(bank)
    if leap is None:
        leap = fleet.leap if fleet is bank else True
    bank = fleet.bank
    mapper = make_theta_mapper(bank, protocol)
    params = mapper(jnp.asarray(theta_star))
    n_scn = bank.n_scenarios
    keys = jax.random.split(key, n_scn * n_sims).reshape(n_scn, n_sims, 2)
    res = fleet.run(params, keys=keys, backend=backend, leap=leap)

    flat = jax.tree.map(
        lambda a: a.reshape((n_scn * n_sims,) + a.shape[2:]), res
    )
    coefs = jax.vmap(_eq1_coefficients)(flat).reshape(n_scn, n_sims, 3)
    x_ref = jnp.asarray(x_true)
    if x_ref.ndim == 1:
        x_ref = jnp.broadcast_to(x_ref, (n_scn, 3))
    errors = jax.vmap(
        lambda c, xr: jax.vmap(lambda ci: coefficient_error(xr, ci))(c)
    )(coefs, x_ref)  # [N, R, 3]
    return {
        "coefficients": np.asarray(coefs),
        "errors": np.asarray(errors),
        "median_coef": np.asarray(jnp.median(coefs, axis=1)),  # [N, 3]
        "mean_abs_error": np.asarray(jnp.mean(errors, axis=1)),  # [N, 3]
        "sum_error": np.asarray(jnp.sum(errors, axis=2)),  # [N, R]
        "scenario_names": list(bank.names),
    }


def _feature_source(table) -> ScenarioBank:
    """The bank whose scenarios define the amortized context table (accepts
    a :class:`ScenarioBank`/:class:`BucketedBank` or a fleet)."""
    from repro.core.fleet import Fleet  # deferred: fleet sits above us

    if isinstance(table, Fleet):
        return table.bank
    if isinstance(table, ScenarioBank):
        return table
    raise TypeError(
        "amortized calibration needs a ScenarioBank/Fleet to derive scenario "
        f"features from (or an explicit features=[N, F] table); got "
        f"{type(table)!r}"
    )


def calibrate(
    spec: SimSpec,
    table: LegTable,
    x_true: jax.Array,
    key: jax.Array,
    cfg: CalibrationConfig = CalibrationConfig(),
    prior: Optional[PriorBox] = None,
    *,
    protocol: str = "webdav",
    backend: Optional[str] = None,
    presim: Optional[Tuple[jax.Array, ...]] = None,
    amortized: bool = False,
    features: Optional[jax.Array] = None,
) -> "CalibrationResult | AmortizedPosterior":
    """Full likelihood-free calibration of (overhead, mu, sigma).

    With an externally supplied ``presim = (theta, x_sim)`` the simulation
    stage is skipped entirely: ``spec`` may then be ``None`` and ``table``
    may be any :func:`make_theta_mapper` source (a bank/fleet included) —
    this is how :meth:`repro.Fleet.calibrate` reuses the pipeline over
    scenario variants.

    ``amortized=True`` trains a **scenario-conditioned** ratio net instead:
    ``presim`` must then be the 3-tuple ``(theta, x_sim, scenario_id)``
    (:func:`presimulate_bank`'s layout), each tuple is paired with its
    scenario's context row — ``features[scenario_id]``, where ``features``
    defaults to :func:`repro.core.workload.summary_features` of ``table``
    (a bank or fleet) — and the return value is an
    :class:`AmortizedPosterior` whose sampling methods run the per-scenario
    conditional MCMC on demand (no retraining per scenario). A trailing
    ``scenario_id`` column in ``presim`` is ignored when ``amortized`` is
    False, so ``Fleet.presimulate`` output can be passed through verbatim."""
    prior = prior or PriorBox.paper()
    key, k_pre, k_train, k_mcmc = jax.random.split(key, 4)

    scenario_id = None
    if presim is None:
        if amortized:
            raise ValueError(
                "amortized calibration needs presim=(theta, x_sim, "
                "scenario_id) — presimulate over a fleet first "
                "(Fleet.calibrate(amortized=True) does both)"
            )
        log.info("presimulating %d tuples (x%d replicates)",
                 cfg.n_presim, cfg.n_replicates)
        theta, x_sim = presimulate(
            spec, make_theta_mapper(table, protocol), prior, k_pre,
            cfg.n_presim, backend=backend,
            n_replicates=cfg.n_replicates, leap=cfg.use_leap,
        )
    elif len(presim) == 3:
        theta, x_sim, scenario_id = presim
    else:
        theta, x_sim = presim
    if amortized and scenario_id is None:
        raise ValueError(
            "amortized calibration needs the scenario_id column: pass "
            "presim=(theta, x_sim, scenario_id)"
        )

    x_low = jnp.asarray(cfg.x_low)
    x_high = jnp.asarray(cfg.x_high)
    proj_x = lambda x: jnp.clip((x - x_low) / (x_high - x_low), 0.0, 1.0)

    theta_u = prior.to_unit(theta)
    x_u = proj_x(x_sim)

    # one training block serves both modes: the unconditional path is the
    # context_dim=0 special case (pinned bit-compatible by the tests)
    feats = context = None
    names = ()
    if amortized:
        if features is not None:
            feats = jnp.asarray(features, jnp.float32)
            try:  # a bank/fleet still labels the scenarios, if one was given
                names = tuple(_feature_source(table).names)
            except TypeError:
                names = ()
        else:
            source = _feature_source(table)
            feats = jnp.asarray(summary_features(source), jnp.float32)
            names = tuple(source.names)
        if len(names) != feats.shape[0]:
            names = tuple(f"scenario{i}" for i in range(feats.shape[0]))
        scenario_id = jnp.asarray(scenario_id, jnp.int32)
        if (
            int(jnp.min(scenario_id)) < 0  # negative ids would wrap silently
            or int(jnp.max(scenario_id)) >= feats.shape[0]
        ):
            raise ValueError(
                f"scenario_id spans [{int(jnp.min(scenario_id))}, "
                f"{int(jnp.max(scenario_id))}] but the feature table has "
                f"{feats.shape[0]} scenarios"
            )
        x_true = jnp.asarray(x_true)
        if x_true.ndim not in (1, 2) or x_true.shape[-1] != 3 or (
            x_true.ndim == 2 and x_true.shape[0] != feats.shape[0]
        ):
            raise ValueError(
                "amortized x_true must be one shared [3] observation or a "
                f"per-scenario [{feats.shape[0]}, 3] matrix (row i pairs "
                f"with scenario i); got shape {x_true.shape}"
            )
        context = feats[scenario_id]  # [n, F], paired with (theta, x) rows

    ctx_dim = 0 if feats is None else int(feats.shape[1])
    log.info("training %sAALR classifier (%d tuples, %d epochs%s)",
             "conditional " if amortized else "", theta.shape[0], cfg.epochs,
             f", {ctx_dim} context features" if amortized else "")
    clf_cfg = ClassifierConfig(theta_dim=3, x_dim=3, context_dim=ctx_dim,
                               lr=cfg.lr)
    params, metrics = train_classifier(
        k_train, clf_cfg, theta_u, x_u, context,
        epochs=cfg.epochs, batch_size=cfg.batch_size,
    )
    log.info("classifier: loss=%.4f acc=%.3f",
             float(metrics.loss), float(metrics.accuracy))

    if amortized:
        return AmortizedPosterior(
            classifier_params=params,
            features=feats,
            prior=prior,
            x_true_unit=proj_x(x_true),
            cfg=cfg,
            scenario_names=names,
            train_loss=float(metrics.loss),
            train_accuracy=float(metrics.accuracy),
        )

    res, rhat = mcmc_lib.run_chains(
        params, proj_x(x_true), k_mcmc,
        n_chains=cfg.n_chains, n_samples=cfg.n_mcmc,
        burn_in=cfg.burn_in, step_size=cfg.step_size,
        adaptive=cfg.adaptive_mcmc,
    )
    log.info("mcmc accept rate: %.3f, split-R-hat: %s",
             float(res.accept_rate), np.asarray(rhat).round(3))
    if float(jnp.max(rhat)) > 1.2:
        log.warning("MCMC may not have converged (max R-hat %.2f) — "
                    "increase n_mcmc/burn_in", float(jnp.max(rhat)))
    mode_u = mcmc_lib.posterior_mode(res.samples)
    theta_star = prior.from_unit(mode_u)
    # beyond-paper: the chain state maximizing the approximate likelihood
    # ratio at x_true is a MAP estimate under the uniform prior — sharper
    # than per-axis marginal modes when the posterior is correlated.
    map_u = res.samples[jnp.argmax(res.log_ratios)]
    theta_map = prior.from_unit(map_u)
    log.info("theta* (marginal modes) = %s ; theta_MAP (ratio argmax) = %s",
             np.asarray(theta_star), np.asarray(theta_map))
    return CalibrationResult(
        theta_star=theta_star,
        theta_map=theta_map,
        posterior_samples=prior.from_unit(res.samples),
        accept_rate=res.accept_rate,
        classifier_params=params,
        x_true=x_true,
        rhat=rhat,
        epoch_loss=metrics.epoch_loss,
    )


def validate(
    spec: SimSpec,
    table: LegTable,
    theta_star: jax.Array,
    x_true: jax.Array,
    key: jax.Array,
    *,
    n_sims: int = 256,
    protocol: str = "webdav",
    backend: Optional[str] = None,
    n_replicates: int = 1,
    leap: bool = True,
) -> dict:
    """Paper Fig. 6 / Table 1: stochastic simulations under theta*, per-sim
    Eq.-1 fits, Eq.-6 errors against x_true."""
    mapper = make_theta_mapper(table, protocol)
    params = mapper(theta_star)
    keys = jax.random.split(key, n_sims)
    coefs = jax.lax.map(
        lambda k: simulate_coefficients(
            spec, params, k, backend=backend, n_replicates=n_replicates,
            leap=leap,
        ),
        keys,
        batch_size=min(64, n_sims),
    )
    errors = jax.vmap(lambda c: coefficient_error(x_true, c))(coefs)
    return {
        "coefficients": np.asarray(coefs),
        "errors": np.asarray(errors),
        "median_coef": np.asarray(jnp.median(coefs, axis=0)),
        "mean_abs_error": np.asarray(jnp.mean(errors, axis=0)),
        "sum_error": np.asarray(jnp.sum(errors, axis=1)),
    }
