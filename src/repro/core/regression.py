"""No-intercept OLS regression & the paper's evaluation statistics.

The paper fits ``T = 0 + a*S + b*ConTh + c*ConPr`` (Eq. 1, remote access) and
``T = 0 + a*S + b*ConPr`` (Eq. 2, placement/stage-in), reports the
F-statistic of the no-intercept fit, and scores simulations by the relative
coefficient error ``E(coef_sim) = |coef_true - coef_sim| / coef_true``
(Eq. 6).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["OLSFit", "ols_no_intercept", "fit_eq1", "fit_eq2", "coefficient_error"]


class OLSFit(NamedTuple):
    coef: jax.Array  # [k]
    f_statistic: jax.Array  # []
    r_squared: jax.Array  # [] uncentered R^2 (no-intercept convention)
    df_model: jax.Array  # [] = k
    df_resid: jax.Array  # [] = n_obs - k


def ols_no_intercept(
    X: jax.Array,  # [n, k]
    y: jax.Array,  # [n]
    weights: Optional[jax.Array] = None,  # [n] 0/1 validity mask
) -> OLSFit:
    """Closed-form no-intercept OLS with an optional observation mask.

    Masked rows are zeroed out of the normal equations, matching dropping
    them; the degrees of freedom use the effective observation count.
    """
    X = X.astype(jnp.float64) if jax.config.read("jax_enable_x64") else X.astype(jnp.float32)
    y = y.astype(X.dtype)
    n, k = X.shape
    if weights is None:
        w = jnp.ones((n,), X.dtype)
    else:
        w = weights.astype(X.dtype)
    Xw = X * w[:, None]
    yw = y * w
    # full f32 in the normal equations and the solve: a TPU runs f32 dots
    # at one bf16 pass by default, and X^T X squares the regressors' range
    with jax.default_matmul_precision("highest"):
        xtx = Xw.T @ Xw
        xty = Xw.T @ yw
        # ridge epsilon for numerical safety on near-collinear masks
        eye = jnp.eye(k, dtype=X.dtype)
        coef = jnp.linalg.solve(xtx + 1e-8 * eye, xty)
        resid = (yw - Xw @ coef) * 1.0
    n_eff = jnp.sum(w)
    ss_res = jnp.sum(resid**2)
    ss_tot = jnp.sum(yw**2)  # uncentered: no-intercept convention (as in R)
    ss_reg = ss_tot - ss_res
    df_model = jnp.asarray(k, X.dtype)
    df_resid = jnp.maximum(n_eff - k, 1.0)
    f_stat = (ss_reg / df_model) / jnp.maximum(ss_res / df_resid, 1e-30)
    r2 = 1.0 - ss_res / jnp.maximum(ss_tot, 1e-30)
    return OLSFit(coef=coef, f_statistic=f_stat, r_squared=r2,
                  df_model=df_model, df_resid=df_resid)


def fit_eq1(
    transfer_time: jax.Array,
    size_mb: jax.Array,
    conth_mb: jax.Array,
    conpr_mb: jax.Array,
    valid: Optional[jax.Array] = None,
) -> OLSFit:
    """Paper Eq. 1: T ~ 0 + a*S + b*ConTh + c*ConPr (remote data access)."""
    X = jnp.stack([size_mb, conth_mb, conpr_mb], axis=-1)
    return ols_no_intercept(X, transfer_time, valid)


def fit_eq2(
    transfer_time: jax.Array,
    size_mb: jax.Array,
    conpr_mb: jax.Array,
    valid: Optional[jax.Array] = None,
) -> OLSFit:
    """Paper Eq. 2: T ~ 0 + a*S + b*ConPr (data-placement / stage-in)."""
    X = jnp.stack([size_mb, conpr_mb], axis=-1)
    return ols_no_intercept(X, transfer_time, valid)


def coefficient_error(coef_true: jax.Array, coef_sim: jax.Array) -> jax.Array:
    """Paper Eq. 6: elementwise relative coefficient error."""
    return jnp.abs(coef_true - coef_sim) / jnp.abs(coef_true)
