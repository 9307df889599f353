"""AALR ratio classifier (paper Section 5), optionally scenario-conditioned.

A SELU MLP with 4 hidden layers x 128 units is trained to distinguish
dependent tuples ``(theta, x ~ p(x|theta))`` (label 1) from marginal tuples
``(theta, x ~ p(x))`` (label 0). Its logit is the log likelihood-to-marginal
ratio ``log r(x|theta)`` used by the likelihood-free MCMC
(Hermans & Begy, "hypothesis", 2019).

Beyond-paper: with ``ClassifierConfig(context_dim=F)`` the net additionally
conditions on a per-tuple **scenario context vector** (campaign summary
features, see :func:`repro.core.workload.summary_features`). The marginal
class is still built by shuffling theta only — ``(x, context)`` stays
paired — so the logit estimates the *conditional* ratio
``log r(x | theta, s)`` and one trained net amortizes the posterior over
every scenario family (cf. CGSim's scalable-evaluation gap,
arXiv:2510.00822). ``context_dim=0`` (the default) is bit-compatible with
the unconditional classifier.

Inputs are projected onto (0, 1) with the prior/observation bounds before
entering the net, as in the paper ("the dataset is projected onto the
interval (0,1) to stabilize the training").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.train.optimizer import AdamWConfig, AdamWState, adamw_init, adamw_update

__all__ = [
    "ClassifierConfig",
    "init_classifier",
    "classifier_logit",
    "log_ratio",
    "bce_loss",
    "train_classifier",
    "epoch_batch_starts",
    "TrainMetrics",
]

PyTree = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class ClassifierConfig:
    theta_dim: int = 3
    x_dim: int = 3
    context_dim: int = 0  # scenario summary features (0 = unconditional)
    hidden: int = 128
    depth: int = 4  # hidden layers (paper: 4 x 128, SELU)
    lr: float = 1e-4  # paper: ADAM, lr = 0.0001

    @property
    def in_dim(self) -> int:
        return self.theta_dim + self.x_dim + self.context_dim


def init_classifier(key: jax.Array, cfg: ClassifierConfig) -> PyTree:
    dims = [cfg.in_dim] + [cfg.hidden] * cfg.depth + [1]
    params: PyTree = {}
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        key, sub = jax.random.split(key)
        # LeCun-normal init (the SELU-correct initialization)
        params[f"w{i}"] = jax.random.normal(sub, (din, dout), jnp.float32) * (
            din ** -0.5
        )
        params[f"b{i}"] = jnp.zeros((dout,), jnp.float32)
    return params


def _split(params: PyTree) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    n = len(params) // 2
    ws = tuple(params[f"w{i}"] for i in range(n))
    bs = tuple(params[f"b{i}"] for i in range(n))
    return ws, bs


def classifier_logit(
    params: PyTree,
    theta: jax.Array,
    x: jax.Array,
    context: jax.Array | None = None,
    *,
    backend: str | None = None,
) -> jax.Array:
    """Logit of d(theta, x[, context]); inputs are assumed already projected
    to (0,1). ``context`` is the per-tuple scenario feature vector of a
    conditional net (``None`` and a zero-width array are equivalent — both
    reproduce the unconditional logit bitwise)."""
    parts = [theta, x] if context is None else [theta, x, context]
    inp = jnp.concatenate(parts, axis=-1)
    squeeze = inp.ndim == 1
    if squeeze:
        inp = inp[None]
    ws, bs = _split(params)
    out = ops.selu_mlp(inp, ws, bs, backend=backend)[..., 0]
    return out[0] if squeeze else out


def log_ratio(
    params: PyTree,
    theta: jax.Array,
    x: jax.Array,
    context: jax.Array | None = None,
    *,
    backend: str | None = None,
) -> jax.Array:
    """log r(x|theta[, s]) = logit(d); the AALR identity (conditional when
    the net was trained with a scenario context)."""
    return classifier_logit(params, theta, x, context, backend=backend)


def bce_loss(
    params: PyTree,
    theta: jax.Array,  # [N, theta_dim]
    x: jax.Array,  # [N, x_dim]
    labels: jax.Array,  # [N] in {0, 1}
    context: jax.Array | None = None,  # [N, context_dim]
) -> jax.Array:
    logits = classifier_logit(params, theta, x, context)
    return jnp.mean(
        jnp.maximum(logits, 0.0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


class TrainMetrics(NamedTuple):
    loss: jax.Array  # last minibatch
    accuracy: jax.Array  # last minibatch
    # mean minibatch loss of the epoch (``_train_epoch``), or ``[epochs]``
    # of them — the training curve (``train_classifier``)
    epoch_loss: jax.Array | None = None


def _make_batch(
    theta: jax.Array,
    x: jax.Array,
    context: jax.Array,
    order: jax.Array,
    perm: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Assemble one half-dependent / half-marginal training batch.

    Only theta is shuffled for the marginal class: ``(x, context)`` stays
    paired, so a conditional net sees ``theta ~ p(theta)`` against the
    *scenario-matched* marginal ``x ~ p(x|s)`` — the construction that makes
    the logit the conditional ratio ``log r(x | theta, s)``."""
    bt, bx, bc = theta[order], x[order], context[order]
    half = bt.shape[0] // 2
    theta_in = jnp.concatenate([bt[:half], bt[perm][half:]], axis=0)
    x_in = jnp.concatenate([bx[:half], bx[half:]], axis=0)
    ctx_in = jnp.concatenate([bc[:half], bc[half:]], axis=0)
    labels = jnp.concatenate([jnp.ones((half,)), jnp.zeros((bt.shape[0] - half,))])
    return theta_in, x_in, ctx_in, labels


def epoch_batch_starts(n: int, batch_size: int) -> np.ndarray:
    """Start offsets of one epoch's minibatch slices into the shuffled order.

    ``ceil(n / batch_size)`` fixed-size steps; the final step is shifted back
    to end exactly at ``n``, so the ``n % batch_size`` tail tuples train
    every epoch (overlapping the previous step) instead of being silently
    dropped. For ``batch_size | n`` this is exactly ``0, batch_size, ...``
    — the historical schedule, bit for bit."""
    if batch_size > n:
        raise ValueError(f"batch_size {batch_size} exceeds n {n}")
    steps = max(-(-n // batch_size), 1)
    return np.minimum(
        np.arange(steps, dtype=np.int64) * batch_size, n - batch_size
    ).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("batch_size",), donate_argnums=(0, 1))
def _train_epoch(
    params: PyTree,
    opt_state: AdamWState,
    theta: jax.Array,
    x: jax.Array,
    context: jax.Array,
    key: jax.Array,
    lr: jax.Array,
    *,
    batch_size: int,
) -> Tuple[PyTree, AdamWState, TrainMetrics]:
    cfg = AdamWConfig(lr=lambda step: lr)
    n = theta.shape[0]
    k_order, k_scan = jax.random.split(key)
    order = jax.random.permutation(k_order, n)
    starts = jnp.asarray(epoch_batch_starts(n, batch_size))
    step_keys = jax.random.split(k_scan, len(starts))

    def step(carry, inp):
        params, opt_state = carry
        start, k = inp
        idx = jax.lax.dynamic_slice_in_dim(order, start, batch_size)
        perm = jax.random.permutation(k, batch_size)
        theta_in, x_in, ctx_in, labels = _make_batch(theta, x, context, idx, perm)
        loss, grads = jax.value_and_grad(bce_loss)(
            params, theta_in, x_in, labels, ctx_in
        )
        new_params, new_state, _ = adamw_update(grads, opt_state, params, cfg)
        logits = classifier_logit(new_params, theta_in, x_in, ctx_in)
        acc = jnp.mean(((logits > 0) == (labels > 0.5)).astype(jnp.float32))
        return (new_params, new_state), TrainMetrics(loss=loss, accuracy=acc)

    (params, opt_state), ms = jax.lax.scan(
        step, (params, opt_state), (starts, step_keys)
    )
    metrics = TrainMetrics(
        loss=ms.loss[-1], accuracy=ms.accuracy[-1], epoch_loss=jnp.mean(ms.loss)
    )
    return params, opt_state, metrics


def train_classifier(
    key: jax.Array,
    cfg: ClassifierConfig,
    theta: jax.Array,  # [N, theta_dim] projected to (0,1)
    x: jax.Array,  # [N, x_dim] projected to (0,1)
    context: jax.Array | None = None,  # [N, context_dim] projected to (0,1)
    *,
    epochs: int = 10,
    batch_size: int = 4096,
) -> Tuple[PyTree, TrainMetrics]:
    """Train the ratio classifier on dependent/marginal pairs.

    The marginal class is constructed by shuffling theta within the batch —
    the standard AALR trick: ``(theta_perm, x)`` has ``x ~ p(x)`` w.r.t. the
    paired theta. With ``cfg.context_dim > 0`` each tuple carries a scenario
    ``context`` row that stays paired with its x under the shuffle, making
    the learned ratio conditional on the scenario. Each epoch is one jit'd
    ``lax.scan`` over minibatches; a non-divisible ``n`` folds the tail into
    a final overlapping step (see :func:`epoch_batch_starts`) — no tuple is
    dropped.
    """
    n = theta.shape[0]
    if context is None:
        context = jnp.zeros((n, 0), theta.dtype)
    if context.ndim != 2 or context.shape[0] != n:
        raise ValueError(f"context must be [n={n}, context_dim]: {context.shape}")
    if context.shape[1] != cfg.context_dim:
        raise ValueError(
            f"context width {context.shape[1]} != cfg.context_dim "
            f"{cfg.context_dim}"
        )
    batch_size = min(batch_size, n)
    key, init_key = jax.random.split(key)
    params = init_classifier(init_key, cfg)
    opt_state = adamw_init(params, AdamWConfig(lr=cfg.lr))
    lr = jnp.asarray(cfg.lr, jnp.float32)
    metrics = TrainMetrics(jnp.asarray(0.0), jnp.asarray(0.0))
    curve = []
    for _ in range(epochs):
        key, epoch_key = jax.random.split(key)
        params, opt_state, metrics = _train_epoch(
            params, opt_state, theta, x, context, epoch_key, lr,
            batch_size=batch_size,
        )
        curve.append(metrics.epoch_loss)
    return params, metrics._replace(
        epoch_loss=jnp.stack(curve) if curve else jnp.zeros((0,))
    )
