"""Fused SELU-MLP forward Pallas kernel (the AALR ratio classifier).

The MCMC sampler evaluates the 4x128 SELU classifier millions of times per
chain; fusing the five matmuls keeps every intermediate activation in VMEM
(the whole weight stack is < 100 KB). The kernel tiles over the row dimension
and chains the layers on the MXU without touching HBM in between.

Feature dimensions are zero-padded to lane width by the wrapper; SELU(0) = 0,
and zero-padded weight rows/cols contribute nothing, so padding is inert
through every hidden layer (biases are zero in padded columns).

Mosaic kernels have no reverse-mode rule, so :func:`selu_mlp` pairs the
kernel forward with a backward pass through the XLA reference
(:func:`repro.kernels.ref.selu_mlp`, re-run from the saved inputs): the
classifier trains with the kernel in its forward pass on a TPU, and the
MCMC scores with the same kernel.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from repro.kernels import ref

__all__ = ["selu_mlp", "selu_mlp_pallas"]

_LANE = 128
_ALPHA = 1.6732632423543772848170429916717
_SCALE = 1.0507009873554804934193349852946


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad)


def _selu(h: jax.Array) -> jax.Array:
    return _SCALE * jnp.where(h > 0, h, _ALPHA * (jnp.exp(h) - 1.0))


def _mlp_kernel(x_ref, *refs):
    n_layers = (len(refs) - 1) // 2
    w_refs = refs[:n_layers]
    b_refs = refs[n_layers : 2 * n_layers]
    out_ref = refs[-1]
    h = x_ref[...].astype(jnp.float32)
    for i in range(n_layers):
        h = (
            jax.lax.dot_general(
                h,
                w_refs[i][...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + b_refs[i][...].astype(jnp.float32)
        )
        if i < n_layers - 1:
            h = _selu(h)
    out_ref[...] = h


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def selu_mlp_pallas(
    x: jax.Array,  # [N, F_in]
    weights: Tuple[jax.Array, ...],
    biases: Tuple[jax.Array, ...],
    *,
    interpret: bool = False,
    block_n: int = 512,
) -> jax.Array:
    N, f_in = x.shape
    f_out = weights[-1].shape[1]
    dtype = x.dtype

    xp = _pad_axis(_pad_axis(x, 1, _LANE), 0, 8)
    wp = []
    bp = []
    for w, b in zip(weights, biases):
        wp.append(_pad_axis(_pad_axis(w, 0, _LANE), 1, _LANE))
        bp.append(_pad_axis(b[None, :], 1, _LANE))
    Np = xp.shape[0]
    bn = min(block_n, Np)
    xp = _pad_axis(xp, 0, bn)
    Np = xp.shape[0]
    grid = (Np // bn,)

    in_specs = [pl.BlockSpec((bn, xp.shape[1]), lambda i: (i, 0))]
    for w in wp:
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
    for b in bp:
        in_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0)))

    out = pl.pallas_call(
        _mlp_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, wp[-1].shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, wp[-1].shape[1]), jnp.float32),
        interpret=interpret,
    )(xp, *wp, *bp)
    return out[:N, :f_out].astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def selu_mlp(
    x: jax.Array,
    weights: Tuple[jax.Array, ...],
    biases: Tuple[jax.Array, ...],
    interpret: bool = False,
) -> jax.Array:
    """Differentiable :func:`selu_mlp_pallas`: kernel forward, XLA backward."""
    return selu_mlp_pallas(x, weights, biases, interpret=interpret)


def _selu_mlp_fwd(x, weights, biases, interpret):
    return selu_mlp(x, weights, biases, interpret), (x, weights, biases)


def _selu_mlp_bwd(interpret, saved, g):
    return jax.vjp(ref.selu_mlp, *saved)[1](g)


selu_mlp.defvjp(_selu_mlp_fwd, _selu_mlp_bwd)
