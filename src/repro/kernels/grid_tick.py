"""Pallas TPU kernels for the GDAPS fair-share transfer tick.

The tick is three one-hot segment matmuls plus elementwise math (see
``repro.kernels.ref.grid_tick``). For the calibration workload the batch of
concurrent simulations ``B`` is huge (10^4-10^7 across the mesh) while the
per-campaign dimensions are small (legs T ~ 10^2-10^3, procs P <= T, links L
~ 10^0-10^2), so the kernels tile over B and keep the full incidence
matrices resident in VMEM — every matmul then runs on the MXU with no HBM
round-trips between the fused stages.

Three kernels share that layout:

- ``grid_tick_pallas`` — one tick, one campaign's incidences broadcast to
  every batch block;
- ``grid_tick_bank_pallas`` — one tick of a **scenario bank** (per-scenario
  incidence operands, grid over ``(scenario, replica-block)``);
- ``grid_tick_bank_fused_pallas`` — ``K`` ticks of a scenario bank in one
  launch: the whole simulation carry (remaining/done/started/clock/
  concurrency accumulators/background loads) stays resident in VMEM across
  the in-kernel tick loop and is written back to HBM once per window, with
  an early exit as soon as a tile's replicas have all finished.

Padding contract (enforced by the wrappers): T/P/L are padded to lane
multiples. Padded legs are inactive with all-zero one-hot rows and are
**born done** (``done`` state is padded with 1.0, never 0 — the fused
kernel's all-done early exit reduces over the padded lane dim); padded
links have zero bandwidth and a background period of 1 (periods are
divisors, never 0); padded replica rows are likewise born done so they
neither transfer nor keep a tile alive. Under that contract the fair-share
math moves exactly zero bytes through padding, so it is semantically inert
for single ticks and across every tick of a fused window.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from repro.kernels.pallas_compat import tpu_compiler_params

__all__ = [
    "grid_tick_pallas",
    "grid_tick_bank_pallas",
    "grid_tick_bank_fused_pallas",
]

_LANE = 128
_SUBLANE = 8
#: scoped VMEM a Mosaic kernel gets unless it asks for more (TPU v5e), and
#: the most the fused kernel asks for (the v5e holds 128 MiB)
_VMEM_SCOPED = 16 * 2**20
_VMEM_MAX = 96 * 2**20


def _dot(a: jax.Array, b: jax.Array, *, exact: bool = False) -> jax.Array:
    """``[M, K] x [K, N]`` on the MXU. ``exact=True`` for contractions that
    carry MB values or counts above 256: an f32 dot otherwise defaults to
    one bf16 pass, which rounds every operand to 8 mantissa bits. The 0/1
    mask contractions are exact in one pass."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32,
    )


def _dot_t(a: jax.Array, b: jax.Array, *, exact: bool = False) -> jax.Array:
    """``[M, K] x [N, K]^T`` (gathers against a transposed incidence); see
    :func:`_dot` for ``exact``."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32,
    )


def _pad_to(x: jax.Array, axis: int, mult: int, value: float = 0) -> jax.Array:
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=value)


def _tick_kernel(
    active_ref,  # [Bb, T]
    remaining_ref,  # [Bb, T]
    bg_ref,  # [Bb, L]
    keep_ref,  # [1, T]
    bw_ref,  # [1, L]
    m_tp_ref,  # [T, P]
    m_pl_ref,  # [P, L]
    m_tl_ref,  # [T, L]
    xfer_ref,  # [Bb, T] out
    proc_ref,  # [Bb, P] out
    link_ref,  # [Bb, L] out
):
    f32 = jnp.float32
    active = active_ref[...].astype(f32)
    remaining = remaining_ref[...].astype(f32)
    m_tp = m_tp_ref[...]
    m_pl = m_pl_ref[...]
    m_tl = m_tl_ref[...]

    threads = _dot(active, m_tp)  # threads per process: [Bb, P]
    proc_active = (threads > 0).astype(f32)
    campaign = _dot(proc_active, m_pl)  # campaign processes per link: [Bb, L]
    denom = jnp.maximum(campaign + jnp.maximum(bg_ref[...].astype(f32), 0.0), 1.0)
    per_proc = bw_ref[...].astype(f32) / denom  # [Bb, L]
    # gather to legs: one-hot matmuls against the transposed incidences
    per_proc_leg = _dot_t(per_proc, m_tl, exact=True)  # [Bb, T]
    threads_leg = jnp.maximum(_dot_t(threads, m_tp, exact=True), 1.0)
    chunk = active * keep_ref[...].astype(f32) * per_proc_leg / threads_leg
    xfer = jnp.minimum(remaining, chunk)
    xfer_ref[...] = xfer
    proc_ref[...] = _dot(xfer, m_tp, exact=True)
    link_ref[...] = _dot(xfer, m_tl, exact=True)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def grid_tick_pallas(
    active: jax.Array,  # [T] or [B, T]
    remaining: jax.Array,
    keep_frac: jax.Array,  # [T]
    bg_load: jax.Array,  # [L] or [B, L]
    bandwidth: jax.Array,  # [L]
    leg_proc: jax.Array,  # [T, P]
    proc_link: jax.Array,  # [P, L]
    leg_link: jax.Array,  # [T, L]
    *,
    interpret: bool = False,
    block_b: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    unbatched = active.ndim == 1
    if unbatched:
        active = active[None]
        remaining = remaining[None]
        bg_load = bg_load[None]
    B, T = active.shape
    P = leg_proc.shape[1]
    L = proc_link.shape[1]

    # zero-pad every axis to hardware-friendly multiples
    active_p = _pad_to(_pad_to(active, 1, _LANE), 0, _SUBLANE)
    remaining_p = _pad_to(_pad_to(remaining, 1, _LANE), 0, _SUBLANE)
    bg_p = _pad_to(_pad_to(bg_load, 1, _LANE), 0, _SUBLANE)
    keep_p = _pad_to(keep_frac[None, :], 1, _LANE)
    bw_p = _pad_to(bandwidth[None, :], 1, _LANE)
    m_tp = _pad_to(_pad_to(leg_proc, 0, _LANE), 1, _LANE)
    m_pl = _pad_to(_pad_to(proc_link, 0, _LANE), 1, _LANE)
    m_tl = _pad_to(_pad_to(leg_link, 0, _LANE), 1, _LANE)
    Bp, Tp = active_p.shape
    Pp, Lp = m_pl.shape

    bb = min(block_b, Bp)
    # block the batch; broadcast the campaign constants to every block
    grid = (Bp // bb,) if Bp % bb == 0 else (-(-Bp // bb),)
    active_p = _pad_to(active_p, 0, bb)
    remaining_p = _pad_to(remaining_p, 0, bb)
    bg_p = _pad_to(bg_p, 0, bb)
    Bp = active_p.shape[0]
    grid = (Bp // bb,)

    batch_spec = lambda w: pl.BlockSpec((bb, w), lambda i: (i, 0))
    const_spec = lambda h, w: pl.BlockSpec((h, w), lambda i: (0, 0))

    out_shape = (
        jax.ShapeDtypeStruct((Bp, Tp), jnp.float32),
        jax.ShapeDtypeStruct((Bp, Pp), jnp.float32),
        jax.ShapeDtypeStruct((Bp, Lp), jnp.float32),
    )
    xfer, proc_xfer, link_xfer = pl.pallas_call(
        _tick_kernel,
        grid=grid,
        in_specs=[
            batch_spec(Tp),
            batch_spec(Tp),
            batch_spec(Lp),
            const_spec(1, Tp),
            const_spec(1, Lp),
            const_spec(Tp, Pp),
            const_spec(Pp, Lp),
            const_spec(Tp, Lp),
        ],
        out_specs=(
            pl.BlockSpec((bb, Tp), lambda i: (i, 0)),
            pl.BlockSpec((bb, Pp), lambda i: (i, 0)),
            pl.BlockSpec((bb, Lp), lambda i: (i, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(active_p, remaining_p, bg_p, keep_p, bw_p, m_tp, m_pl, m_tl)

    xfer = xfer[:B, :T]
    proc_xfer = proc_xfer[:B, :P]
    link_xfer = link_xfer[:B, :L]
    if unbatched:
        return xfer[0], proc_xfer[0], link_xfer[0]
    return xfer, proc_xfer, link_xfer


# ---------------------------------------------------------------------------
# bank-tiled variant: per-scenario incidence matrices, grid over
# (scenario, replica-block)
# ---------------------------------------------------------------------------

def _bank_tick_kernel(
    active_ref,  # [1, Rb, T]
    remaining_ref,  # [1, Rb, T]
    bg_ref,  # [1, Rb, L]
    keep_ref,  # [1, 1, T] bank-wide, or [1, Rb, T] per-replica keeps
    bw_ref,  # [1, 1, L]
    m_tp_ref,  # [1, T, P]
    m_pl_ref,  # [1, P, L]
    m_tl_ref,  # [1, T, L]
    xfer_ref,  # [1, Rb, T] out
    proc_ref,  # [1, Rb, P] out
    link_ref,  # [1, Rb, L] out
):
    f32 = jnp.float32
    active = active_ref[0].astype(f32)
    remaining = remaining_ref[0].astype(f32)
    m_tp = m_tp_ref[0]
    m_pl = m_pl_ref[0]
    m_tl = m_tl_ref[0]

    threads = _dot(active, m_tp)  # [Rb, P]
    proc_active = (threads > 0).astype(f32)
    campaign = _dot(proc_active, m_pl)  # [Rb, L]
    denom = jnp.maximum(campaign + jnp.maximum(bg_ref[0].astype(f32), 0.0), 1.0)
    per_proc = bw_ref[0].astype(f32) / denom  # [Rb, L]
    per_proc_leg = _dot_t(per_proc, m_tl, exact=True)  # [Rb, T]
    threads_leg = jnp.maximum(_dot_t(threads, m_tp, exact=True), 1.0)
    chunk = active * keep_ref[0].astype(f32) * per_proc_leg / threads_leg
    xfer = jnp.minimum(remaining, chunk)
    xfer_ref[0] = xfer
    proc_ref[0] = _dot(xfer, m_tp, exact=True)
    link_ref[0] = _dot(xfer, m_tl, exact=True)


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def grid_tick_bank_pallas(
    active: jax.Array,  # [S, R, T]
    remaining: jax.Array,  # [S, R, T]
    keep_frac: jax.Array,  # [S, T] or [S, R, T] (per-replica keeps)
    bg_load: jax.Array,  # [S, R, L]
    bandwidth: jax.Array,  # [S, L]
    leg_proc: jax.Array,  # [S, T, P]
    proc_link: jax.Array,  # [S, P, L]
    leg_link: jax.Array,  # [S, T, L]
    *,
    interpret: bool = False,
    block_r: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fair-share tick for a **scenario bank**: the incidence matrices carry a
    leading scenario dim instead of being broadcast constants. The grid runs
    ``(scenario, replica-block)``; each scenario's incidences stay resident in
    VMEM across its replica blocks, so heterogeneous campaigns batch without
    retraces or HBM round-trips between the fused matmul stages.

    ``keep_frac`` may carry a replica dim (one theta draw per replica, as the
    calibration presimulation sweeps do); bank-wide ``[S, T]`` keeps are
    broadcast to the replica blocks.

    The single-campaign padding contract applies per scenario: padded legs
    are inactive with all-zero one-hot rows, padded links have zero
    bandwidth, so padding transfers exactly nothing.
    """
    S, R, T = active.shape
    P = leg_proc.shape[2]
    L = proc_link.shape[2]
    # bank-wide keeps stay a single [S, 1, T] row per scenario (the kernel
    # broadcasts over the replica block); only genuinely per-replica keeps
    # pay the [S, R, T] operand
    per_replica_keep = keep_frac.ndim == 3

    active_p = _pad_to(_pad_to(active, 2, _LANE), 1, _SUBLANE)
    remaining_p = _pad_to(_pad_to(remaining, 2, _LANE), 1, _SUBLANE)
    bg_p = _pad_to(_pad_to(bg_load, 2, _LANE), 1, _SUBLANE)
    if per_replica_keep:
        keep_p = _pad_to(_pad_to(keep_frac, 2, _LANE), 1, _SUBLANE)
    else:
        keep_p = _pad_to(keep_frac[:, None, :], 2, _LANE)
    bw_p = _pad_to(bandwidth[:, None, :], 2, _LANE)
    m_tp = _pad_to(_pad_to(leg_proc, 1, _LANE), 2, _LANE)
    m_pl = _pad_to(_pad_to(proc_link, 1, _LANE), 2, _LANE)
    m_tl = _pad_to(_pad_to(leg_link, 1, _LANE), 2, _LANE)
    Tp = active_p.shape[2]
    Pp, Lp = m_pl.shape[1], m_pl.shape[2]

    rb = min(block_r, active_p.shape[1])
    active_p = _pad_to(active_p, 1, rb)
    remaining_p = _pad_to(remaining_p, 1, rb)
    bg_p = _pad_to(bg_p, 1, rb)
    if per_replica_keep:
        keep_p = _pad_to(keep_p, 1, rb)
    Rp = active_p.shape[1]
    grid = (S, Rp // rb)

    rep_spec = lambda w: pl.BlockSpec((1, rb, w), lambda s, r: (s, r, 0))
    scn_spec = lambda h, w: pl.BlockSpec((1, h, w), lambda s, r: (s, 0, 0))

    out_shape = (
        jax.ShapeDtypeStruct((S, Rp, Tp), jnp.float32),
        jax.ShapeDtypeStruct((S, Rp, Pp), jnp.float32),
        jax.ShapeDtypeStruct((S, Rp, Lp), jnp.float32),
    )
    xfer, proc_xfer, link_xfer = pl.pallas_call(
        _bank_tick_kernel,
        grid=grid,
        in_specs=[
            rep_spec(Tp),
            rep_spec(Tp),
            rep_spec(Lp),
            rep_spec(Tp) if per_replica_keep else scn_spec(1, Tp),
            scn_spec(1, Lp),
            scn_spec(Tp, Pp),
            scn_spec(Pp, Lp),
            scn_spec(Tp, Lp),
        ],
        out_specs=(
            pl.BlockSpec((1, rb, Tp), lambda s, r: (s, r, 0)),
            pl.BlockSpec((1, rb, Pp), lambda s, r: (s, r, 0)),
            pl.BlockSpec((1, rb, Lp), lambda s, r: (s, r, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(active_p, remaining_p, bg_p, keep_p, bw_p, m_tp, m_pl, m_tl)

    return (
        xfer[:, :R, :T],
        proc_xfer[:, :R, :P],
        link_xfer[:, :R, :L],
    )


# ---------------------------------------------------------------------------
# fused multi-tick variant: K ticks per launch, carry resident in VMEM
# ---------------------------------------------------------------------------

def _bank_fused_kernel(
    t_ref,          # [1, Rb, LANE] i32 (lane 0 carries the clock)
    steps_ref,      # [1, Rb, LANE] i32
    remaining_ref,  # [1, Rb, T]
    done_ref,       # [1, Rb, T] f32 0/1 (padding = 1)
    started_ref,    # [1, Rb, T] f32 0/1
    t_start_ref,    # [1, Rb, T] i32
    t_end_ref,      # [1, Rb, T] i32
    conth_ref,      # [1, Rb, T]
    conpr_ref,      # [1, Rb, T]
    bg_ref,         # [1, Rb, L]
    noise_ref,      # [K, 1, Rb, L] standard-normal background draws
    mu_ref,         # [1, 1, L] bank-wide or [1, Rb, L] per-replica moments
    sigma_ref,      # [1, 1, L] or [1, Rb, L]
    release_ref,    # [1, 1, T] i32
    mdep_ref,       # [1, T, T] dep one-hot: column t selects row dep[t]
    nodep_ref,      # [1, 1, T] 1.0 where the leg has no dependency
    period_ref,     # [1, 1, L] i32 (padding = 1)
    mt_ref,         # [1, 1, LANE] i32 per-scenario max_ticks in lane 0
    keep_ref,       # [1, 1, T] bank-wide or [1, Rb, T] per-replica keeps
    bw_ref,         # [1, 1, L]
    m_tp_ref,       # [1, T, P]
    m_pl_ref,       # [1, P, L]
    m_tl_ref,       # [1, T, L]
    t_out, steps_out, remaining_out, done_out, started_out,
    t_start_out, t_end_out, conth_out, conpr_out, bg_out,
):
    f32 = jnp.float32
    i32 = jnp.int32
    K = noise_ref.shape[0]

    release = release_ref[0]  # [1, T] i32
    mdep = mdep_ref[0]
    nodep = nodep_ref[0]
    period = period_ref[0]  # [1, L] i32
    mt = mt_ref[0][:, :1]  # [1, 1] i32
    mu = mu_ref[0].astype(f32)
    sigma = sigma_ref[0].astype(f32)
    keep = keep_ref[0].astype(f32)
    bw = bw_ref[0].astype(f32)
    m_tp = m_tp_ref[0]
    m_pl = m_pl_ref[0]
    m_tl = m_tl_ref[0]

    def alive_of(t, done):  # [Rb, 1] bool
        all_done = jnp.min(done, axis=1, keepdims=True) > 0.5
        return (t[:, :1] < mt) & ~all_done

    def tick(k, state):
        (t, steps, remaining, done, started, t_start, t_end, conth, conpr,
         bg) = state
        t_col = t[:, :1]  # [Rb, 1]
        alive = alive_of(t, done)
        noise = noise_ref[k, 0].astype(f32)  # [Rb, L]
        fresh = jnp.maximum(mu + sigma * noise, 0.0)
        due = ((t_col % period) == 0) & alive
        bg = jnp.where(due, fresh, bg)

        # dep[t] gather as a one-hot matmul (MXU): column t of mdep selects
        # done[dep[t]]; legs without a dependency get the nodep bias instead
        dep_ok = (_dot(done, mdep) + nodep) > 0.5
        active = (done < 0.5) & (release <= t_col) & dep_ok & alive
        a = active.astype(f32)

        threads = _dot(a, m_tp)  # [Rb, P]
        proc_active = (threads > 0).astype(f32)
        campaign = _dot(proc_active, m_pl)  # [Rb, L]
        denom = jnp.maximum(campaign + jnp.maximum(bg, 0.0), 1.0)
        per_proc = bw / denom  # [Rb, L]
        per_proc_leg = _dot_t(per_proc, m_tl, exact=True)  # [Rb, T]
        threads_leg = jnp.maximum(_dot_t(threads, m_tp, exact=True), 1.0)
        chunk = a * keep * per_proc_leg / threads_leg
        xfer = jnp.minimum(remaining, chunk)
        proc_xfer = _dot(xfer, m_tp, exact=True)
        link_xfer = _dot(xfer, m_tl, exact=True)

        own_proc = _dot_t(proc_xfer, m_tp, exact=True)  # [Rb, T]
        own_link = _dot_t(link_xfer, m_tl, exact=True)
        conth = conth + a * (own_proc - xfer)
        conpr = conpr + a * (own_link - own_proc)
        remaining = remaining - xfer
        newly = active & (remaining <= 1e-6)
        done = jnp.maximum(done, newly.astype(f32))
        t_start = jnp.where(
            active & (started < 0.5),
            jnp.broadcast_to(t_col, t_start.shape), t_start,
        )
        started = jnp.maximum(started, a)
        t_end = jnp.where(
            newly, jnp.broadcast_to(t_col + 1, t_end.shape), t_end
        )
        adv = alive.astype(i32)
        return (
            t + adv, steps + adv, remaining, done, started, t_start, t_end,
            conth, conpr, bg,
        )

    def body(k, state):
        # early exit: once every replica of this tile is done (or clocked
        # out), the remaining ticks of the window are skipped outright
        return jax.lax.cond(
            jnp.any(alive_of(state[0], state[3])),
            lambda s: tick(k, s),
            lambda s: s,
            state,
        )

    state = (
        t_ref[0], steps_ref[0], remaining_ref[0].astype(f32),
        done_ref[0].astype(f32), started_ref[0].astype(f32),
        t_start_ref[0], t_end_ref[0], conth_ref[0].astype(f32),
        conpr_ref[0].astype(f32), bg_ref[0].astype(f32),
    )
    state = jax.lax.fori_loop(0, K, body, state)
    (t, steps, remaining, done, started, t_start, t_end, conth, conpr,
     bg) = state
    t_out[0] = t
    steps_out[0] = steps
    remaining_out[0] = remaining
    done_out[0] = done
    started_out[0] = started
    t_start_out[0] = t_start
    t_end_out[0] = t_end
    conth_out[0] = conth
    conpr_out[0] = conpr
    bg_out[0] = bg


def _fused_vmem_bytes(rb: int, Tp: int, Pp: int, Lp: int, K: int,
                      keep_rows: int, bg_rows: int) -> int:
    """The fused kernel's VMEM blocks, each double-buffered: one tile's carry
    in and out, its noise window and moments, and the scenario's constants
    (the dense incidences and the ``[Tp, Tp]`` dependency block). A block of
    one row occupies a whole sublane tile."""
    sub = lambda h: max(h, _SUBLANE)
    tile = (
        2 * 2 * rb * _LANE  # clock and steps, in and out
        + 2 * 7 * rb * Tp  # the seven per-leg carry arrays, in and out
        + 2 * rb * Lp  # background load, in and out
        + K * rb * Lp  # noise window
        + 2 * sub(bg_rows) * Lp + sub(keep_rows) * Tp
    )
    scenario = (
        Tp * Tp + Tp * Pp + Pp * Lp + Tp * Lp
        + _SUBLANE * (2 * Tp + 2 * Lp + _LANE)  # release, nodep, period, bw, max_ticks
    )
    return 4 * 2 * (tile + scenario)


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def grid_tick_bank_fused_pallas(
    state: Tuple[jax.Array, ...],  # ref.BANK_WINDOW_STATE_FIELDS layout
    noise: jax.Array,  # [K, S, R, L] standard-normal background draws
    bg_mu: jax.Array,  # [S, 1, L] or [S, R, L]
    bg_sigma: jax.Array,  # [S, 1, L] or [S, R, L]
    release: jax.Array,  # [S, T] i32
    dep: jax.Array,  # [S, T] i32 (-1 = none)
    bg_period: jax.Array,  # [S, L] i32
    max_ticks: jax.Array,  # [S] i32
    keep_frac: jax.Array,  # [S, T] or [S, R, T]
    bandwidth: jax.Array,  # [S, L]
    leg_proc: jax.Array,  # [S, T, P]
    proc_link: jax.Array,  # [S, P, L]
    leg_link: jax.Array,  # [S, T, L]
    *,
    interpret: bool = False,
    block_r: int = 128,
) -> Tuple[jax.Array, ...]:
    """``K = noise.shape[0]`` fair-share ticks of a scenario bank per kernel
    launch. The grid runs ``(scenario, replica-block)``; each tile loads its
    simulation carry once, loops the ticks with every array resident in
    VMEM/registers, and stores the carry back once — the per-tick HBM
    round-trip and launch overhead of the one-tick kernel amortize over the
    window. Elements freeze mid-window exactly like the reference
    (:func:`repro.kernels.ref.grid_tick_bank_window`): aliveness masks the
    update, and a tile whose replicas are all done skips its remaining
    ticks. ``dep`` gathers are lowered as a one-hot matmul so the loop body
    stays MXU/VPU-only.

    VMEM budget scales with ``block_r * K`` (the ``noise`` window block);
    lower ``block_r`` for very large windows. The launch asks for twice
    the blocks' size (``_fused_vmem_bytes``; the kernel's own scratch takes
    the rest), at least the scoped default and at most ``_VMEM_MAX``: a
    bank of 512 replicas of a 424-leg, 329-process campaign needs 17.1 MiB
    at ``block_r = 128``.
    """
    (t, steps, remaining, done, started, t_start, t_end, conth, conpr,
     bg) = state
    S, R, T = remaining.shape
    L = bandwidth.shape[-1]
    per_replica_keep = keep_frac.ndim == 3
    # mu and sigma must agree on replica handling inside the kernel: if
    # either carries a replica dim, broadcast both to [S, R, L] (a mixed
    # pair would otherwise silently read replica 0's row for every replica)
    per_replica_bg = bg_mu.shape[1] != 1 or bg_sigma.shape[1] != 1
    if per_replica_bg:
        bg_mu = jnp.broadcast_to(bg_mu, (S, R, L))
        bg_sigma = jnp.broadcast_to(bg_sigma, (S, R, L))

    i32 = jnp.int32
    f32 = jnp.float32
    lane3 = lambda x: _pad_to(x.astype(i32)[:, :, None], 2, _LANE)
    rep = lambda x, v=0.0: _pad_to(_pad_to(x, 2, _LANE, v), 1, _SUBLANE, v)

    # per-(scenario, replica) state: clock/steps lane-expanded, legs/links
    # lane-padded. done is padded with 1.0 (born done) on both the replica
    # and leg axes so padding never transfers and never keeps a tile alive.
    t_p = rep(lane3(t))
    steps_p = rep(lane3(steps))
    remaining_p = rep(remaining.astype(f32))
    done_p = rep(done.astype(f32), 1.0)
    started_p = rep(started.astype(f32))
    t_start_p = rep(t_start.astype(i32))
    t_end_p = rep(t_end.astype(i32))
    conth_p = rep(conth.astype(f32))
    conpr_p = rep(conpr.astype(f32))
    bg_p = rep(bg.astype(f32))
    noise_p = _pad_to(_pad_to(noise.astype(f32), 3, _LANE), 2, _SUBLANE)
    if per_replica_bg:
        mu_p = rep(bg_mu.astype(f32))
        sigma_p = rep(bg_sigma.astype(f32))
    else:
        mu_p = _pad_to(bg_mu.astype(f32), 2, _LANE)
        sigma_p = _pad_to(bg_sigma.astype(f32), 2, _LANE)

    # per-scenario campaign constants
    release_p = _pad_to(release.astype(i32)[:, None, :], 2, _LANE)
    mdep = (
        (jnp.arange(T, dtype=i32)[None, :, None] == jnp.maximum(dep, 0)[:, None, :])
        & (dep >= 0)[:, None, :]
    ).astype(f32)  # [S, T(dep), T(leg)]
    mdep_p = _pad_to(_pad_to(mdep, 1, _LANE), 2, _LANE)
    nodep_p = _pad_to((dep < 0).astype(f32)[:, None, :], 2, _LANE)
    period_p = _pad_to(bg_period.astype(i32)[:, None, :], 2, _LANE, 1)
    mt_p = _pad_to(max_ticks.astype(i32)[:, None, None], 2, _LANE)
    if per_replica_keep:
        keep_p = rep(keep_frac.astype(f32))
    else:
        keep_p = _pad_to(keep_frac.astype(f32)[:, None, :], 2, _LANE)
    bw_p = _pad_to(bandwidth.astype(f32)[:, None, :], 2, _LANE)
    m_tp = _pad_to(_pad_to(leg_proc, 1, _LANE), 2, _LANE)
    m_pl = _pad_to(_pad_to(proc_link, 1, _LANE), 2, _LANE)
    m_tl = _pad_to(_pad_to(leg_link, 1, _LANE), 2, _LANE)
    Tp = remaining_p.shape[2]
    Pp, Lp = m_pl.shape[1], m_pl.shape[2]
    K = noise.shape[0]

    rb = min(block_r, remaining_p.shape[1])
    pad_r = lambda x, v=0.0: _pad_to(x, 1, rb, v)
    t_p, steps_p = pad_r(t_p), pad_r(steps_p)
    remaining_p, done_p = pad_r(remaining_p), pad_r(done_p, 1.0)
    started_p, t_start_p, t_end_p = (
        pad_r(started_p), pad_r(t_start_p), pad_r(t_end_p)
    )
    conth_p, conpr_p, bg_p = pad_r(conth_p), pad_r(conpr_p), pad_r(bg_p)
    noise_p = _pad_to(noise_p, 2, rb)
    if per_replica_keep:
        keep_p = pad_r(keep_p)
    if per_replica_bg:
        mu_p, sigma_p = pad_r(mu_p), pad_r(sigma_p)
    Rp = remaining_p.shape[1]
    grid = (S, Rp // rb)

    need = _fused_vmem_bytes(rb, Tp, Pp, Lp, K, keep_p.shape[1], mu_p.shape[1])
    vmem = max(_VMEM_SCOPED, min(2 * need, _VMEM_MAX))

    rep_spec = lambda w: pl.BlockSpec((1, rb, w), lambda s, r: (s, r, 0))
    scn_spec = lambda h, w: pl.BlockSpec((1, h, w), lambda s, r: (s, 0, 0))

    sds = jax.ShapeDtypeStruct
    out_shape = (
        sds((S, Rp, _LANE), i32),  # t
        sds((S, Rp, _LANE), i32),  # steps
        sds((S, Rp, Tp), f32),     # remaining
        sds((S, Rp, Tp), f32),     # done
        sds((S, Rp, Tp), f32),     # started
        sds((S, Rp, Tp), i32),     # t_start
        sds((S, Rp, Tp), i32),     # t_end
        sds((S, Rp, Tp), f32),     # conth
        sds((S, Rp, Tp), f32),     # conpr
        sds((S, Rp, Lp), f32),     # bg
    )
    out = pl.pallas_call(
        _bank_fused_kernel,
        grid=grid,
        in_specs=[
            rep_spec(_LANE),  # t
            rep_spec(_LANE),  # steps
            rep_spec(Tp),     # remaining
            rep_spec(Tp),     # done
            rep_spec(Tp),     # started
            rep_spec(Tp),     # t_start
            rep_spec(Tp),     # t_end
            rep_spec(Tp),     # conth
            rep_spec(Tp),     # conpr
            rep_spec(Lp),     # bg
            pl.BlockSpec((K, 1, rb, Lp), lambda s, r: (0, s, r, 0)),  # noise
            rep_spec(Lp) if per_replica_bg else scn_spec(1, Lp),  # bg_mu
            rep_spec(Lp) if per_replica_bg else scn_spec(1, Lp),  # bg_sigma
            scn_spec(1, Tp),   # release
            scn_spec(Tp, Tp),  # mdep
            scn_spec(1, Tp),   # nodep
            scn_spec(1, Lp),   # period
            scn_spec(1, _LANE),  # max_ticks
            rep_spec(Tp) if per_replica_keep else scn_spec(1, Tp),
            scn_spec(1, Lp),   # bandwidth
            scn_spec(Tp, Pp),
            scn_spec(Pp, Lp),
            scn_spec(Tp, Lp),
        ],
        out_specs=(
            rep_spec(_LANE), rep_spec(_LANE),
            rep_spec(Tp), rep_spec(Tp), rep_spec(Tp),
            rep_spec(Tp), rep_spec(Tp), rep_spec(Tp), rep_spec(Tp),
            rep_spec(Lp),
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=tpu_compiler_params(vmem_limit_bytes=vmem),
    )(
        t_p, steps_p, remaining_p, done_p, started_p, t_start_p, t_end_p,
        conth_p, conpr_p, bg_p, noise_p, mu_p, sigma_p, release_p, mdep_p,
        nodep_p, period_p, mt_p, keep_p, bw_p, m_tp, m_pl, m_tl,
    )
    (t_o, steps_o, remaining_o, done_o, started_o, t_start_o, t_end_o,
     conth_o, conpr_o, bg_o) = out
    return (
        t_o[:, :R, 0],
        steps_o[:, :R, 0],
        remaining_o[:, :R, :T],
        done_o[:, :R, :T] > 0.5,
        started_o[:, :R, :T] > 0.5,
        t_start_o[:, :R, :T],
        t_end_o[:, :R, :T],
        conth_o[:, :R, :T],
        conpr_o[:, :R, :T],
        bg_o[:, :R, :L],
    )
