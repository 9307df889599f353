"""Jit'd dispatch wrappers for the Pallas kernels.

Backend selection:

- ``"pallas"``            — real Pallas lowering (TPU target).
- ``"pallas_interpret"``  — Pallas with ``interpret=True`` (CPU validation).
- ``"xla"``               — the pure-jnp reference path (:mod:`repro.kernels.ref`).
- ``"auto"``              — ``"pallas"`` on TPU, ``"xla"`` elsewhere.

The CPU container cannot lower Pallas natively, so the 512-device dry-run and
the smoke tests run the XLA path; kernel correctness is established separately
by the interpret-mode sweeps in ``tests/test_kernels_*.py``.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.utils import spans

__all__ = [
    "default_backend",
    "grid_tick",
    "grid_tick_bank",
    "grid_tick_bank_fused",
    "flash_attention",
    "decode_attention",
    "mlstm_chunk",
    "selu_mlp",
]

_VALID = ("auto", "xla", "pallas", "pallas_interpret")


@functools.lru_cache(maxsize=1)
def _platform() -> str:
    return jax.devices()[0].platform


def default_backend() -> str:
    # repro: allow[trace-purity] -- REPRO_KERNEL_BACKEND is a process-start constant: the backend is jit-static everywhere, so a trace-time read cannot go stale within a process
    env = os.environ.get("REPRO_KERNEL_BACKEND", "auto")
    if env not in _VALID:
        raise ValueError(f"REPRO_KERNEL_BACKEND must be one of {_VALID}: {env}")
    return env


def _resolve(backend: Optional[str]) -> str:
    backend = backend or default_backend()
    if backend == "auto":
        return "pallas" if _platform() == "tpu" else "xla"
    return backend


def grid_tick(
    active: jax.Array,
    remaining: jax.Array,
    keep_frac: jax.Array,
    bg_load: jax.Array,
    bandwidth: jax.Array,
    leg_proc: jax.Array,
    proc_link: jax.Array,
    leg_link: jax.Array,
    *,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fair-share tick of one campaign: per-sim state ``active`` /
    ``remaining`` ``[T]`` (or rows ``[..., T]``), ``bg_load`` ``[L]`` (or
    ``[..., L]``), ``keep_frac`` ``[T]`` (or per row), against the campaign's
    ``bandwidth [L]`` and incidences.

    On the Pallas backends a ``vmap`` of this call folds its mapped axis into
    the kernel's rows (256 a grid step) instead of one grid step per mapped
    simulation; see :func:`_pallas_tick`."""
    b = _resolve(backend)
    if b == "xla":
        return ref.grid_tick(
            active, remaining, keep_frac, bg_load, bandwidth,
            leg_proc, proc_link, leg_link,
        )
    return _pallas_tick(b == "pallas_interpret")(
        active, remaining, keep_frac, bg_load, bandwidth,
        leg_proc, proc_link, leg_link,
    )


#: counters of :mod:`repro.utils.spans`: traces of the per-sim tick's
#: batching rule that folded the mapped axis into the kernel's rows, and
#: those that made it the bank kernel's scenario axis (mapped incidences)
TICK_FOLDED = "kernels.grid_tick.folded"
TICK_UNFOLDED = "kernels.grid_tick.unfolded"


@functools.lru_cache(maxsize=None)
def _pallas_tick(interpret: bool):
    """The Pallas fair-share tick of :func:`grid_tick`, with a batching rule.

    Called as is, ``[T]`` state runs ``grid_tick_pallas`` and rows
    ``[..., T]`` run ``grid_tick_bank_pallas`` as one scenario of
    ``prod(...)`` replica rows. Under ``vmap`` Pallas's own rule would add a
    grid axis of the batch size, one 8-row block (one real row) a step. The
    rule here instead:

    - folds the mapped axis into the rows when only per-row operands
      (``active``, ``remaining``, ``bg_load``, ``keep_frac``) are mapped, by
      calling this same function on the stacked rows, so that an enclosing
      ``vmap`` folds its axis in turn;
    - makes the mapped axis the bank kernel's scenario axis when the
      campaign operands are mapped too (the vmap lowering of
      ``simulate_bank``, per-scenario incidences), rows as replicas.

    Each row's output depends on that row alone, so every path gives the
    same bits per row.
    """
    from repro.kernels import grid_tick as _k

    def bank(active, remaining, keep, bg, bw, m_tp, m_pl, m_tl):
        """Bank kernel over ``[S, *rows, ...]`` state, ``keep`` ``[S, T]``
        (shared by the rows) or ``[S, *rows, T]``."""
        s, rows = active.shape[0], active.shape[1:-1]
        flat = lambda x: x.reshape(s, -1, x.shape[-1])
        keep = keep if keep.ndim == 2 else flat(keep)
        outs = _k.grid_tick_bank_pallas(
            flat(active), flat(remaining), keep, flat(bg), bw, m_tp, m_pl, m_tl,
            interpret=interpret,
        )
        return tuple(o.reshape((s, *rows, o.shape[-1])) for o in outs)

    @jax.custom_batching.custom_vmap
    def tick(active, remaining, keep, bg, bw, m_tp, m_pl, m_tl):
        if active.ndim == 1:
            return _k.grid_tick_pallas(
                active, remaining, keep, bg, bw, m_tp, m_pl, m_tl,
                interpret=interpret,
            )
        outs = bank(active[None], remaining[None], keep[None], bg[None],
                    bw[None], m_tp[None], m_pl[None], m_tl[None])
        return tuple(o[0] for o in outs)

    @tick.def_vmap
    def rule(axis_size, in_batched, active, remaining, keep, bg, *campaign):
        def lift(x, batched):
            return x if batched else jnp.broadcast_to(x, (axis_size, *x.shape))

        a_b, r_b, keep_b, bg_b = in_batched[:4]
        active, remaining, bg = lift(active, a_b), lift(remaining, r_b), lift(bg, bg_b)
        if any(in_batched[4:]):
            spans.count(TICK_UNFOLDED)
            campaign = [lift(x, b) for x, b in zip(campaign, in_batched[4:])]
            out = bank(active, remaining, lift(keep, keep_b), bg, *campaign)
            return out, (True,) * 3
        spans.count(TICK_FOLDED)
        if keep_b or keep.ndim > 1:
            # per row from here on: a keep mapped by this axis alone repeats
            # over the call's own rows
            keep = lift(keep, keep_b)
            keep = jnp.broadcast_to(
                keep.reshape(keep.shape[:1] + (1,) * (active.ndim - keep.ndim)
                             + keep.shape[1:]),
                active.shape,
            )
        return tick(active, remaining, keep, bg, *campaign), (True,) * 3

    return tick


def grid_tick_bank(
    active: jax.Array,  # [S, R, T]
    remaining: jax.Array,  # [S, R, T]
    keep_frac: jax.Array,  # [S, T] or [S, R, T]
    bg_load: jax.Array,  # [S, R, L]
    bandwidth: jax.Array,  # [S, L]
    leg_proc: jax.Array,  # [S, T, P]
    proc_link: jax.Array,  # [S, P, L]
    leg_link: jax.Array,  # [S, T, L]
    *,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scenario-bank fair-share tick: per-scenario incidence operands instead
    of broadcast constants (the hot path of ``engine.simulate_bank`` on TPU;
    the XLA path broadcasts through the batched reference).

    Ranks are validated up front: per-sim state must carry the replica dim
    (``[S, R, ...]``) — without the check, ``[S, T]`` inputs would silently
    mis-broadcast against the ``[S, 1, ...]``-lifted campaign operands and
    produce garbage fair shares instead of an error. ``keep_frac`` may be
    bank-wide ``[S, T]`` or per-replica ``[S, R, T]``.
    """
    if active.ndim != 3 or remaining.ndim != 3 or bg_load.ndim != 3:
        raise ValueError(
            "grid_tick_bank: per-sim state must be [S(cenario), R(eplica), ...] "
            f"— got active {active.shape}, remaining {remaining.shape}, "
            f"bg_load {bg_load.shape}; vmap/reshape a replica dim in, or use "
            "grid_tick for unbanked state"
        )
    if keep_frac.ndim not in (2, 3):
        raise ValueError(
            f"grid_tick_bank: keep_frac must be [S, T] or [S, R, T]: "
            f"{keep_frac.shape}"
        )
    if bandwidth.ndim != 2:
        raise ValueError(
            f"grid_tick_bank: bandwidth must be [S, L]: {bandwidth.shape}"
        )
    if leg_proc.ndim != 3 or proc_link.ndim != 3 or leg_link.ndim != 3:
        raise ValueError(
            "grid_tick_bank: incidence matrices must carry the scenario dim "
            f"([S, T, P] / [S, P, L] / [S, T, L]) — got {leg_proc.shape}, "
            f"{proc_link.shape}, {leg_link.shape}"
        )
    s = active.shape[0]
    for name, arr in (
        ("remaining", remaining), ("keep_frac", keep_frac), ("bg_load", bg_load),
        ("bandwidth", bandwidth), ("leg_proc", leg_proc),
        ("proc_link", proc_link), ("leg_link", leg_link),
    ):
        if arr.shape[0] != s:
            raise ValueError(
                f"grid_tick_bank: {name} scenario dim {arr.shape[0]} != {s}"
            )
    b = _resolve(backend)
    if b == "xla":
        keep3 = keep_frac if keep_frac.ndim == 3 else keep_frac[:, None]
        return ref.grid_tick(
            active, remaining, keep3, bg_load, bandwidth[:, None],
            leg_proc[:, None], proc_link[:, None], leg_link[:, None],
        )
    from repro.kernels import grid_tick as _k

    return _k.grid_tick_bank_pallas(
        active, remaining, keep_frac, bg_load, bandwidth,
        leg_proc, proc_link, leg_link,
        interpret=(b == "pallas_interpret"),
    )


def _bank_noise_chain(
    n_links: int, key: jax.Array, window: int
) -> Tuple[jax.Array, jax.Array]:
    """Pre-draw one window of background noise for the fused kernel:
    ``window`` unconditional replays of :func:`repro.kernels.ref.bank_split_draw`
    — the exact per-tick split-and-draw stream — collected as
    ``noise [K, S, R, L]`` plus the key chain ``[K + 1, S, R, 2]`` (entry
    ``j`` = the carry key after ``j`` splits, so an element that runs ``j``
    alive ticks inside the window resumes from ``chain[j]``, keys of frozen
    elements included)."""

    def draw(k, _):
        nk, noise = ref.bank_split_draw(k, n_links)
        return nk, (nk, noise)

    _, (keys_k, noise_k) = jax.lax.scan(draw, key, None, length=window)
    chain = jnp.concatenate([key[None], keys_k], axis=0)
    return chain, noise_k


def grid_tick_bank_fused(
    state: Tuple[jax.Array, ...],  # ref.BANK_WINDOW_STATE_FIELDS layout
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
    window: int,
    leap: bool = False,
    backend: Optional[str] = None,
    key: Optional[jax.Array] = None,  # [S, R, 2] carried PRNG keys
    noise: Optional[jax.Array] = None,  # [K, S, R, L] predrawn normals
):
    """``window`` fused simulation ticks of a scenario bank in one dispatch.

    This is the hot body of the windowed banked engine: instead of one
    ``grid_tick_bank`` launch (plus a full HBM round-trip of the carry and a
    ``while_loop`` cond evaluation) *per tick*, one call advances every
    (scenario, replica) element by up to ``window`` ticks, freezing elements
    that finish or hit their scenario's ``max_ticks`` mid-window. ``state``
    follows :data:`repro.kernels.ref.BANK_WINDOW_STATE_FIELDS`.

    RNG modes (exactly one): with ``key=`` the per-element keys ride along —
    split in-step on the XLA scan (bitwise-stable across window sizes), or
    pre-drawn into a key chain for the Pallas kernel and re-synchronized
    from its alive-step counts — and the call returns ``(state, key)``.
    With ``noise=`` the predrawn rows are consumed as-is and the ``state``
    tuple alone returns (the raw kernel contract, used by the parity tests).

    Backend dispatch: ``xla`` runs the :func:`repro.kernels.ref.grid_tick_bank_window`
    scan over the reference tick; ``pallas`` / ``pallas_interpret`` run the
    fused kernel (``grid_tick_bank_fused_pallas``) that keeps the whole carry
    resident in VMEM for all ``window`` ticks and early-exits when a tile's
    replicas all finish. ``leap=True`` makes every inner step an event leap;
    the Pallas path then falls back to the reference scan driving the
    per-tick bank kernel (the leap body's data-dependent event search does
    not pay off inside one kernel), so leap windows still leap.

    **shard_map safety**: every op in here is row-local over the leading
    scenario axis ``S`` — no reductions, gathers, or scans cross rows, and
    the RNG keys ride per-element in the carry.  The windowed engine relies
    on this when it wraps the window loop in ``shard_map`` over a scenario
    mesh (``simulate_bank(..., mesh=)``): each shard sees an ordinary
    smaller bank, needs no collectives (``check_vma=False``), and produces
    bitwise the rows it would produce unsharded.  Keep new window-body ops
    row-local or the sharded engine's bitwise-parity contract breaks.
    """
    if len(state) != len(ref.BANK_WINDOW_STATE_FIELDS):
        raise ValueError(
            f"grid_tick_bank_fused: state must carry "
            f"{len(ref.BANK_WINDOW_STATE_FIELDS)} arrays "
            f"({', '.join(ref.BANK_WINDOW_STATE_FIELDS)}): got {len(state)}"
        )
    if window < 1:
        raise ValueError(f"grid_tick_bank_fused: window must be >= 1: {window}")
    if (key is None) == (noise is None):
        raise ValueError(
            "grid_tick_bank_fused: pass exactly one of key= or noise="
        )
    if noise is not None and (noise.ndim != 4 or noise.shape[0] != window):
        raise ValueError(
            f"grid_tick_bank_fused: noise must be [window={window}, S, R, L]: "
            f"{noise.shape}"
        )
    if bg_mu.ndim != 3 or bg_sigma.ndim != 3:
        raise ValueError(
            "grid_tick_bank_fused: bg moments must be [S, 1, L] or "
            f"[S, R, L]: {bg_mu.shape}, {bg_sigma.shape}"
        )
    b = _resolve(backend)
    if b == "xla" or leap:
        # tick=None selects the reference scan's built-in index-based
        # fair-share tick (gathers beat tiny one-hot matmuls off-TPU); the
        # Pallas leap path injects the bank kernel per event step instead
        tick = None if b == "xla" else functools.partial(grid_tick_bank, backend=b)
        return ref.grid_tick_bank_window(
            state, bg_mu, bg_sigma, release, dep, bg_period, max_ticks,
            keep_frac, bandwidth, leg_proc, proc_link, leg_link,
            leap=leap, tick=tick, key=key, noise=noise, window=window,
        )
    from repro.kernels import grid_tick as _k

    chain = None
    if key is not None:
        chain, noise = _bank_noise_chain(bg_mu.shape[-1], key, window)
    out = _k.grid_tick_bank_fused_pallas(
        state, noise, bg_mu, bg_sigma, release, dep, bg_period, max_ticks,
        keep_frac, bandwidth, leg_proc, proc_link, leg_link,
        interpret=(b == "pallas_interpret"),
    )
    if chain is None:
        return out
    steps = out[1]
    s, r = steps.shape
    key = jnp.take_along_axis(
        chain, jnp.broadcast_to(steps[None, :, :, None], (1, s, r, 2)), axis=0
    )[0]
    return out, key


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    backend: Optional[str] = None,
    grouped: bool = False,
) -> jax.Array:
    b = _resolve(backend)
    if b == "xla":
        from repro.kernels import flash_attention as _k

        # the chunked flash algorithm in pure jnp: O(S*blk) memory — the
        # honest CPU/dry-run stand-in for the Pallas kernel. Tiny sequences
        # use the quadratic oracle directly (cheaper than the scan).
        if q.shape[1] * k.shape[1] <= 256 * 256 and not grouped:
            return ref.flash_attention(
                q, k, v, causal=causal, window=window, scale=scale,
                q_offset=q_offset,
            )
        return _k.flash_attention_xla(
            q, k, v, causal, window, scale, q_offset, grouped
        )
    from repro.kernels import flash_attention as _k

    # positional call: custom_vjp nondiff args may not be passed by keyword
    return _k.flash_attention_pallas(
        q, k, v, causal, window, scale, q_offset, b == "pallas_interpret"
    )


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    b = _resolve(backend)
    if b == "xla":
        return ref.decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    from repro.kernels import decode_attention as _k

    return _k.decode_attention_pallas(
        q, k_cache, v_cache, lengths, scale=scale,
        interpret=(b == "pallas_interpret"),
    )


def mlstm_chunk(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array,
    f_gate: jax.Array,
    *,
    chunk: int = 128,
    normalize: bool = True,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    b = _resolve(backend)
    if b == "xla":
        from repro.kernels import mlstm_chunk as _k

        # chunked recurrence in pure jnp for anything beyond toy lengths
        # (the fully-parallel oracle is O(S^2) in memory)
        if q.shape[1] <= 256:
            return ref.mlstm_chunk(
                q, k, v, i_gate, f_gate, normalize=normalize, scale=scale
            )
        return _k.mlstm_chunk_xla(
            q, k, v, i_gate, f_gate, chunk=chunk, normalize=normalize,
            scale=scale,
        )
    from repro.kernels import mlstm_chunk as _k

    return _k.mlstm_chunk_pallas(
        q, k, v, i_gate, f_gate, chunk=chunk, normalize=normalize, scale=scale,
        interpret=(b == "pallas_interpret"),
    )


def selu_mlp(
    x: jax.Array,
    weights: Tuple[jax.Array, ...],
    biases: Tuple[jax.Array, ...],
    *,
    backend: Optional[str] = None,
) -> jax.Array:
    b = _resolve(backend)
    if b == "xla":
        return ref.selu_mlp(x, weights, biases)
    from repro.kernels import selu_mlp as _k

    # differentiable: kernel forward, reverse pass through the XLA reference
    return _k.selu_mlp(
        x, tuple(weights), tuple(biases), b == "pallas_interpret"
    )
