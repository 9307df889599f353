"""Pure-jnp reference oracles for every Pallas kernel in this package.

Each function is the semantic ground truth: kernels are validated against
these in ``interpret=True`` mode over shape/dtype sweeps (see tests), and the
XLA dispatch path in :mod:`repro.kernels.ops` executes these directly on
backends without Pallas support (CPU dry-run).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "onehot_sum",
    "leg_index",
    "gather_legs",
    "grid_tick",
    "grid_tick_bank_window",
    "flash_attention",
    "decode_attention",
    "mlstm_chunk",
    "selu_mlp",
]


# ---------------------------------------------------------------------------
# one-hot contractions of per-leg values
# ---------------------------------------------------------------------------
#: Lane count of :func:`onehot_sum`'s summation order. Changing it changes
#: the last ulp of every concurrency accumulator.
SUM_LANES = 16


def onehot_sum(v: jax.Array, m: jax.Array) -> jax.Array:
    """Per-process / per-link sums of a per-leg quantity:
    ``[..., T] x [..., T, X] -> [..., X]``.

    Written as explicit adds in a fixed order, not as a matmul or a
    reduction, for two reasons. The summation order of a dot or a reduce is
    the backend's choice and follows the padded leg width and the batch
    shape, so the same scenario summed at two bank layouts (bucket pads vs
    monolithic pads, a shard vs the whole bank, a server slot bank vs a
    one-row fleet) would drift in the last ulp. And an f32 dot on a TPU
    runs at reduced MXU precision by default, which would round MB values
    to bf16; the products here are exact on every backend.

    The order: leg ``t`` goes to lane ``t % SUM_LANES``; each lane adds its
    legs in index order, block by block of ``SUM_LANES`` legs, and the lanes
    are then added in lane order. A compiler does not reassociate float
    adds it was given explicitly, and a wider pad only appends blocks of
    exact zeros to every lane, so the result does not depend on the pad
    width. The program holds ``T / SUM_LANES + SUM_LANES`` adds, each over
    a ``[..., SUM_LANES, X]`` slab, which fuse into one loop.
    """
    n = v.shape[-1]
    blocks = -(-n // SUM_LANES)
    if blocks * SUM_LANES > n:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, blocks * SUM_LANES - n)])
        m = jnp.pad(m, [(0, 0)] * (m.ndim - 2)
                    + [(0, blocks * SUM_LANES - n), (0, 0)])
    block = lambda k: (
        v[..., k * SUM_LANES:(k + 1) * SUM_LANES, None]
        * m[..., k * SUM_LANES:(k + 1) * SUM_LANES, :]
    )
    lanes = block(0)  # [..., SUM_LANES, X]
    for k in range(1, blocks):
        lanes = lanes + block(k)
    acc = lanes[..., 0, :]
    for j in range(1, SUM_LANES):
        acc = acc + lanes[..., j, :]
    return acc


def leg_index(m: jax.Array) -> jax.Array:
    """Process / link index of each leg from a one-hot incidence:
    ``[..., T, X] -> [..., T]`` i32 (0 for an all-zero padding row)."""
    return jnp.argmax(m, axis=-1).astype(jnp.int32)


def gather_legs(v: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather per-process / per-link values back to legs by their
    :func:`leg_index`: ``[..., X] x [..., T] -> [..., T]``, batch dims
    broadcast. Exact, the same as the one-hot matmul on real legs; a
    padding leg reads entry 0, so callers mask it by ``active``."""
    batch = jnp.broadcast_shapes(v.shape[:-1], idx.shape[:-1])
    return jnp.take_along_axis(
        jnp.broadcast_to(v, batch + v.shape[-1:]),
        jnp.broadcast_to(idx, batch + idx.shape[-1:]),
        axis=-1,
    )


# ---------------------------------------------------------------------------
# grid_tick: GDAPS fair-share transfer tick (paper Section 4)
# ---------------------------------------------------------------------------
def grid_tick(
    active: jax.Array,  # [..., T] f32 in {0,1}
    remaining: jax.Array,  # [..., T] f32 MB
    keep_frac: jax.Array,  # [..., T] f32 = 1 - protocol overhead
    bg_load: jax.Array,  # [..., L] f32 background processes (>=0)
    bandwidth: jax.Array,  # [..., L] f32 MB/tick
    leg_proc: jax.Array,  # [..., T, P] f32 one-hot
    proc_link: jax.Array,  # [..., P, L] f32 one-hot
    leg_link: jax.Array,  # [..., T, L] f32 one-hot
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One simulation tick of the GDAPS transfer mechanism.

    chunk = (link.bandwidth / (background_load + campaign_load)) / n_threads
    chunk -= chunk * protocol.overhead

    Returns ``(xfer[..., T], proc_xfer[..., P], link_xfer[..., L])`` — MB
    moved this tick per leg / per process / per link (campaign traffic only).

    All operands broadcast over leading batch dims, so a scenario bank can
    pass per-scenario incidence matrices ``[N, T, P]`` against per-sim state
    ``[N, T]`` (or ``[N, R, T]`` with ``[N, 1, T, P]`` incidences) directly —
    no vmap required.
    """
    f32 = jnp.float32
    active = active.astype(f32)
    threads_per_proc = onehot_sum(active, leg_proc)  # [..., P]
    proc_is_active = (threads_per_proc > 0).astype(f32)
    campaign_load = onehot_sum(proc_is_active, proc_link)  # [..., L]
    denom = jnp.maximum(campaign_load + jnp.maximum(bg_load, 0.0), 1.0)
    per_proc_bw = bandwidth / denom  # [..., L]
    # gather link/process quantities back to legs
    per_proc_bw_leg = gather_legs(per_proc_bw, leg_index(leg_link))  # [..., T]
    threads_leg = jnp.maximum(
        gather_legs(threads_per_proc, leg_index(leg_proc)), 1.0
    )
    chunk = active * keep_frac * per_proc_bw_leg / threads_leg
    xfer = jnp.minimum(remaining, chunk)
    proc_xfer = onehot_sum(xfer, leg_proc)  # [..., P]
    link_xfer = onehot_sum(xfer, leg_link)  # [..., L]
    return xfer, proc_xfer, link_xfer


# ---------------------------------------------------------------------------
# grid_tick_bank_window: K fused simulation ticks over a scenario bank
# ---------------------------------------------------------------------------

#: Window-body carry layout shared by the reference scan, the Pallas fused
#: kernel and the engine: per-(scenario, replica) tick clock and alive-step
#: count, then the per-leg transfer state, then the per-link background load.
BANK_WINDOW_STATE_FIELDS = (
    "t",          # [S, R] i32 current tick of each (scenario, replica)
    "steps",      # [S, R] i32 alive inner steps taken inside this window
    "remaining",  # [S, R, T] f32 MB left per leg
    "done",       # [S, R, T] bool
    "started",    # [S, R, T] bool
    "t_start",    # [S, R, T] i32 first active tick
    "t_end",      # [S, R, T] i32 completion tick
    "conth",      # [S, R, T] f32 sibling-thread traffic accumulator
    "conpr",      # [S, R, T] f32 other-process traffic accumulator
    "bg",         # [S, R, L] f32 current background load
)


def bank_take_legs(v: jax.Array, idx: jax.Array) -> jax.Array:
    """``v[s, r, idx[s, t]]``: ``[S, R, X] x [S, T] -> [S, R, T]``, the bank
    scan's gather-direction lookup. The index is the scenario's, shared by
    all its replica rows, and is never broadcast to ``[S, R, T]`` (see
    :func:`grid_tick_bank_window`): ``v`` is laid out ``[R, S * X]`` and
    read by one column gather at the flat indices ``s * X + idx[s, t]``.
    Callers pass indices in ``[0, X)``."""
    s, r, x = v.shape
    flat = jnp.swapaxes(v, 0, 1).reshape(r, s * x)
    at = (idx + x * jnp.arange(s, dtype=idx.dtype)[:, None]).reshape(-1)
    out = jnp.take(flat, at, axis=1, mode="clip")  # [R, S * T]
    return jnp.swapaxes(out.reshape(r, s, -1), 0, 1)


def _bank_dep_ok(dep: jax.Array, done: jax.Array) -> jax.Array:
    """``done[s, r, dep[s, t]]`` with -1 mapping to True: [S, R, T]."""
    gathered = bank_take_legs(done, jnp.maximum(dep, 0))
    return jnp.where(dep[:, None, :] >= 0, gathered, True)


def bank_split_draw(
    key: jax.Array, n_links: int
) -> Tuple[jax.Array, jax.Array]:
    """One background-resample draw of the banked RNG stream: split every
    (scenario, replica) key once and draw its ``[n_links]`` normals —
    ``([S, R, 2] keys, [S, R, 2] -> ([S, R, 2], [S, R, L]))``.

    This is the **canonical** per-tick split-and-draw sequence: the window
    scan's ``key=`` mode consumes it in-step, and the fused kernel's
    key-chain precompute (``ops._bank_noise_chain``) replays it
    unconditionally — the chain resync from alive-step counts is only
    correct while both sides draw from this one helper, so any change to
    the split order or draw shape must happen here.
    """
    pair = jax.vmap(jax.vmap(jax.random.split))(key)  # [S, R, 2, 2]
    nk, sub = pair[:, :, 0], pair[:, :, 1]
    noise = jax.vmap(
        jax.vmap(lambda kk: jax.random.normal(kk, (n_links,)))
    )(sub)
    return nk, noise


def grid_tick_bank_window(
    state: Tuple[jax.Array, ...],  # see BANK_WINDOW_STATE_FIELDS
    bg_mu: jax.Array,  # [S, 1, L] or [S, R, L] background-load mean
    bg_sigma: jax.Array,  # [S, 1, L] or [S, R, L]
    release: jax.Array,  # [S, T] i32
    dep: jax.Array,  # [S, T] i32 (-1 = none)
    bg_period: jax.Array,  # [S, L] i32
    max_ticks: jax.Array,  # [S] i32 per-scenario tick bound
    keep_frac: jax.Array,  # [S, T] or [S, R, T]
    bandwidth: jax.Array,  # [S, L]
    leg_proc: jax.Array,  # [S, T, P]
    proc_link: jax.Array,  # [S, P, L]
    leg_link: jax.Array,  # [S, T, L]
    *,
    leap: bool,
    tick: Optional[Callable[..., Tuple[jax.Array, jax.Array, jax.Array]]] = None,
    key: Optional[jax.Array] = None,  # [S, R, 2] carried PRNG keys
    noise: Optional[jax.Array] = None,  # [K, S, R, L] predrawn normals
    window: Optional[int] = None,  # required with key=
):
    """Reference fused window: ``K`` simulation ticks of a whole scenario bank
    as one ``lax.scan``, element-for-element identical to ``K`` iterations of
    the per-tick banked body under its alive freeze.

    The freeze is folded into the update masks instead of a post-hoc carry
    select: a (scenario, replica) element is *alive* while its clock is below
    its scenario's ``max_ticks`` and it still has unfinished legs. Masking
    ``active`` (and the clock/background updates) by aliveness is bitwise
    identical to freezing the whole carry — a frozen element transfers
    nothing, so every other state array is a fixed point of the tick update.

    Background randomness comes in two modes:

    - ``key=`` (the engine's XLA path): each inner step splits every
      (scenario, replica) key once and draws its normals in-step — the
      identical subgraph at the identical ``[S, R, L]`` shape for every
      window size, which is what keeps results *bitwise* stable across
      ``K`` (hoisting the draws to a ``[K, ...]`` batch invites XLA to
      contract the ``mu + sigma * noise`` FMA differently per shape).
      Frozen elements keep their key: returns ``(state, key)``.
    - ``noise=`` (the fused-kernel contract): the K predrawn normal rows
      are consumed one per tick and ``steps`` tells the caller how many
      splits to advance each element's key chain by. Returns ``state``.

    ``leap=True`` makes every inner step an event leap (the window then
    covers up to ``K`` *events*, not ticks — windows leap, they never degrade
    to dt=1). ``tick`` is the bank fair-share kernel to drive (the
    ``ops.grid_tick_bank`` signature); keeping it injectable lets the
    interpret-mode kernel and the TPU kernel share this scan. With
    ``tick=None`` the window runs its built-in fair-share tick. Either way
    the incidence matrices are one-hot, so every gather-direction
    contraction (process/link quantities back to legs, and the dependency
    lookup) is an exact index gather by the precomputed ``argmax`` index,
    bit-identical to the one-hot matmul with no matmul precision to pin.
    The index is the scenario's ``[S, T]``, shared by every replica row
    (:func:`bank_take_legs`): one column gather over the replica rows.
    Broadcast to ``[S, R, T]``, it made each lookup a general gather of
    ``S * R * T`` index tuples, which took 97 % of the leap event step's
    device time on a TPU v5e. The scatter-direction sums are
    :func:`onehot_sum`, whose result does not depend on the bank layout or
    on the backend's matmul precision.
    """
    f32 = jnp.float32
    i32 = jnp.int32
    if (key is None) == (noise is None):
        raise ValueError(
            "grid_tick_bank_window: pass exactly one of key= (draw in-step) "
            "or noise= (predrawn rows)"
        )
    if key is not None and window is None:
        raise ValueError("grid_tick_bank_window: key= mode requires window=")
    n_links = bg_mu.shape[-1]

    # index tables for the gather-direction contractions (process/link
    # quantities back to legs), computed once, outside the scan; the
    # scatter direction is a fixed-order one-hot sum (onehot_sum)
    proc_of_leg = leg_index(leg_proc)  # [S, T]
    link_of_leg = leg_index(leg_link)  # [S, T]
    m_cat = jnp.concatenate([leg_proc, leg_link], axis=-1)[:, None]
    n_procs = leg_proc.shape[-1]

    leg_from_proc = lambda v: bank_take_legs(v, proc_of_leg)
    leg_from_link = lambda v: bank_take_legs(v, link_of_leg)

    def scatter_pl(v: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Per-process and per-link sums of a per-leg quantity, as one sum
        against the concatenated incidences (each column is summed on its
        own, so the bits equal two separate sums)."""
        both = onehot_sum(v, m_cat)
        return both[..., :n_procs], both[..., n_procs:]

    if tick is None:
        keep3 = keep_frac if keep_frac.ndim == 3 else keep_frac[:, None]

        def tick(a, remaining, _keep, bg, bandwidth_, _lp, _pl, _ll):
            threads = jnp.einsum("srt,stp->srp", a, leg_proc)
            proc_active = (threads > 0).astype(f32)
            campaign = jnp.einsum("srp,spl->srl", proc_active, proc_link)
            denom = jnp.maximum(campaign + jnp.maximum(bg, 0.0), 1.0)
            per_proc_bw = bandwidth_[:, None, :] / denom  # [S, R, L]
            per_proc_bw_leg = leg_from_link(per_proc_bw)
            threads_leg = jnp.maximum(leg_from_proc(threads), 1.0)
            chunk = a * keep3 * per_proc_bw_leg / threads_leg
            xfer = jnp.minimum(remaining, chunk)
            proc_xfer, link_xfer = scatter_pl(xfer)
            return xfer, proc_xfer, link_xfer

    def step(carry, noise_t):
        (t, steps, remaining, done, started, t_start, t_end, conth, conpr,
         bg), k = carry
        alive = (t < max_ticks[:, None]) & ~jnp.all(done, axis=-1)  # [S, R]
        t3 = t[:, :, None]
        if k is not None:
            # the canonical split-and-draw sequence (see bank_split_draw);
            # frozen elements keep their key (vmap-of-while semantics)
            nk, noise_t = bank_split_draw(k, n_links)
            k = jnp.where(alive[:, :, None], nk, k)
        fresh_t = jnp.maximum(bg_mu + bg_sigma * noise_t, 0.0)
        due = (t3 % bg_period[:, None, :] == 0) & alive[:, :, None]
        bg = jnp.where(due, fresh_t, bg)

        dep_done = _bank_dep_ok(dep, done)
        active = (
            (~done) & (release[:, None, :] <= t3) & dep_done
            & alive[:, :, None]
        )
        a = active.astype(f32)

        if not leap:
            xfer, proc_xfer, link_xfer = tick(
                a, remaining, keep_frac, bg, bandwidth,
                leg_proc, proc_link, leg_link,
            )
            remaining = remaining - xfer
            newly_done = active & (remaining <= 1e-6)
            done = done | newly_done
            own_proc_xfer = leg_from_proc(proc_xfer)
            own_link_xfer = leg_from_link(link_xfer)
            conth = conth + a * (own_proc_xfer - xfer)
            conpr = conpr + a * (own_link_xfer - own_proc_xfer)
            t_start = jnp.where(active & (~started), t3, t_start)
            started = started | active
            t_end = jnp.where(newly_done, t3 + 1, t_end)
            adv = alive.astype(i32)
        else:
            inf_rem = jnp.full_like(remaining, jnp.inf)
            rate, proc_rate, link_rate = tick(
                a, inf_rem, keep_frac, bg, bandwidth,
                leg_proc, proc_link, leg_link,
            )
            ttc = jnp.where(
                active & (rate > 0),
                jnp.ceil(remaining / jnp.maximum(rate, 1e-30)),
                jnp.inf,
            )
            pending = (~done) & (release[:, None, :] > t3)
            t_rel = jnp.where(
                pending, (release[:, None, :] - t3).astype(f32), jnp.inf
            )
            # sigma=0 links hold bg = max(mu, 0) from t=0 forever — their
            # resample ticks are rate no-ops, so they never throttle dt
            # (mirrors the per-sim leap body; keeps the leap exact)
            t_bg = jnp.where(
                bg_sigma > 0,
                (bg_period[:, None, :] - t3 % bg_period[:, None, :])
                .astype(f32),  # >= 1
                jnp.inf,
            )
            dt = jnp.minimum(
                jnp.minimum(jnp.min(ttc, axis=-1), jnp.min(t_rel, axis=-1)),
                jnp.min(t_bg, axis=-1),
            )  # [S, R]
            dt = jnp.where(jnp.isfinite(dt), jnp.maximum(dt, 1.0), 1.0)
            dt3 = dt[:, :, None]

            rem_mid = remaining - a * rate * (dt3 - 1.0)
            xfer_f = jnp.minimum(rem_mid, rate) * a
            proc_xfer_f, link_xfer_f = scatter_pl(xfer_f)
            remaining = rem_mid - xfer_f

            own_proc_rate = leg_from_proc(proc_rate)
            own_link_rate = leg_from_link(link_rate)
            own_proc_f = leg_from_proc(proc_xfer_f)
            own_link_f = leg_from_link(link_xfer_f)
            conth = conth + a * ((own_proc_rate - rate) * (dt3 - 1.0)
                                 + (own_proc_f - xfer_f))
            conpr = conpr + a * ((own_link_rate - own_proc_rate) * (dt3 - 1.0)
                                 + (own_link_f - own_proc_f))

            newly_done = active & (remaining <= 1e-6)
            done = done | newly_done
            t_start = jnp.where(active & (~started), t3, t_start)
            started = started | active
            t_end = jnp.where(newly_done, t3 + dt3.astype(i32), t_end)
            adv = dt.astype(i32) * alive.astype(i32)

        return ((
            t + adv, steps + alive.astype(i32), remaining, done, started,
            t_start, t_end, conth, conpr, bg,
        ), k), None

    if key is not None:
        (final, key), _ = jax.lax.scan(
            step, (tuple(state), key), None, length=window
        )
        return final, key
    (final, _), _ = jax.lax.scan(step, (tuple(state), None), noise)
    return final


# ---------------------------------------------------------------------------
# flash_attention: causal/GQA/sliding-window attention (training & prefill)
# ---------------------------------------------------------------------------
def flash_attention(
    q: jax.Array,  # [B, Sq, Hq, D]
    k: jax.Array,  # [B, Skv, Hkv, D]
    v: jax.Array,  # [B, Skv, Hkv, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,  # sliding window size (None = full)
    scale: Optional[float] = None,
    q_offset: int = 0,  # absolute position of q[0] (for prefill continuation)
) -> jax.Array:
    """Reference multi-head attention with GQA and optional sliding window."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    rep = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    dtype = q.dtype
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kf = jnp.repeat(kf, rep, axis=2)  # [B, Skv, Hq, D]
    vf = jnp.repeat(vf, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    q_pos = jnp.arange(Sq)[:, None] + q_offset
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (can happen with window=0 edge cases) -> zeros
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# decode_attention: one-token query against a long KV cache (serving)
# ---------------------------------------------------------------------------
def decode_attention(
    q: jax.Array,  # [B, Hq, D] single new token per sequence
    k_cache: jax.Array,  # [B, S, Hkv, D]
    v_cache: jax.Array,  # [B, S, Hkv, D]
    lengths: jax.Array,  # [B] i32 valid cache lengths
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference KV-cache decode attention (GQA), masking positions >= length."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    rep = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    dtype = q.dtype
    qf = q.astype(jnp.float32) * scale
    kf = jnp.repeat(k_cache.astype(jnp.float32), rep, axis=2)
    vf = jnp.repeat(v_cache.astype(jnp.float32), rep, axis=2)
    logits = jnp.einsum("bhd,bshd->bhs", qf, kf)
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # [B, S]
    logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out = jnp.einsum("bhs,bshd->bhd", probs, vf)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# mlstm_chunk: chunkwise-parallel mLSTM (xLSTM) / gated linear attention
# ---------------------------------------------------------------------------
def mlstm_chunk(
    q: jax.Array,  # [B, S, H, Dk]
    k: jax.Array,  # [B, S, H, Dk]
    v: jax.Array,  # [B, S, H, Dv]
    i_gate: jax.Array,  # [B, S, H] input-gate pre-activations
    f_gate: jax.Array,  # [B, S, H] forget-gate pre-activations
    *,
    eps: float = 1e-6,
    normalize: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference mLSTM (matrix-memory LSTM) in its fully-parallel form.

    ``normalize=True`` follows xLSTM (arXiv:2405.04517): stabilized
    exponential input gates, *sigmoid* forget gates in log space, and the
    max(|.|, exp(-m)) normalizer. ``normalize=False`` is the mamba-2 SSD
    variant: ``f_gate`` is the raw log-decay (<= 0), ``i_gate`` the raw
    log-injection, no stabilizer shift and no normalizer — the two memories
    are the same chunkwise recurrence (see DESIGN.md).
    """
    B, S, H, Dk = q.shape
    dtype = q.dtype
    if scale is None:
        scale = Dk ** -0.5 if normalize else 1.0
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    fg = f_gate.astype(jnp.float32)
    logf = jax.nn.log_sigmoid(fg) if normalize else fg
    logi = i_gate.astype(jnp.float32)
    # cumulative log forget: F[t] = sum_{u<=t} logf[u]
    F = jnp.cumsum(logf, axis=1)
    # D_ts = F[t] - F[s] + logi[s] for s <= t  (decay from s to t)
    dmat = F[:, :, None, :] - F[:, None, :, :] + logi[:, None, :, :]  # [B,S,S,H]
    causal = jnp.tril(jnp.ones((S, S), bool))
    dmat = jnp.where(causal[None, :, :, None], dmat, -jnp.inf)
    if normalize:
        # stabilizer m[t] = max_s D_ts
        m = jnp.max(dmat, axis=2, keepdims=True)  # [B,S,1,H]
    else:
        m = jnp.zeros_like(dmat[:, :, :1, :])
    dexp = jnp.exp(dmat - m)  # [B,S,S,H]
    scores = jnp.einsum("bthd,bshd->btsh", qf, kf) * dexp
    out = jnp.einsum("btsh,bshd->bthd", scores, vf)
    if normalize:
        norm = jnp.maximum(jnp.abs(scores.sum(axis=2)), jnp.exp(-m[:, :, 0, :])) + eps
        out = out / norm[..., None]
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# selu_mlp: fused SELU MLP forward (SBI classifier, 4 hidden layers x 128)
# ---------------------------------------------------------------------------
def selu_mlp(
    x: jax.Array,  # [N, F_in]
    weights: Tuple[jax.Array, ...],  # list of [F_i, F_{i+1}]
    biases: Tuple[jax.Array, ...],  # list of [F_{i+1}]
) -> jax.Array:
    """Reference MLP with SELU nonlinearities on all but the last layer."""
    h = x.astype(jnp.float32)
    n = len(weights)
    for i, (w, b) in enumerate(zip(weights, biases)):
        h = h @ w.astype(jnp.float32) + b.astype(jnp.float32)
        if i < n - 1:
            h = jax.nn.selu(h)
    return h.astype(x.dtype)
