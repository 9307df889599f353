"""Vectorized GDAPS tick engine.

The SimPy process-based discrete-event simulator of the paper is executed
here as a dense, synchronous tick program (one tick = one second, exactly the
paper's chunk granularity): the compiled :class:`~repro.core.workload.LegTable`
becomes constant one-hot incidence matrices, per-tick fair-share bandwidth
allocation becomes three small matmuls (MXU work), and the tick loop is a
``jax.lax.while_loop``. Batches of stochastic simulations are ``vmap``-ed and
sharded over the device mesh by the calibration layer.

Semantics are identical to an event-driven execution at 1-tick resolution;
``repro.core.refsim`` provides the plain-Python oracle used by the tests.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
from typing import Callable, Iterator, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec
import numpy as np

from repro.core.workload import (
    BucketedBank,
    LegTable,
    PAD_BG_PERIOD,
    PAD_PROFILE,
    PAD_PROTOCOL,
    ScenarioBank,
)
from repro.kernels import ops, ref

__all__ = [
    "SimSpec",
    "SimParams",
    "SimResult",
    "BankCheckpoint",
    "simulate",
    "simulate_batch",
    "bank_spec",
    "make_bank_params",
    "simulate_bank",
    "simulate_bank_stepped",
    "resolve_mesh",
    "default_tick_window",
    "record_window_sweep",
    "bank_trace_count",
    "reset_bank_trace_count",
    "count_bank_traces",
    "register_cache_clear_hook",
]


class SimSpec(NamedTuple):
    """Static (weakly-typed, jnp) arrays describing one compiled campaign.

    The same structure carries a **stacked bank** of campaigns: every field
    then has a leading ``[N]`` scenario dim (see :func:`bank_spec`),
    ``max_ticks`` becomes a per-scenario array, and ``leg_valid`` masks the
    padding (padded legs are born done). ``simulate`` always consumes the
    per-scenario view — :func:`simulate_bank` vmaps it over the bank."""

    size_mb: jax.Array  # [T] f32
    release: jax.Array  # [T] i32
    dep: jax.Array  # [T] i32 (-1 = none)
    profile: jax.Array  # [T] i32 ProfileTag
    protocol_id: jax.Array  # [T] i32
    leg_proc: jax.Array  # [T, P] f32 one-hot
    proc_link: jax.Array  # [P, L] f32 one-hot
    leg_link: jax.Array  # [T, L] f32 one-hot
    bandwidth: jax.Array  # [L] f32 MB/tick
    bg_period: jax.Array  # [L] i32
    max_ticks: Union[int, jax.Array]  # python int or [] i32 (bank member)
    leg_valid: Optional[jax.Array] = None  # [T] bool (None = all real legs)

    @property
    def n_legs(self) -> int:
        return self.size_mb.shape[-1]

    @property
    def n_links(self) -> int:
        return self.bandwidth.shape[-1]

    @staticmethod
    def from_table(table: LegTable, max_ticks: Optional[int] = None) -> "SimSpec":
        return SimSpec(
            size_mb=jnp.asarray(table.size_mb),
            release=jnp.asarray(table.release),
            dep=jnp.asarray(table.dep),
            profile=jnp.asarray(table.profile),
            protocol_id=jnp.asarray(table.protocol_id),
            leg_proc=jnp.asarray(table.leg_proc_onehot()),
            proc_link=jnp.asarray(table.proc_link_onehot()),
            leg_link=jnp.asarray(table.leg_link_onehot()),
            bandwidth=jnp.asarray(table.links.bandwidth),
            bg_period=jnp.asarray(table.links.bg_period),
            max_ticks=(
                int(max_ticks)
                if max_ticks is not None
                else table.max_ticks_upper_bound()
            ),
        )


class SimParams(NamedTuple):
    """Runtime simulator parameters (the calibration target ``theta`` maps
    onto these without retracing: per-leg keep fraction and per-link
    background-load distribution). ``enabled`` masks legs out of the
    campaign entirely (born-done; used by the access-profile optimizer to
    evaluate candidate assignments against one static super-table)."""

    keep_frac: jax.Array  # [T] f32 = 1 - overhead per leg
    bg_mu: jax.Array  # [L] f32
    bg_sigma: jax.Array  # [L] f32
    enabled: Optional[jax.Array] = None  # [T] bool (None = all enabled)


class SimResult(NamedTuple):
    """Per-leg observation record (the paper's (T, S, ConTh, ConPr) tuples)."""

    transfer_time: jax.Array  # [T] f32 ticks (active duration)
    size_mb: jax.Array  # [T] f32
    conth_mb: jax.Array  # [T] f32 traffic of sibling threads during window
    conpr_mb: jax.Array  # [T] f32 traffic of other campaign procs on the link
    done: jax.Array  # [T] bool
    ticks: jax.Array  # [] i32 total ticks simulated
    profile: jax.Array  # [T] i32
    start_tick: jax.Array  # [T] f32 first active tick per leg


class _Carry(NamedTuple):
    t: jax.Array
    remaining: jax.Array
    done: jax.Array
    started: jax.Array
    t_start: jax.Array
    t_end: jax.Array
    conth: jax.Array
    conpr: jax.Array
    bg: jax.Array
    key: jax.Array


def _leap_body(
    spec: SimSpec,
    params: SimParams,
    backend: Optional[str],
    c: _Carry,
    alive: Optional[jax.Array] = None,
) -> _Carry:
    """Event-leap tick body (beyond-paper, semantics-exact).

    Between events (a leg completing, a release tick, a background-load
    resample) the fair-share rates are constant, so a whole inter-event
    window of ``dt`` ticks is applied in closed form: ``dt-1`` rate-exact
    ticks plus the (possibly clipped) final tick. One ``grid_tick`` rate
    evaluation plus two small one-hot sums per window replaces ``dt``
    full tick evaluations; results are bit-comparable to the tick loop for
    deterministic background loads (see tests/benchmarks: ~10x).

    ``alive`` (a scalar bool, batched under vmap) folds the while-loop
    freeze into the update masks for windowed execution: with ``alive``
    False the carry — clock, RNG key and background loads included — passes
    through bit-identically to a frozen iteration, because a leg that is
    forced inactive transfers nothing and every accumulator update is a
    fixed point. ``None`` (the per-tick while loop) skips the masking.
    """
    t = c.t
    # background-load resample due at this tick (same order as _tick_body)
    key, sub = jax.random.split(c.key)
    noise = jax.random.normal(sub, c.bg.shape, jnp.float32)
    fresh = jnp.maximum(params.bg_mu + params.bg_sigma * noise, 0.0)
    due = t % spec.bg_period == 0
    if alive is not None:
        due &= alive
        key = jnp.where(alive, key, c.key)
    bg = jnp.where(due, fresh, c.bg)

    dep_done = jnp.where(spec.dep >= 0, c.done[jnp.maximum(spec.dep, 0)], True)
    active = (~c.done) & (spec.release <= t) & dep_done
    if alive is not None:
        active &= alive
    a = active.astype(jnp.float32)

    # unclipped fair-share rates (chunk per tick) under the current loads
    inf_rem = jnp.full_like(c.remaining, jnp.inf)
    rate, proc_rate, link_rate = ops.grid_tick(
        a, inf_rem, params.keep_frac, bg, spec.bandwidth,
        spec.leg_proc, spec.proc_link, spec.leg_link, backend=backend,
    )

    # ticks until each event class; the window includes its event tick
    ttc = jnp.where(
        active & (rate > 0), jnp.ceil(c.remaining / jnp.maximum(rate, 1e-30)),
        jnp.inf,
    )
    pending = (~c.done) & (spec.release > t)
    t_rel = jnp.where(pending, (spec.release - t).astype(jnp.float32), jnp.inf)
    # background-resample events only matter for stochastic links: a
    # sigma=0 link holds bg = max(mu, 0) from its t=0 resample forever, so
    # its period ticks are rate no-ops and skipping them keeps the
    # closed-form leap exact (deterministic links no longer throttle dt)
    t_bg = jnp.where(
        params.bg_sigma > 0,
        (spec.bg_period - t % spec.bg_period).astype(jnp.float32),  # >= 1
        jnp.inf,
    )
    dt = jnp.minimum(jnp.minimum(jnp.min(ttc), jnp.min(t_rel)), jnp.min(t_bg))
    dt = jnp.where(jnp.isfinite(dt), jnp.maximum(dt, 1.0), 1.0)

    # dt-1 rate-exact ticks + the final (possibly clipped) tick
    rem_mid = c.remaining - a * rate * (dt - 1.0)
    xfer_f = jnp.minimum(rem_mid, rate) * a
    proc_xfer_f = ref.onehot_sum(xfer_f, spec.leg_proc)
    link_xfer_f = ref.onehot_sum(xfer_f, spec.leg_link)
    remaining = rem_mid - xfer_f

    proc_of_leg = ref.leg_index(spec.leg_proc)
    link_of_leg = ref.leg_index(spec.leg_link)
    own_proc_rate = ref.gather_legs(proc_rate, proc_of_leg)
    own_link_rate = ref.gather_legs(link_rate, link_of_leg)
    own_proc_f = ref.gather_legs(proc_xfer_f, proc_of_leg)
    own_link_f = ref.gather_legs(link_xfer_f, link_of_leg)
    conth = c.conth + a * ((own_proc_rate - rate) * (dt - 1.0)
                           + (own_proc_f - xfer_f))
    conpr = c.conpr + a * ((own_link_rate - own_proc_rate) * (dt - 1.0)
                           + (own_link_f - own_proc_f))

    newly_done = active & (remaining <= 1e-6)
    done = c.done | newly_done
    t_start = jnp.where(active & (~c.started), t, c.t_start)
    started = c.started | active
    t_end = jnp.where(newly_done, t + dt.astype(jnp.int32), c.t_end)

    adv = dt.astype(jnp.int32)
    if alive is not None:
        adv *= alive.astype(jnp.int32)
    return _Carry(
        t=t + adv,
        remaining=remaining,
        done=done,
        started=started,
        t_start=t_start,
        t_end=t_end,
        conth=conth,
        conpr=conpr,
        bg=bg,
        key=key,
    )


def _tick_body(
    spec: SimSpec,
    params: SimParams,
    backend: Optional[str],
    c: _Carry,
    alive: Optional[jax.Array] = None,
) -> _Carry:
    """One simulation tick. ``alive`` folds the while-loop freeze into the
    update masks for windowed execution (see :func:`_leap_body`)."""
    t = c.t
    # background-load resampling, once per link update period (paper Sec. 4)
    key, sub = jax.random.split(c.key)
    noise = jax.random.normal(sub, c.bg.shape, jnp.float32)
    fresh = jnp.maximum(params.bg_mu + params.bg_sigma * noise, 0.0)
    due = t % spec.bg_period == 0
    if alive is not None:
        due &= alive
        key = jnp.where(alive, key, c.key)
    bg = jnp.where(due, fresh, c.bg)

    dep_done = jnp.where(spec.dep >= 0, c.done[jnp.maximum(spec.dep, 0)], True)
    active = (~c.done) & (spec.release <= t) & dep_done
    if alive is not None:
        active &= alive
    a = active.astype(jnp.float32)

    xfer, proc_xfer, link_xfer = ops.grid_tick(
        a,
        c.remaining,
        params.keep_frac,
        bg,
        spec.bandwidth,
        spec.leg_proc,
        spec.proc_link,
        spec.leg_link,
        backend=backend,
    )

    remaining = c.remaining - xfer
    newly_done = active & (remaining <= 1e-6)
    done = c.done | newly_done

    # concurrency traffic accumulators (paper Eq. 1 regressors):
    #   ConTh — traffic of the *other threads of the same process* while the
    #           leg is active;
    #   ConPr — traffic of *other campaign processes on the same link*.
    own_proc_xfer = ref.gather_legs(proc_xfer, ref.leg_index(spec.leg_proc))
    own_link_xfer = ref.gather_legs(link_xfer, ref.leg_index(spec.leg_link))
    conth = c.conth + a * (own_proc_xfer - xfer)
    conpr = c.conpr + a * (own_link_xfer - own_proc_xfer)

    t_start = jnp.where(active & (~c.started), t, c.t_start)
    started = c.started | active
    t_end = jnp.where(newly_done, t + 1, c.t_end)

    adv = 1 if alive is None else alive.astype(jnp.int32)
    return _Carry(
        t=t + adv,
        remaining=remaining,
        done=done,
        started=started,
        t_start=t_start,
        t_end=t_end,
        conth=conth,
        conpr=conpr,
        bg=bg,
        key=key,
    )


@functools.partial(jax.jit, static_argnames=("backend", "leap", "window"))
def _simulate(
    spec: SimSpec,
    params: SimParams,
    key: jax.Array,
    *,
    backend: Optional[str] = None,
    leap: bool = False,
    window: int = 1,
) -> SimResult:
    """Jitted body of :func:`simulate`. ``window`` must be a resolved int
    (trace-purity contract: ``window=None`` is resolved by the public
    wrapper *outside* jit, so env/table reads never run at trace time and
    never go stale inside a cached trace — see CONTRACTS.md)."""
    n = spec.n_legs
    born_done = jnp.zeros((n,), bool)
    if params.enabled is not None:
        born_done |= ~params.enabled.astype(bool)
    if spec.leg_valid is not None:
        # bank padding contract: padded legs are born done and stay inert
        born_done |= ~spec.leg_valid.astype(bool)
    init = _Carry(
        t=jnp.zeros((), jnp.int32),
        remaining=spec.size_mb,
        done=born_done,
        started=jnp.zeros((n,), bool),
        t_start=jnp.zeros((n,), jnp.int32),
        t_end=jnp.zeros((n,), jnp.int32),
        conth=jnp.zeros((n,), jnp.float32),
        conpr=jnp.zeros((n,), jnp.float32),
        bg=jnp.zeros((spec.n_links,), jnp.float32),
        key=key,
    )

    if leap:
        base = functools.partial(_leap_body, spec, params, backend)
    else:
        base = functools.partial(_tick_body, spec, params, backend)

    def cond(c: _Carry) -> jax.Array:
        return (c.t < spec.max_ticks) & (~jnp.all(c.done))

    if window > 1:
        def body(c: _Carry) -> _Carry:
            def inner(cc: _Carry, _):
                # the freeze mask re-evaluates the loop condition per inner
                # tick, so a sim finishing mid-window stops exactly there
                return base(cc, alive=cond(cc)), None

            return jax.lax.scan(inner, c, None, length=window)[0]
    else:
        body = base

    final = jax.lax.while_loop(cond, body, init)
    return SimResult(
        # unfinished legs have t_end frozen at 0 while t_start may be > 0:
        # mask them to 0 instead of emitting a negative duration
        transfer_time=jnp.where(
            final.done, (final.t_end - final.t_start).astype(jnp.float32), 0.0
        ),
        size_mb=spec.size_mb,
        conth_mb=final.conth,
        conpr_mb=final.conpr,
        done=final.done,
        ticks=final.t,
        profile=spec.profile,
        start_tick=final.t_start.astype(jnp.float32),
    )


def simulate(
    spec: SimSpec,
    params: SimParams,
    key: jax.Array,
    *,
    backend: Optional[str] = None,
    leap: bool = False,
    window: Optional[int] = 1,
) -> SimResult:
    """Run one stochastic simulation of the campaign.

    Returns per-leg observations; legs that never finish within
    ``spec.max_ticks`` have ``done=False`` and ``transfer_time=0`` (their
    end tick is undefined, so the duration is masked out rather than
    reported as the garbage ``-t_start`` — consumers must filter on
    ``done`` for duration statistics). ``leap=True`` enables the exact
    event-leap acceleration (identical results for deterministic background
    loads; statistically equivalent — same per-event sampling — for
    stochastic ones).

    ``window=K`` fuses ``K`` ticks (or, under ``leap``, ``K`` event leaps —
    windows leap, they never degrade to dt=1) into each while-loop
    iteration via an inner ``lax.scan`` whose per-tick freeze mask
    replicates the loop condition, so results are **bit-identical** to the
    per-tick loop for every ``K`` — including the stochastic background
    stream and the final ``ticks`` clock — while the loop dispatch/cond
    overhead amortizes ``K``-fold (see ``tests/test_tick_window.py``).
    ``window=None`` resolves the auto default, like every other window
    entry point — resolved *here*, outside the jitted body, so the env
    var / sweep-table reads happen per call, not once at trace time.
    """
    window = _resolve_window(window, leap) if window is None else int(window)
    return _simulate(
        spec, params, key, backend=backend, leap=leap, window=window
    )


def _params_axes(params: SimParams, base_ndim: int = 1) -> SimParams:
    """Per-field vmap axes: 0 for fields carrying a leading batch dim beyond
    their per-sim rank, None for shared fields (mixing is allowed — e.g. a
    population of ``enabled`` masks under one shared theta)."""
    ax = lambda f: None if f is None else (0 if f.ndim > base_ndim else None)
    return SimParams(
        keep_frac=ax(params.keep_frac),
        bg_mu=ax(params.bg_mu),
        bg_sigma=ax(params.bg_sigma),
        enabled=ax(params.enabled),
    )


@functools.partial(jax.jit, static_argnames=("backend", "leap", "window"))
def _simulate_batch(
    spec: SimSpec,
    params: SimParams,
    keys: jax.Array,  # [B, 2] PRNG keys
    *,
    backend: Optional[str] = None,
    leap: bool = False,
    window: int = 1,
) -> SimResult:
    """Jitted body of :func:`simulate_batch` (``window`` pre-resolved)."""
    return jax.vmap(
        lambda p, k: _simulate(spec, p, k, backend=backend, leap=leap,
                               window=window),
        in_axes=(_params_axes(params), 0),
    )(params, keys)


def simulate_batch(
    spec: SimSpec,
    params: SimParams,
    keys: jax.Array,  # [B, 2] PRNG keys
    *,
    backend: Optional[str] = None,
    leap: bool = False,
    window: Optional[int] = 1,
) -> SimResult:
    """Vectorized batch of stochastic simulations.

    Each ``params`` field may carry a leading batch dim (one theta and/or one
    ``enabled`` mask per sim) or be unbatched (shared theta, e.g. the 16k
    validation runs of Section 5). ``window`` fuses K ticks per loop
    iteration (bit-identical results; see :func:`simulate`); ``None``
    resolves the auto default outside the jitted body.
    """
    window = _resolve_window(window, leap) if window is None else int(window)
    return _simulate_batch(
        spec, params, keys, backend=backend, leap=leap, window=window
    )


# ---------------------------------------------------------------------------
# ScenarioBank execution: one trace, vmap over (scenario, replica)
# ---------------------------------------------------------------------------

# every SimSpec field maps over the leading scenario dim, including the
# per-scenario max_ticks scalar and the padding mask
_BANK_SPEC_AXES = SimSpec(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0)

_bank_traces = 0

# cache-clear callbacks run by reset_bank_trace_count(clear_caches=True).
# Higher layers that memoize compiled artifacts keyed on process history
# (e.g. the fleet-level compile cache in repro.core.fleet) register here so
# trace-count assertions stay order-independent without the engine importing
# them.
_cache_clear_hooks: list[Callable[[], None]] = []


def register_cache_clear_hook(fn: Callable[[], None]) -> None:
    """Register ``fn()`` to run whenever the banked-engine caches are
    dropped (see :func:`reset_bank_trace_count`). Idempotent per function."""
    if fn not in _cache_clear_hooks:
        _cache_clear_hooks.append(fn)


def bank_trace_count() -> int:
    """Number of times the banked engine has been (re)traced in this process
    — the observable behind the "no per-scenario retrace" contract."""
    return _bank_traces


def reset_bank_trace_count(*, clear_caches: bool = True) -> None:
    """Zero the banked-engine trace counter.

    The counter is process-global and only grows, which makes absolute
    trace-count assertions order-dependent (a shape traced by an earlier
    caller is cached and silently costs zero). ``clear_caches=True``
    (default) also drops the jit caches of both banked lowerings — so the
    next ``simulate_bank`` call re-traces no matter what ran before — and
    every registered higher-layer cache (the fleet-level compile cache; see
    :func:`register_cache_clear_hook`): the order-independent fixture for
    tests and benchmarks.
    """
    global _bank_traces
    _bank_traces = 0
    if clear_caches:
        _simulate_bank.clear_cache()
        _simulate_bank_banked.clear_cache()
        _simulate_bank_bucketed_impl.clear_cache()
        _simulate_bank_sharded.clear_cache()
        _banked_window_step.clear_cache()
        _banked_window_step_sharded.clear_cache()
        _admit_bank_rows.clear_cache()
        _admit_bank_rows_sharded.clear_cache()
        _bank_snapshot.clear_cache()
        _bank_snapshot_sharded.clear_cache()
        for fn in list(_cache_clear_hooks):
            fn()


class _TraceDelta:
    """Live view of banked-engine traces since the scope was entered."""

    def __init__(self) -> None:
        self._start = _bank_traces

    @property
    def count(self) -> int:
        return _bank_traces - self._start


@contextlib.contextmanager
def count_bank_traces() -> Iterator[_TraceDelta]:
    """Context manager counting banked-engine (re)traces inside the block::

        with count_bank_traces() as traces:
            simulate_bank(bank, params, keys)
        assert traces.count == expected

    Relative counting makes assertions robust to whatever earlier callers
    already traced (pair with :func:`reset_bank_trace_count` when the
    assertion must also be immune to cached shapes).
    """
    yield _TraceDelta()


def bank_spec(bank: ScenarioBank) -> SimSpec:
    """The stacked ``[N, ...]`` SimSpec view of a compiled bank.

    The device arrays are memoized on the bank instance (compiled banks are
    immutable by contract), so repeated warm ``simulate_bank`` calls don't
    re-upload the spec every dispatch. When first called under a jit trace
    the arrays are tracers — those must not leak into the cache.
    """
    cached = getattr(bank, "_spec_cache", None)
    if cached is not None:
        return cached
    spec = _bank_spec_uncached(bank)
    if not isinstance(spec.size_mb, jax.core.Tracer):
        bank._spec_cache = spec
    return spec


def _bank_spec_uncached(bank: ScenarioBank) -> SimSpec:
    return SimSpec(
        size_mb=jnp.asarray(bank.size_mb),
        release=jnp.asarray(bank.release),
        dep=jnp.asarray(bank.dep),
        profile=jnp.asarray(bank.profile),
        protocol_id=jnp.asarray(bank.protocol_id),
        leg_proc=jnp.asarray(bank.leg_proc),
        proc_link=jnp.asarray(bank.proc_link),
        leg_link=jnp.asarray(bank.leg_link),
        bandwidth=jnp.asarray(bank.bandwidth),
        bg_period=jnp.asarray(bank.bg_period),
        max_ticks=jnp.asarray(bank.max_ticks),
        leg_valid=jnp.asarray(bank.leg_valid),
    )


def make_bank_params(
    bank: ScenarioBank,
    *,
    overhead: Optional[float] = None,
    bg_mu: Optional[float] = None,
    bg_sigma: Optional[float] = None,
    protocol: Optional[str] = None,
) -> SimParams:
    """Bank-wide :class:`SimParams` (``[N, T]`` keep, ``[N, L]`` moments) with
    the same override knobs as :func:`make_params`, applied across the unified
    protocol namespace of the bank."""
    keep = bank.keep_frac.astype(np.float32).copy()
    if overhead is not None:
        if protocol is None:
            keep[bank.leg_valid] = 1.0 - overhead
        else:
            pid = bank.protocol_names.index(protocol)
            keep[bank.protocol_id == pid] = 1.0 - overhead
    mu = bank.bg_mu if bg_mu is None else np.where(bank.link_valid, bg_mu, 0.0)
    sigma = (
        bank.bg_sigma if bg_sigma is None
        else np.where(bank.link_valid, bg_sigma, 0.0)
    )
    return SimParams(
        keep_frac=jnp.asarray(keep),
        bg_mu=jnp.asarray(mu, jnp.float32),
        bg_sigma=jnp.asarray(sigma, jnp.float32),
    )


def _vmap_bank_core(
    spec: SimSpec,
    params: SimParams,
    keys: jax.Array,
    *,
    backend: Optional[str],
    leap: bool,
    window: int = 1,
) -> SimResult:
    """Unjitted vmap-of-``simulate`` bank program (shared by the jitted
    monolithic entry point and the shard_map per-device body — every op is
    row-local over the scenario axis, so sharding it is collective-free)."""

    def one_scenario(spec_i: SimSpec, params_i: SimParams, keys_i: jax.Array):
        # _simulate, not the public wrapper: window is already a resolved
        # int here and the traced path must not re-enter window resolution
        return jax.vmap(
            lambda p, k: _simulate(spec_i, p, k, backend=backend, leap=leap,
                                   window=window),
            in_axes=(_params_axes(params_i), 0),
        )(params_i, keys_i)

    # outer vmap peels the scenario dim off every spec/params field; the
    # inner vmap runs the replicas, sharing params fields without an [N, R]
    # leading shape
    outer_params_axes = SimParams(
        keep_frac=0,
        bg_mu=0,
        bg_sigma=0,
        enabled=None if params.enabled is None else 0,
    )
    return jax.vmap(
        one_scenario, in_axes=(_BANK_SPEC_AXES, outer_params_axes, 0)
    )(spec, params, keys)


@functools.partial(jax.jit, static_argnames=("backend", "leap", "window"))
def _simulate_bank(
    spec: SimSpec,  # stacked [N, ...]
    params: SimParams,  # fields [N, ...] or [N, R, ...]
    keys: jax.Array,  # [N, R, 2]
    *,
    backend: Optional[str],
    leap: bool,
    window: int = 1,
) -> SimResult:
    global _bank_traces
    _bank_traces += 1  # executes at trace time only
    return _vmap_bank_core(
        spec, params, keys, backend=backend, leap=leap, window=window
    )


# ---------------------------------------------------------------------------
# manual banked lowering: one while loop over [S, R, ...] state driving
# ops.grid_tick_bank directly (the bank-tiled kernel on TPU)
# ---------------------------------------------------------------------------


def _rep3(field: Optional[jax.Array]) -> Optional[jax.Array]:
    """Lift a bank-wide ``[S, X]`` params field to broadcast against
    per-(scenario, replica) ``[S, R, X]`` state (no-op if already 3-D)."""
    if field is None or field.ndim == 3:
        return field
    return field[:, None, :]


def _banked_init_carry(spec: SimSpec, params: SimParams, keys: jax.Array) -> _Carry:
    """Initial ``[S, R, ...]`` carry of the banked lowering (padded and
    disabled legs born done)."""
    S, T = spec.size_mb.shape
    L = spec.bandwidth.shape[-1]
    R = keys.shape[1]

    born_done = jnp.zeros((S, R, T), bool)
    if params.enabled is not None:
        born_done |= ~_rep3(params.enabled).astype(bool)
    if spec.leg_valid is not None:
        born_done |= ~spec.leg_valid[:, None, :].astype(bool)

    return _Carry(
        t=jnp.zeros((S, R), jnp.int32),
        remaining=jnp.broadcast_to(spec.size_mb[:, None, :], (S, R, T)),
        done=born_done,
        started=jnp.zeros((S, R, T), bool),
        t_start=jnp.zeros((S, R, T), jnp.int32),
        t_end=jnp.zeros((S, R, T), jnp.int32),
        conth=jnp.zeros((S, R, T), jnp.float32),
        conpr=jnp.zeros((S, R, T), jnp.float32),
        bg=jnp.zeros((S, R, L), jnp.float32),
        key=keys,
    )


def _banked_live(spec: SimSpec, c: _Carry) -> jax.Array:  # [S, R]
    return (c.t < spec.max_ticks[:, None]) & ~jnp.all(c.done, axis=-1)


def _banked_result(spec: SimSpec, final: _Carry) -> SimResult:
    S, R, T = final.remaining.shape
    return SimResult(
        transfer_time=jnp.where(
            final.done, (final.t_end - final.t_start).astype(jnp.float32), 0.0
        ),
        size_mb=jnp.broadcast_to(spec.size_mb[:, None, :], (S, R, T)),
        conth_mb=final.conth,
        conpr_mb=final.conpr,
        done=final.done,
        ticks=final.t,
        profile=jnp.broadcast_to(spec.profile[:, None, :], (S, R, T)),
        start_tick=final.t_start.astype(jnp.float32),
    )


def _bank_window_body(
    spec: SimSpec,
    params: SimParams,
    backend: Optional[str],
    leap: bool,
    window: int,
    c: _Carry,
) -> _Carry:
    """Advance the whole bank by one fused ``window``-tick step.

    One :func:`repro.kernels.ops.grid_tick_bank_fused` dispatch — a single
    kernel launch on the Pallas backend — advances every (scenario, replica)
    element by up to ``window`` ticks. The carried RNG keys ride along in
    ``key=`` mode: each element's key advances by exactly its alive-step
    count (split in-step on XLA, chain-resynchronized around the fused
    kernel), so frozen carries stay frozen bit for bit, keys included.
    """
    state = (
        c.t, jnp.zeros_like(c.t), c.remaining, c.done, c.started,
        c.t_start, c.t_end, c.conth, c.conpr, c.bg,
    )
    (t, steps, remaining, done, started, t_start, t_end, conth, conpr,
     bg), key = ops.grid_tick_bank_fused(
        state, _rep3(params.bg_mu), _rep3(params.bg_sigma),
        spec.release, spec.dep, spec.bg_period, spec.max_ticks,
        params.keep_frac, spec.bandwidth,
        spec.leg_proc, spec.proc_link, spec.leg_link,
        window=window, leap=leap, backend=backend, key=c.key,
    )
    return _Carry(
        t=t, remaining=remaining, done=done, started=started,
        t_start=t_start, t_end=t_end, conth=conth, conpr=conpr, bg=bg,
        key=key,
    )


@functools.partial(jax.jit, static_argnames=("backend", "leap", "window"))
def _simulate_bank_banked(
    spec: SimSpec,  # stacked [S, ...]
    params: SimParams,  # fields [S, ...] or [S, R, ...]
    keys: jax.Array,  # [S, R, 2]
    *,
    backend: Optional[str],
    leap: bool,
    window: int = 1,
) -> SimResult:
    """Manual banked lowering: the tick/leap loop carries ``[S, R, ...]``
    state and calls :func:`repro.kernels.ops.grid_tick_bank` (or, for
    ``window > 1``, the fused multi-tick
    :func:`repro.kernels.ops.grid_tick_bank_fused`) directly, so the TPU hot
    path hits the bank-tiled kernel (per-scenario incidences — and, fused,
    the whole carry — resident in VMEM) instead of the per-sim kernel under
    a double vmap.

    Semantics are element-for-element those of :func:`_simulate_bank`: each
    (scenario, replica) advances under its own condition (its carry freezes
    once it finishes or hits its scenario's ``max_ticks``), and the RNG
    splits follow the per-scenario body exactly — for every ``window``,
    bit-identically to the per-tick loop.
    """
    global _bank_traces
    _bank_traces += 1  # executes at trace time only
    return _banked_core(
        spec, params, keys, backend=backend, leap=leap, window=window
    )


def _banked_core(
    spec: SimSpec,
    params: SimParams,
    keys: jax.Array,
    *,
    backend: Optional[str],
    leap: bool,
    window: int = 1,
) -> SimResult:
    """Unjitted banked while-loop program (shared by the jitted monolithic
    entry point and the shard_map per-device body). Under shard_map the loop
    condition is evaluated per device shard — no collectives anywhere in
    cond or body — so a shard whose scenarios all finish early stops
    dispatching windows while its neighbours keep ticking."""
    init = _banked_init_carry(spec, params, keys)

    def cond(c: _Carry) -> jax.Array:
        return jnp.any(_banked_live(spec, c))

    # every window size runs the same fused body (window=1 is a length-1
    # window): windowed-vs-per-tick parity is then structural — the K-tick
    # and 1-tick programs share one inner step, so XLA's per-expression
    # rounding (FMA contraction in the noise/fair-share math) cannot drift
    # between them the way it does between separately-written loop bodies
    body = functools.partial(
        _bank_window_body, spec, params, backend, leap, window
    )
    final = jax.lax.while_loop(cond, body, init)
    return _banked_result(spec, final)


# ---------------------------------------------------------------------------
# sharded bank execution: one SPMD program over a 1-D device mesh
# ---------------------------------------------------------------------------


def resolve_mesh(
    mesh: Union[None, Mesh, int, Sequence],
) -> Optional[Mesh]:
    """Normalize a mesh spec to a 1-D :class:`jax.sharding.Mesh` (or None).

    Accepts ``None`` (no sharding), an existing 1-D mesh, a device count
    (the first ``n`` of ``jax.devices()``) or an explicit device sequence.
    The scenario axis is named ``"s"`` for meshes built here; an existing
    mesh keeps its own axis name.
    """
    if mesh is None:
        return None
    if isinstance(mesh, Mesh):
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"bank sharding needs a 1-D mesh over the scenario axis, got "
                f"axes {mesh.axis_names}"
            )
        return mesh
    if isinstance(mesh, int):
        devs = jax.devices()
        if not 1 <= mesh <= len(devs):
            raise ValueError(
                f"mesh device count {mesh} outside 1..{len(devs)} available"
            )
        return Mesh(np.array(devs[:mesh]), ("s",))
    return Mesh(np.array(list(mesh)), ("s",))


def _pad_rows(arr: jax.Array, pad: int, value) -> jax.Array:
    """Append ``pad`` constant rows along the leading (scenario) axis."""
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, widths, constant_values=value)


def _pad_spec_rows(spec: SimSpec, pad: int) -> SimSpec:
    """Append ``pad`` inert scenarios to a stacked spec (the same contract
    as ``workload.compile_bank``'s shard padding: zero-size released legs,
    all-zero incidences, ``max_ticks=0`` so the rows are never live)."""
    leg_valid = spec.leg_valid
    if leg_valid is None:
        leg_valid = jnp.ones(spec.size_mb.shape, bool)
    return SimSpec(
        size_mb=_pad_rows(spec.size_mb, pad, 0.0),
        release=_pad_rows(spec.release, pad, 0),
        dep=_pad_rows(spec.dep, pad, -1),
        profile=_pad_rows(spec.profile, pad, PAD_PROFILE),
        protocol_id=_pad_rows(spec.protocol_id, pad, PAD_PROTOCOL),
        leg_proc=_pad_rows(spec.leg_proc, pad, 0.0),
        proc_link=_pad_rows(spec.proc_link, pad, 0.0),
        leg_link=_pad_rows(spec.leg_link, pad, 0.0),
        bandwidth=_pad_rows(spec.bandwidth, pad, 0.0),
        bg_period=_pad_rows(spec.bg_period, pad, PAD_BG_PERIOD),
        max_ticks=_pad_rows(spec.max_ticks, pad, 0),
        leg_valid=_pad_rows(leg_valid, pad, False),
    )


def _pad_params_rows(params: SimParams, pad: int) -> SimParams:
    return SimParams(
        keep_frac=_pad_rows(params.keep_frac, pad, 1.0),
        bg_mu=_pad_rows(params.bg_mu, pad, 0.0),
        bg_sigma=_pad_rows(params.bg_sigma, pad, 0.0),
        enabled=(
            None if params.enabled is None
            else _pad_rows(params.enabled, pad, False)
        ),
    )


@functools.partial(
    jax.jit, static_argnames=("mesh", "backend", "leap", "window", "lowering")
)
def _simulate_bank_sharded(
    spec: SimSpec,  # stacked [S, ...]
    params: SimParams,  # fields [S, ...] or [S, R, ...]
    keys: jax.Array,  # [S, R, 2]
    *,
    mesh: Mesh,
    backend: Optional[str],
    leap: bool,
    window: int = 1,
    lowering: str = "banked",
) -> SimResult:
    """One SPMD bank program over a 1-D device mesh.

    The scenario axis is padded (in-trace) to a multiple of the mesh size
    with inert scenarios and partitioned with ``shard_map``; each device
    runs the same banked window loop (:func:`_banked_core`) on its local
    ``[S/D, R, ...]`` carry. Every op in the loop is row-local over the
    scenario axis and the loop condition reduces over the local shard only,
    so the program contains **zero collectives**: shards tick independently
    (a shard whose scenarios finish early stops dispatching windows), the
    per-element freeze masks and per-element RNG streams are untouched by
    the partitioning, and the result is **bit-identical** to the unsharded
    run in stable scenario order (the pad rows are sliced off before
    returning). ``check_vma=False`` because replication checking has
    nothing to verify in a collective-free program (and per-shard
    while-loop trip counts legitimately differ).
    """
    global _bank_traces
    _bank_traces += 1  # executes at trace time only

    n_dev = mesh.devices.size
    axis = mesh.axis_names[0]
    s = keys.shape[0]
    pad = -s % n_dev
    if pad:
        spec = _pad_spec_rows(spec, pad)
        params = _pad_params_rows(params, pad)
        keys = _pad_rows(keys, pad, 0)

    core = _vmap_bank_core if lowering == "vmap" else _banked_core
    fn = functools.partial(core, backend=backend, leap=leap, window=window)
    p = PartitionSpec(axis)
    out = jax.shard_map(
        fn, mesh=mesh, in_specs=(p, p, p), out_specs=p, check_vma=False
    )(spec, params, keys)
    if pad:
        out = jax.tree.map(lambda a: a[:s], out)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("backend", "leap", "window"),
    donate_argnames=("carry",),
)
def _banked_window_step(
    spec: SimSpec,
    params: SimParams,
    carry: _Carry,
    *,
    backend: Optional[str],
    leap: bool,
    window: int,
) -> _Carry:
    """One donated window step: the host-driven twin of the while-loop body.

    ``carry`` is **donated** — XLA reuses its buffers for the output carry,
    so a host-driven window loop runs with zero per-step carry allocations
    (verified warning-free on CPU; see ``tests/test_tick_window.py``). Do
    not reuse a carry after passing it here.
    """
    global _bank_traces
    _bank_traces += 1  # executes at trace time only
    return _bank_window_body(spec, params, backend, leap, window, carry)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "backend", "leap", "window"),
    donate_argnames=("carry",),
)
def _banked_window_step_sharded(
    spec: SimSpec,
    params: SimParams,
    carry: _Carry,
    *,
    mesh: Mesh,
    backend: Optional[str],
    leap: bool,
    window: int,
) -> _Carry:
    """Sharded twin of :func:`_banked_window_step`: one donated window step
    partitioned over a 1-D device mesh with ``shard_map``.

    Unlike :func:`_simulate_bank_sharded` there is no in-trace scenario
    padding — host-driven callers (the serving layer's resident slot banks)
    keep their scenario axis a multiple of the mesh size by construction,
    so the step stays a pure ``[S/D, R, ...]``-per-device window body with
    zero collectives and the same bit-exact freeze semantics as the
    unsharded step. ``check_vma=False`` for the same reason as the
    monolithic sharded program: there is nothing replicated to verify.
    """
    global _bank_traces
    _bank_traces += 1  # executes at trace time only
    if carry.t.shape[0] % mesh.devices.size:
        raise ValueError(
            f"sharded window step needs the scenario axis "
            f"({carry.t.shape[0]}) to be a multiple of the mesh size "
            f"({mesh.devices.size}); pad the bank with inert scenarios "
            "(workload.pad_bank_scenarios)"
        )
    def body(sp: SimSpec, pa: SimParams, ca: _Carry) -> _Carry:
        return _bank_window_body(sp, pa, backend, leap, window, ca)

    p = PartitionSpec(mesh.axis_names[0])
    return jax.shard_map(
        body, mesh=mesh, in_specs=(p, p, p), out_specs=p, check_vma=False
    )(spec, params, carry)


@functools.partial(jax.jit, donate_argnames=("carry",))
def _admit_bank_rows(
    spec: SimSpec,
    params: SimParams,
    keys: jax.Array,  # [S, R, 2]
    carry: _Carry,
    mask: jax.Array,  # [S] bool — rows to (re)initialize from spec/params/keys
) -> _Carry:
    """Merge freshly admitted scenario rows into a running donated carry.

    The continuous-batching admission step: ``spec``/``params``/``keys``
    are the *full* ``[S, ...]`` slot-bank views with the new scenarios
    already written into their rows; ``mask`` selects exactly those rows.
    Masked rows restart from :func:`_banked_init_carry` state while every
    other row's carry passes through untouched — bit for bit, keys
    included — so admission never perturbs in-flight scenarios and the
    call's trace signature depends only on the slot-bank shape (admitting
    1 row costs the same trace as admitting all of them: zero, after the
    first).
    """
    global _bank_traces
    _bank_traces += 1  # executes at trace time only
    fresh = _banked_init_carry(spec, params, keys)

    def merge(new: jax.Array, old: jax.Array) -> jax.Array:
        m = mask.reshape((mask.shape[0],) + (1,) * (old.ndim - 1))
        return jnp.where(m, new, old)

    return _Carry(*(merge(n, o) for n, o in zip(fresh, carry)))


@functools.partial(
    jax.jit, static_argnames=("mesh",), donate_argnames=("carry",)
)
def _admit_bank_rows_sharded(
    spec: SimSpec,
    params: SimParams,
    keys: jax.Array,  # [S, R, 2]
    carry: _Carry,
    mask: jax.Array,  # [S] bool
    *,
    mesh: Mesh,
) -> _Carry:
    """Sharded twin of :func:`_admit_bank_rows`: the masked admission merge
    partitioned over the 1-D mesh with ``shard_map``.

    The merge is row-local over the scenario axis (masked rows restart from
    init-carry state, others pass through bit for bit), so sharding it is
    collective-free — and, crucially for the serving layer's zero-retrace
    contract, the output carry keeps the *same* ``P(axis)`` sharding the
    sharded window step produces and consumes: admission never perturbs the
    carry's sharding, so the admit → step → snapshot cycle holds one stable
    set of jit cache keys under a mesh.
    """
    global _bank_traces
    _bank_traces += 1  # executes at trace time only

    def body(
        sp: SimSpec, pa: SimParams, ke: jax.Array, ca: _Carry, ma: jax.Array
    ) -> _Carry:
        fresh = _banked_init_carry(sp, pa, ke)

        def merge(new: jax.Array, old: jax.Array) -> jax.Array:
            m = ma.reshape((ma.shape[0],) + (1,) * (old.ndim - 1))
            return jnp.where(m, new, old)

        return _Carry(*(merge(n, o) for n, o in zip(fresh, ca)))

    p = PartitionSpec(mesh.axis_names[0])
    return jax.shard_map(
        body, mesh=mesh, in_specs=(p, p, p, p, p), out_specs=p,
        check_vma=False,
    )(spec, params, keys, carry, mask)


def _bank_snapshot_body(spec: SimSpec, carry: _Carry):
    live = jnp.any(_banked_live(spec, carry), axis=-1)
    return live, _banked_result(spec, carry)


@jax.jit
def _bank_snapshot(spec: SimSpec, carry: _Carry):
    """One async dispatch: ``([S] row liveness, bank SimResult view)``.

    The serving scheduler's batched-liveness surface: instead of a blocking
    per-bank ``np.asarray(any(live))`` round-trip before every step, the
    server dispatches this snapshot right after each window step and fetches
    *last* round's snapshots in one batched host sync per scheduling round.
    The carry is **not** donated — both outputs are fresh buffers (jit
    outputs never alias non-donated inputs), so the snapshot survives the
    next step's carry donation and retirement can slice result rows from it
    without ever waiting on an in-flight window. Frozen rows make the
    one-round-stale view exact: a finished row's carry never changes again
    (CONTRACTS.md §7), so its result slice is bitwise identical in every
    later version.
    """
    global _bank_traces
    _bank_traces += 1  # executes at trace time only
    return _bank_snapshot_body(spec, carry)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _bank_snapshot_sharded(spec: SimSpec, carry: _Carry, *, mesh: Mesh):
    """Sharded twin of :func:`_bank_snapshot` (row-local, collective-free;
    ``check_vma=False`` as for the other sharded bank programs)."""
    global _bank_traces
    _bank_traces += 1  # executes at trace time only
    p = PartitionSpec(mesh.axis_names[0])
    return jax.shard_map(
        _bank_snapshot_body, mesh=mesh, in_specs=(p, p), out_specs=(p, p),
        check_vma=False,
    )(spec, carry)


def _shard_carry(carry: _Carry, mesh: Mesh) -> _Carry:
    """Place a (freshly initialized) carry with the ``P(axis)`` sharding the
    sharded window step emits, so the very first admit/step under a mesh
    already sees the steady-state input sharding — one trace per program,
    no init-carry → stepped-carry sharding transition to warm through."""
    sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), carry)


class BankCheckpoint(NamedTuple):
    """Resumable snapshot of a host-driven banked run (see
    :func:`simulate_bank_stepped`). ``carry`` holds host-side (numpy) copies
    of the ``[S, R, ...]`` window-loop carry, so a checkpoint survives the
    donation of the live device carry into the next step and serializes
    with ``np.savez`` (``Fleet.save_checkpoint`` wraps exactly that)."""

    windows_done: int
    window: int
    carry: _Carry


def _snapshot_carry(carry: _Carry) -> _Carry:
    return _Carry(*(np.asarray(a) for a in carry))


def _validate_resume_carry(carry: _Carry, spec: SimSpec, keys) -> None:
    """Reject a resume carry whose shapes do not match the target bank.

    A checkpoint taken against one bank cannot continue another: differing
    pad shapes (legs/links), scenario counts, or replica counts would
    either crash deep inside the jitted window step or — worse, for a
    same-rank mismatch — silently simulate garbage. Checked loudly here,
    at the resume boundary, where the caller can still see which fleet and
    checkpoint disagree.
    """
    S, R = np.shape(keys)[0], np.shape(keys)[1]
    T = spec.size_mb.shape[-1]
    L = spec.bandwidth.shape[-1]
    expect = {
        "t": (S, R),
        "remaining": (S, R, T),
        "done": (S, R, T),
        "started": (S, R, T),
        "t_start": (S, R, T),
        "t_end": (S, R, T),
        "conth": (S, R, T),
        "conpr": (S, R, T),
        "bg": (S, R, L),
        "key": (S, R, 2),
    }
    for field, want in expect.items():
        got = tuple(np.shape(getattr(carry, field)))
        if got != want:
            raise ValueError(
                f"checkpoint carry field {field!r} has shape {got} but the "
                f"target bank expects {want} (scenarios={S}, replicas={R}, "
                f"pad_legs={T}, pad_links={L}) — the checkpoint was taken "
                "against a bank with different pads/scenarios/replicas and "
                "cannot resume this one"
            )


def simulate_bank_stepped(
    bank: Union[ScenarioBank, SimSpec],
    params: SimParams,
    keys: jax.Array,  # [S, R, 2]
    *,
    backend: Optional[str] = None,
    leap: bool = False,
    window: Optional[int] = None,
    sync_every: Optional[int] = 8,
    checkpoint_every: Optional[int] = None,
    on_checkpoint: Optional[Callable[[BankCheckpoint], None]] = None,
    resume: Optional[BankCheckpoint] = None,
) -> SimResult:
    """Banked simulation as a host-driven loop of donated window steps.

    Runs up to ``ceil(max_ticks / window)`` dispatches of
    :func:`_banked_window_step` instead of one ``lax.while_loop`` program:
    the trip count is bounded statically and the carry buffers are donated
    into every step, so the loop state is updated in place. Windows past an
    element's completion are frozen no-ops, which makes the result
    **bit-identical** to ``simulate_bank(..., lowering="banked")`` at the
    same ``window``. Every ``sync_every`` windows the host checks whether
    any element is still live and stops early — ``max_ticks`` is a safe
    *upper bound*, often far above the realized length, and without the
    check every post-completion window would still execute its masked
    no-op math. The check is a device sync, so it is amortized rather than
    per-step (``sync_every=None`` disables it for fully-async pipelines).

    Long runs can snapshot and resume: every ``checkpoint_every`` windows,
    ``on_checkpoint(BankCheckpoint(...))`` receives a host-side copy of the
    carry (safe across the donation of the live buffers), and passing such
    a snapshot back as ``resume=`` re-uploads the carry and continues from
    the recorded window — bit-identically, because every window is a pure
    function of the carry. ``Fleet.save_checkpoint`` / ``load_checkpoint``
    give the snapshots a ``Fleet.save``-compatible on-disk form.

    This is the introspectable/streaming execution mode — callers can stop
    early, checkpoint the carry, or interleave host work between windows;
    the fused while-loop program remains the faster fire-and-forget path.
    """
    spec = bank_spec(bank) if isinstance(bank, ScenarioBank) else bank
    window = _resolve_window(window, leap)
    bound = int(np.max(np.asarray(bank.max_ticks)))
    # never scan far past the bank's longest simulation in one window —
    # the same pow2-quantized cap as simulate_bank (keeps stepped results
    # comparable with the while-loop path at the same resolved window)
    window = _clamp_window(window, bound)
    start = 0
    if resume is not None:
        if int(resume.window) != window:
            raise ValueError(
                f"checkpoint was taken at window={resume.window}, cannot "
                f"resume at window={window} (windows_done would not align)"
            )
        start = int(resume.windows_done)
        _validate_resume_carry(resume.carry, spec, keys)
        carry = _Carry(*(jnp.asarray(a) for a in resume.carry))
    else:
        # the carry embeds the keys and is donated into the first step —
        # copy so the caller's keys buffer survives
        carry = _banked_init_carry(spec, params, jnp.array(keys, copy=True))
    for i in range(start, max(1, -(-bound // window))):
        carry = _banked_window_step(
            spec, params, carry, backend=backend, leap=leap, window=window
        )
        if (
            checkpoint_every is not None
            and on_checkpoint is not None
            and (i + 1) % checkpoint_every == 0
        ):
            on_checkpoint(
                BankCheckpoint(
                    windows_done=i + 1, window=window,
                    carry=_snapshot_carry(carry),
                )
            )
        if (
            sync_every is not None
            and (i + 1) % sync_every == 0
            and not bool(jnp.any(_banked_live(spec, carry)))
        ):
            break
    return _banked_result(spec, carry)


_VALID_LOWERINGS = ("auto", "banked", "vmap")

# auto-tuned fused-window defaults per backend platform, (tick, leap).
# On TPU every window is one fused-kernel launch, so K amortizes the
# launch + HBM carry round-trip + cond evaluation K-fold (VMEM window
# block scales with K — see grid_tick_bank_fused_pallas). Off-TPU the
# window lowers to a lax.scan that does not shorten the op chain — it only
# adds the tail window's masked no-op ticks — and the
# ``benchmarks/bank_throughput.py`` window sweep shows K=1 winning on the
# CPU bench host for both modes, so the off-TPU auto default stays
# per-tick. (The CPU wins of the window rework come from the restructured
# body itself: aliveness folded into the update masks instead of a
# 10-array carry select, index-gather one-hot contractions, and
# sigma=0 background-resample events dropped from the leap schedule.)
# Leap windows hold K *events*, each already covering many ticks, so their
# K is kept smaller to bound tail waste.
_WINDOW_DEFAULTS = {"tpu": (32, 16)}
_WINDOW_DEFAULT_OTHER = (1, 1)

# persisted per-backend window sweep table: measured best-K per platform,
# written by benchmarks/bank_throughput.py's window_sweep section (full,
# non-smoke runs) via record_window_sweep and committed alongside the code.
# The hardcoded pairs above remain the fallback for platforms the sweep has
# never run on.
#
# The window interacts with the bucket *work-cost model* (see
# workload.compile_bank): a scenario's packing cost is
# ``units * (_COST_STEP_BASE + pow2ceil(n_legs))`` where ``units`` is
# ``LegTable.leap_event_estimate()`` under the leap engine and
# ``ceil(expected_ticks / resolved window)`` under tick stepping — the
# tick-mode unit count reads this table through ``_resolve_window(None,
# False)``, so retuning a backend's window also rebalances cost-packed
# buckets on the next compile. Knobs: ``compile_bank(bucket_packing=
# "cost"|"count", bucket_slack=..., bucket_cost_leap=..., bucket_counts=
# ...)``; the model constants live next to the formula in
# ``core/workload.py`` (_COST_STEP_BASE, _COST_DISPATCH_BASE,
# _DEFAULT_BUCKET_SLACK).
_WINDOW_TABLE_PATH = os.path.join(os.path.dirname(__file__), "window_table.json")


def _window_table_path(path: Optional[str] = None) -> str:
    return (
        path
        # repro: allow[trace-purity] -- host-side: the public simulate* wrappers resolve window=None before entering jit; traced callers pass resolved ints
        or os.environ.get("REPRO_WINDOW_TABLE", "").strip()
        or _WINDOW_TABLE_PATH
    )


@functools.lru_cache(maxsize=None)
def _load_window_table(path: str) -> dict:
    try:
        # repro: allow[trace-purity] -- host-side only: window=None is resolved in the unjitted public wrappers (see _simulate's contract)
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return {}
    table = {}
    for plat, entry in raw.items():
        if isinstance(entry, dict):
            table[str(plat)] = {
                k: int(v) for k, v in entry.items()
                if k in ("tick", "leap") and int(v) >= 1
            }
    return table


def default_tick_window(leap: bool = False) -> int:
    """The auto-tuned fused-window size for this process's backend (what
    ``window=None`` resolves to, absent ``REPRO_TICK_WINDOW``).

    Resolution order: the persisted per-backend sweep table
    (``src/repro/core/window_table.json``, measured by the bench's
    ``window_sweep`` and overridable via ``REPRO_WINDOW_TABLE=path``), then
    the hardcoded per-platform fallback. The committed table pins CPU to
    K=1 — the sweep shows fused windows only amortize real kernel-launch
    cost, which XLA:CPU does not pay (``fused_vs_per_tick_speedup`` ~1.0).
    """
    plat = ops._platform()
    entry = _load_window_table(_window_table_path()).get(plat, {})
    key = "leap" if leap else "tick"
    if key in entry:
        return entry[key]
    pair = _WINDOW_DEFAULTS.get(plat, _WINDOW_DEFAULT_OTHER)
    return pair[1] if leap else pair[0]


def record_window_sweep(
    platform: str,
    *,
    tick: Optional[int] = None,
    leap: Optional[int] = None,
    path: Optional[str] = None,
) -> str:
    """Persist measured best window sizes for ``platform`` into the sweep
    table consulted by :func:`default_tick_window` (read-modify-write; other
    platforms' entries survive). Returns the table path written."""
    p = _window_table_path(path)
    try:
        with open(p) as f:
            table = json.load(f)
        if not isinstance(table, dict):
            table = {}
    except (OSError, ValueError):
        table = {}
    entry = table.setdefault(platform, {})
    if tick is not None:
        entry["tick"] = max(1, int(tick))
    if leap is not None:
        entry["leap"] = max(1, int(leap))
    with open(p, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
        f.write("\n")
    _load_window_table.cache_clear()
    return p


def _resolve_window(window: Optional[int], leap: bool = False) -> int:
    """``None`` -> ``REPRO_TICK_WINDOW`` or the per-backend auto default;
    explicit values are validated (>= 1)."""
    if window is None:
        # repro: allow[trace-purity] -- host-side only: traced callers always pass a resolved int window, the public wrappers resolve None before jit
        env = os.environ.get("REPRO_TICK_WINDOW", "").strip()
        if not env:
            return default_tick_window(leap)
        window = env
    w = int(window)
    if w < 1:
        raise ValueError(f"tick window must be >= 1: {window!r}")
    return w


def _clamp_window(window: int, tick_bound: int) -> int:
    """Cap a window at a bank/bucket tick bound, **quantized to the next
    power of two** of the bound. The window is a jit-static argument, so a
    raw ``min(window, bound)`` would bake content-dependent tick bounds
    into the trace key and retrace fleets/chunks that share pad shapes but
    differ in bounds below the window — eroding the pinned zero-retrace
    contracts. Quantizing keeps the cap (a bucket bounded at 40 ticks never
    pays a 64-tick window... it pays at most its bound's pow2 bracket) while
    collapsing nearby bounds onto one static value; bounds at or above the
    window resolve to the window itself, the common case."""
    cap = 1
    while cap < tick_bound:
        cap *= 2
    return max(1, min(window, cap))


def _resolve_lowering(lowering: Optional[str]) -> str:
    lowering = lowering or os.environ.get("REPRO_BANK_LOWERING", "auto")
    if lowering not in _VALID_LOWERINGS:
        raise ValueError(
            f"bank lowering must be one of {_VALID_LOWERINGS}: {lowering!r}"
        )
    if lowering == "auto":
        # the banked window body is the fast path everywhere since the
        # fused-window rework: on TPU it drives the bank-tiled fused kernel
        # (carry resident in VMEM), off-TPU its index-based tick replaces
        # the tiny one-hot matmuls with gathers — measurably ahead of the
        # vmap-of-simulate program on CPU too (BENCH_bank.json:
        # banked_vs_vmap_speedup). The vmap program remains as the
        # cross-check lowering (REPRO_BANK_LOWERING=vmap).
        return "banked"
    return lowering


def _dispatch_bank(
    spec: SimSpec,
    params: SimParams,
    keys: jax.Array,
    *,
    backend: Optional[str],
    leap: bool,
    lowering: Optional[str],
    window: int = 1,
    mesh: Optional[Mesh] = None,
) -> SimResult:
    if keys.ndim != 3:
        raise ValueError(f"keys must be [n_scenarios, n_replicas, 2]: {keys.shape}")
    if mesh is not None:
        return _simulate_bank_sharded(
            spec, params, keys, mesh=mesh, backend=backend, leap=leap,
            window=window, lowering=_resolve_lowering(lowering),
        )
    if _resolve_lowering(lowering) == "vmap":
        return _simulate_bank(
            spec, params, keys, backend=backend, leap=leap, window=window
        )
    return _simulate_bank_banked(
        spec, params, keys, backend=backend, leap=leap, window=window
    )


# Cost-packed banks split long-tail scenarios into singleton buckets at
# native pads (see compile_bank). A 1-scenario program leaves the engine's
# scenario axis a single row, so on tiled backends its fused kernel runs
# nearly empty. When the replica count allows, the bucketed dispatcher
# *widens* such buckets across the replica axis — [1, R] elements reshaped
# to [fold, R/fold] with the spec broadcast over the folded scenario rows —
# which is bitwise inert: the engine is element-independent (per-element
# freeze masks and per-element RNG), and the while condition ranges over the
# same element set either way, so iteration counts and per-element
# trajectories are unchanged; only the tile occupancy differs. The fold is
# capped so the broadcast spec stays small.
_SINGLETON_FOLD_MAX = 8


def _replica_fold(n_replicas: int) -> int:
    """Largest power of two <= _SINGLETON_FOLD_MAX dividing n_replicas."""
    fold = 1
    while (
        fold * 2 <= _SINGLETON_FOLD_MAX and n_replicas % (fold * 2) == 0
    ):
        fold *= 2
    return fold


@functools.partial(
    jax.jit,
    static_argnames=(
        "bucket_legs", "bucket_links", "pad_legs", "backend", "leap",
        "lowering", "windows", "mesh",
    ),
)
def _simulate_bank_bucketed_impl(
    specs: Tuple[SimSpec, ...],  # per-bucket stacked specs
    params: SimParams,  # bank-wide fields in original scenario order
    keys: jax.Array,  # [N, R, 2]
    idx: Tuple[jax.Array, ...],  # per-bucket original scenario ids
    *,
    bucket_legs: Tuple[int, ...],
    bucket_links: Tuple[int, ...],
    pad_legs: int,
    backend: Optional[str],
    leap: bool,
    lowering: str,
    windows: Tuple[int, ...] = (),
    mesh: Optional[Mesh] = None,
) -> SimResult:
    """One fused program over every sub-bank: gather the bucket's params
    rows, simulate, scatter into the caller's ``[N, R]`` order. Fusing keeps
    warm dispatch cost at a single call (the eager per-bucket slice/scatter
    ops would otherwise dominate the warm wall on small fleets); each inner
    banked program still (re)uses its own per-shape trace/counter.

    Buckets compiled with shard padding (``compile_bank(shards=k)``) carry
    more spec rows than real ``scenario_ids``; the gather index is extended
    by repeating the last real id (the pad rows are never live, so their
    params/keys are irrelevant) and the pad rows are dropped again before
    the scatter — the caller-visible ``[N, R]`` order never sees them.
    Under ``mesh`` each bucket's program runs sharded over the scenario
    axis (:func:`_simulate_bank_sharded`), so the fused windows and the
    scatter-back stay device-local per bucket."""
    n, r = keys.shape[:2]
    if mesh is not None:
        sim = functools.partial(
            _simulate_bank_sharded, mesh=mesh, lowering=lowering
        )
    else:
        sim = _simulate_bank if lowering == "vmap" else _simulate_bank_banked
    out = SimResult(
        transfer_time=jnp.zeros((n, r, pad_legs), jnp.float32),
        size_mb=jnp.zeros((n, r, pad_legs), jnp.float32),
        conth_mb=jnp.zeros((n, r, pad_legs), jnp.float32),
        conpr_mb=jnp.zeros((n, r, pad_legs), jnp.float32),
        done=jnp.ones((n, r, pad_legs), bool),  # padding is born done
        ticks=jnp.zeros((n, r), jnp.int32),
        profile=jnp.full((n, r, pad_legs), PAD_PROFILE, jnp.int32),
        start_tick=jnp.zeros((n, r, pad_legs), jnp.float32),
    )
    if not windows:
        windows = (1,) * len(specs)
    for spec_b, ids, t_b, l_b, w_b in zip(
        specs, idx, bucket_legs, bucket_links, windows
    ):
        n_real = ids.shape[0]
        s_b = spec_b.size_mb.shape[0]
        gid = ids
        if s_b != n_real:
            # shard-padded bucket: extend the gather with the last real id
            # (pad rows are born done with max_ticks=0 — never live)
            gid = jnp.concatenate(
                [ids, jnp.broadcast_to(ids[-1:], (s_b - n_real,))]
            )
        legs = lambda f: None if f is None else f[gid][..., :t_b]
        links = lambda f: None if f is None else f[gid][..., :l_b]
        sub_params = SimParams(
            keep_frac=legs(params.keep_frac),
            bg_mu=links(params.bg_mu),
            bg_sigma=links(params.bg_sigma),
            enabled=legs(params.enabled),
        )
        # singleton long-tail bucket: widen across the replica axis so the
        # fused kernel fills its scenario tiles (bitwise inert, see
        # _replica_fold). Per-replica (ndim-3) param leaves opt out — their
        # replica axis cannot be folded without reshaping caller data.
        fold = 1
        if (
            mesh is None
            and s_b == 1
            and n_real == 1
            and r > 1
            and all(
                a is None or a.ndim == 2
                for a in (
                    params.keep_frac, params.bg_mu,
                    params.bg_sigma, params.enabled,
                )
            )
        ):
            fold = _replica_fold(r)
        if fold > 1:
            widen = lambda a: jnp.broadcast_to(a, (fold,) + a.shape[1:])
            res = sim(
                jax.tree.map(widen, spec_b),
                jax.tree.map(widen, sub_params),
                keys[gid].reshape(fold, r // fold, 2),
                backend=backend, leap=leap, window=w_b,
            )
            res = jax.tree.map(
                lambda a: a.reshape((1, r) + a.shape[2:]), res
            )
        else:
            res = sim(spec_b, sub_params, keys[gid], backend=backend,
                      leap=leap, window=w_b)
        if s_b != n_real:
            res = jax.tree.map(lambda a: a[:n_real], res)
        out = SimResult(
            transfer_time=out.transfer_time.at[ids, :, :t_b].set(res.transfer_time),
            size_mb=out.size_mb.at[ids, :, :t_b].set(res.size_mb),
            conth_mb=out.conth_mb.at[ids, :, :t_b].set(res.conth_mb),
            conpr_mb=out.conpr_mb.at[ids, :, :t_b].set(res.conpr_mb),
            done=out.done.at[ids, :, :t_b].set(res.done),
            ticks=out.ticks.at[ids].set(res.ticks),
            profile=out.profile.at[ids, :, :t_b].set(res.profile),
            start_tick=out.start_tick.at[ids, :, :t_b].set(res.start_tick),
        )
    return out


def _simulate_bank_bucketed(
    bank: BucketedBank,
    params: SimParams,
    keys: jax.Array,  # [N, R, 2]
    *,
    backend: Optional[str],
    leap: bool,
    lowering: Optional[str],
    window: int = 1,
    mesh: Optional[Mesh] = None,
) -> SimResult:
    """Run each max_ticks-bucketed sub-bank under its own cached trace and
    scatter the per-bucket results back into the caller's ``[N, R]`` order
    (global pads; the tail beyond a bucket's pad reports inert padding).
    The fused window is resolved **per bucket** against its realized tick
    bound (pow2-quantized; see :func:`_clamp_window`) — a bucket bounded at
    5 ticks never pays a 32-tick window, and the quantization keeps the
    static window from retracing on content-dependent bounds."""
    if keys.ndim != 3:
        raise ValueError(f"keys must be [n_scenarios, n_replicas, 2]: {keys.shape}")
    specs = tuple(bank_spec(b.bank) for b in bank.buckets)
    idx = getattr(bank, "_idx_cache", None)
    if idx is None:
        idx = tuple(jnp.asarray(b.scenario_ids) for b in bank.buckets)
        if not any(isinstance(i, jax.core.Tracer) for i in idx):
            bank._idx_cache = idx
    return _simulate_bank_bucketed_impl(
        specs, params, keys, idx,
        bucket_legs=tuple(b.bank.pad_legs for b in bank.buckets),
        bucket_links=tuple(b.bank.pad_links for b in bank.buckets),
        pad_legs=bank.pad_legs,
        backend=backend,
        leap=leap,
        lowering=_resolve_lowering(lowering),
        windows=tuple(
            _clamp_window(window, int(np.max(b.bank.max_ticks)))
            for b in bank.buckets
        ),
        mesh=mesh,
    )


def _sanitizers_wanted() -> bool:
    """Cheap gate for the REPRO_DEBUG / nan_guard sanitizer hook: avoids
    importing ``repro.analysis`` on the hot path unless the env var is set
    or a ``nan_guard`` scope already pulled the module in."""
    if os.environ.get("REPRO_DEBUG", "").strip().lower() in (
        "1",
        "true",
        "on",
        "yes",
    ):
        return True
    mod = sys.modules.get("repro.analysis.sanitize")
    return mod is not None and mod.result_checks_enabled()


def simulate_bank(
    bank: Union[ScenarioBank, SimSpec],
    params: SimParams,
    keys: jax.Array,  # [N, R, 2] PRNG keys (R replicas per scenario)
    *,
    backend: Optional[str] = None,
    leap: bool = False,
    lowering: Optional[str] = None,
    bucketed: bool = True,
    window: Optional[int] = None,
    mesh: Union[None, Mesh, int, Sequence] = None,
) -> SimResult:
    """Simulate every scenario of the bank x ``R`` stochastic replicas.

    One jit trace serves every bank of the same padded shape — scenario
    diversity costs zero retraces. Fields of the result carry ``[N, R]``
    leading dims; padded legs report ``done=True`` with zero transfer (mask
    with ``bank.leg_valid`` downstream). ``params`` fields may be bank-wide
    (``[N, ...]``) or per-replica (``[N, R, ...]``).

    ``lowering`` picks the jit program: ``"banked"`` runs the manual
    ``[S, R, ...]`` tick loop on ``ops.grid_tick_bank`` — the bank-tiled TPU
    kernel — while ``"vmap"`` keeps the original vmap-of-``simulate``
    program. ``"auto"`` (default; override with ``REPRO_BANK_LOWERING``)
    resolves to ``"banked"`` on TPU and ``"vmap"`` elsewhere. Both are
    element-for-element equivalent (see ``tests/test_bank_buckets.py``).

    A :class:`~repro.core.workload.BucketedBank` (from ``compile_bank(...,
    n_buckets=k)``) runs one trace per distinct sub-bank shape, each
    stopping at its own bucket's tick bound, and the results are scattered
    back into the
    caller's original ``[N, R]`` scenario order — same contract, warm
    throughput no longer gated by the slowest scenario of the whole fleet.
    Pass ``bucketed=False`` to force the monolithic single-trace path.

    ``window=K`` fuses ``K`` ticks (``K`` event leaps under ``leap``) into
    every loop iteration of whichever lowering runs — one
    ``grid_tick_bank_fused`` kernel launch per window on the banked TPU
    path, an inner ``lax.scan`` elsewhere — with results **bit-identical**
    to per-tick execution for every ``K`` (the windowed freeze mask
    replicates the loop condition tick for tick, RNG streams included).
    ``None`` resolves ``REPRO_TICK_WINDOW`` or the per-backend auto default
    (:func:`default_tick_window`); bucketed banks additionally cap each
    bucket's window at its own tick bound's power-of-two bracket (the
    quantization keeps the jit-static window independent of exact
    content-dependent bounds, preserving the zero-retrace contracts).

    The flattened ``N*R`` batch is embarrassingly parallel. ``mesh``
    (a 1-D :class:`jax.sharding.Mesh`, a device count, or a device
    sequence; see :func:`resolve_mesh`) runs the whole bank as **one SPMD
    program** ``shard_map``-partitioned over the scenario axis: the
    scenario count is padded to a multiple of the mesh size with inert
    scenarios (the compile-time twin is ``workload.compile_bank(...,
    shards=k)``), each device loops over its local shard under its own
    early-exit condition, and — the program being collective-free — the
    results are **bit-identical** to the unsharded run in stable scenario
    order. Bucketed banks shard each bucket's program over the same mesh,
    keeping the fused windows and the scatter-back device-local per bucket
    (see ``tests/test_multidevice.py``).
    """
    w = _resolve_window(window, leap)
    mesh = resolve_mesh(mesh)
    if isinstance(bank, ScenarioBank):
        # never scan far past the fleet's longest simulation in one window
        # (pow2-quantized so the static window doesn't retrace on
        # content-dependent bounds; see _clamp_window)
        w = _clamp_window(w, int(np.max(np.asarray(bank.max_ticks))))
    if bucketed and isinstance(bank, BucketedBank):
        result = _simulate_bank_bucketed(
            bank, params, keys, backend=backend, leap=leap, lowering=lowering,
            window=w, mesh=mesh,
        )
    else:
        spec = bank_spec(bank) if isinstance(bank, ScenarioBank) else bank
        result = _dispatch_bank(
            spec, params, keys, backend=backend, leap=leap, lowering=lowering,
            window=w, mesh=mesh,
        )
    if _sanitizers_wanted():
        from repro.analysis import sanitize as _sanitize

        return _sanitize.sanitize_result_hook(
            result,
            bank if isinstance(bank, ScenarioBank) else None,
            where="simulate_bank",
        )
    return result


def make_params(
    table: LegTable,
    *,
    overhead: Optional[float] = None,
    bg_mu: Optional[float] = None,
    bg_sigma: Optional[float] = None,
    protocol: Optional[str] = None,
) -> SimParams:
    """Build :class:`SimParams` from a leg table, optionally overriding the
    overhead of one protocol (or all legs) and the background-load moments of
    every link — the knobs the paper calibrates (theta)."""
    keep = table.keep_frac.astype(np.float32).copy()
    if overhead is not None:
        if protocol is None:
            keep[:] = 1.0 - overhead
        else:
            pid = table.protocol_names.index(protocol)
            keep[table.protocol_id == pid] = 1.0 - overhead
    links = table.links
    mu = links.bg_mu if bg_mu is None else np.full_like(links.bg_mu, bg_mu)
    sigma = (
        links.bg_sigma if bg_sigma is None else np.full_like(links.bg_sigma, bg_sigma)
    )
    return SimParams(
        keep_frac=jnp.asarray(keep),
        bg_mu=jnp.asarray(mu),
        bg_sigma=jnp.asarray(sigma),
    )
