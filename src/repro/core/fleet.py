"""``repro.Fleet``: one session façade for compile -> simulate -> calibrate.

The paper's promise — exploring many data-access profiles over heterogeneous
WLCG-like workloads — previously required wiring four layers by hand:
``workload.compile_bank`` (padding/bucketing knobs), ``engine.simulate_bank``
(lowering/leap dispatch), the calibration sweeps, and the optimizer. A
:class:`Fleet` owns that lifecycle behind one object:

- **compile** — :meth:`Fleet.from_pairs` / :meth:`Fleet.from_scenarios` /
  :meth:`Fleet.from_table` compile (and memoize, via the fleet-level compile
  cache) a :class:`~repro.core.workload.ScenarioBank` or
  :class:`~repro.core.workload.BucketedBank`;
- **simulate** — :meth:`Fleet.run` dispatches to ``engine.simulate_bank``
  with the fleet's lowering/leap/backend defaults and returns results in
  stable scenario order; :meth:`Fleet.stream` pipelines an *iterator* of
  ``(grid, campaign)`` pairs through fixed-pad chunk banks that all reuse
  the first chunk's jit trace — campaigns larger than memory cost zero
  retraces after chunk one;
- **persist** — :meth:`Fleet.save` / :meth:`Fleet.load` round-trip the
  compiled bank arrays plus pad/bucket metadata (npz + json) for
  cross-process reuse;
- **calibrate** — :meth:`Fleet.presimulate` / :meth:`Fleet.calibrate` /
  :meth:`Fleet.validate` run the likelihood-free pipeline over the fleet's
  scenario variants; ``calibrate(amortized=True)`` conditions the ratio net
  on :meth:`Fleet.summary_features` and returns an
  :class:`~repro.core.calibration.AmortizedPosterior` (per-scenario theta*
  from one trained net, no retraining); :meth:`Fleet.coefficients` is the
  Eq.-1 summary statistic of any run.

The compile cache is registered with
:func:`repro.core.engine.register_cache_clear_hook`, so
``engine.reset_bank_trace_count(clear_caches=True)`` drops it together with
the jit caches — trace-count assertions stay order-independent.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import threading
import zipfile
from typing import (
    Any,
    Callable,
    Dict,
    Hashable,
    Iterable,
    Iterator,
    List,
    NamedTuple,
    Optional,
    Sequence,
    TYPE_CHECKING,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
import numpy as np

from repro.core import calibration as calibration_lib
from repro.core import engine as engine_lib
from repro.core import workload
from repro.core.engine import SimParams, SimResult, make_bank_params, simulate_bank
from repro.core.scenarios import sample_scenarios
from repro.core.topology import Grid
from repro.core.workload import (
    BankBucket,
    BucketedBank,
    Campaign,
    LegTable,
    ScenarioBank,
    bank_from_tables,
    compile_bank,
    compile_campaign,
    pad_bank_scenarios,
    subset_bank,
    summary_features,
)
from repro.utils import spans

if TYPE_CHECKING:
    from repro.core.residency import ResidentBank

__all__ = ["Fleet", "StreamChunk", "clear_compile_cache"]

# every ScenarioBank dataclass field persisted/loaded as a dense array
_ARRAY_FIELDS = tuple(
    f.name
    for f in dataclasses.fields(ScenarioBank)
    if f.name not in ("protocol_names", "names", "tables")
)

# fleet-level compile cache: compiled banks are immutable and expensive
# (python-loop compilation of every campaign), so repeated façade
# constructions with the same recipe reuse the artifact. Values are banks
# (or ``(keepalive, bank)`` tuples for identity-keyed entries), never Fleet
# instances — run options stay per-façade. Bounded FIFO: long-lived
# processes that keep minting recipes (e.g. a fresh super-table per
# optimizer call) must not retain every bank ever compiled.
_COMPILE_CACHE_MAX = 64
_compile_cache: dict = {}

# every mutation (and compound lookup-then-insert) of _compile_cache holds
# this lock: the Fleet.stream(prefetch=) background thread builds chunk
# banks through the same cache the consumer thread reads, and the FIFO
# eviction in _cache_put is a compound operation that must stay atomic.
# The discipline is machine-checked by repro.analysis.lock_discipline().
_COMPILE_CACHE_LOCK = threading.RLock()


def _cache_get(key: Hashable) -> Any:
    with _COMPILE_CACHE_LOCK:
        return _compile_cache.get(key)


def _cache_put(key: Hashable, value: Any) -> None:
    with _COMPILE_CACHE_LOCK:
        _compile_cache.pop(key, None)  # re-insert at the back
        _compile_cache[key] = value
        while len(_compile_cache) > _COMPILE_CACHE_MAX:
            _compile_cache.pop(next(iter(_compile_cache)))


def clear_compile_cache() -> None:
    """Drop every memoized compiled bank (run automatically by
    ``engine.reset_bank_trace_count(clear_caches=True)``)."""
    with _COMPILE_CACHE_LOCK:
        _compile_cache.clear()


engine_lib.register_cache_clear_hook(clear_compile_cache)


class StreamChunk(NamedTuple):
    """One yielded chunk of :meth:`Fleet.stream`: the chunk's compiled bank,
    its simulation result (sliced to the chunk's real scenarios), and their
    names."""

    bank: ScenarioBank
    result: SimResult
    names: List[str]


PairsLike = Sequence[Tuple[Grid, Campaign]]

# what `devices=` accepts everywhere: nothing, a device count, an explicit
# device sequence, or an existing 1-D mesh (see engine.resolve_mesh)
DevicesLike = Union[None, int, Sequence[Any], Mesh]

# what `params_or_theta=` accepts (see Fleet._resolve_params): base params,
# explicit SimParams, a theta [3] vector / per-scenario [N, 3] matrix, or a
# callable rebuilding params for a (chunk) bank
ParamsLike = Union[
    None,
    SimParams,
    jax.Array,
    np.ndarray,
    Sequence[float],
    Callable[[ScenarioBank], SimParams],
]

# a max_ticks spec: None (safe upper bound), a uniform cap, or per-scenario
TicksLike = Union[None, int, Sequence[int], np.ndarray]


class Fleet:
    """A compiled scenario fleet with its run policy
    (lowering/leap/backend/window).

    Construct via :meth:`from_pairs` (explicit ``(grid, campaign)`` pairs),
    :meth:`from_scenarios` (the generator registry), :meth:`from_table`
    (an already-compiled :class:`LegTable`), :meth:`load` (persisted bank),
    or wrap an existing bank: ``Fleet(bank)``.
    """

    def __init__(
        self,
        bank: ScenarioBank,
        *,
        lowering: Optional[str] = None,
        leap: bool = False,
        backend: Optional[str] = None,
        window: Optional[int] = None,
        devices: DevicesLike = None,
    ) -> None:
        if not isinstance(bank, ScenarioBank):
            raise TypeError(f"Fleet wraps a compiled ScenarioBank, got {type(bank)!r}")
        if engine_lib._sanitizers_wanted():
            from repro.analysis import sanitize as _sanitize

            _sanitize.check_bank_once(bank)
        self.bank = bank
        self.lowering = lowering
        self.leap = leap
        self.backend = backend
        self.window = window
        # None | device count | device sequence | 1-D Mesh — resolved (and
        # memoized; jax.devices() is only consulted once) on first sharded run
        self.devices = devices
        self._mesh: Optional[Mesh] = None
        self._base_params: Optional[SimParams] = None
        self._mappers: Dict[str, Callable[[jax.Array], SimParams]] = {}

    def _resolve_mesh(self, devices: DevicesLike = None) -> Optional[Mesh]:
        """The fleet's execution mesh (``engine.resolve_mesh``), memoized for
        the fleet default so every :meth:`run` reuses one Mesh object (equal
        meshes hash equal anyway — the jit cache would not retrace — but the
        memo also skips re-walking ``jax.devices()``)."""
        if devices is not None:
            return engine_lib.resolve_mesh(devices)
        if self.devices is not None and self._mesh is None:
            self._mesh = engine_lib.resolve_mesh(self.devices)
        return self._mesh

    # -- compile ------------------------------------------------------------

    @classmethod
    def from_pairs(
        cls,
        pairs: Union[PairsLike, Callable[[], PairsLike]],
        *,
        max_ticks: TicksLike = None,
        n_buckets: int = 1,
        bucket_packing: str = "cost",
        bucket_slack: Optional[float] = None,
        bucket_counts: Optional[Sequence[int]] = None,
        pad_floors: Optional[Tuple[int, int, int]] = None,
        pad_multiple: int = 1,
        bucket_pad_floors: Optional[Sequence[Tuple[int, int, int]]] = None,
        cache_key: Optional[Any] = None,
        lowering: Optional[str] = None,
        leap: bool = False,
        backend: Optional[str] = None,
        window: Optional[int] = None,
        devices=None,
    ) -> "Fleet":
        """Compile ``(grid, campaign)`` pairs into a fleet.

        ``pad_floors = (legs, procs, links)`` sets the global pad floors
        (:func:`~repro.core.workload.compile_bank` ``pad_*``), the knob that
        lets differently-sized fleets share one jit trace; ``n_buckets`` /
        ``bucket_packing`` / ``bucket_slack`` / ``bucket_counts`` /
        ``bucket_pad_floors`` select and shape the bucketed warm path (see
        :func:`~repro.core.workload.compile_bank`'s bucketing contract —
        the fleet's ``leap`` flag doubles as the cost model's
        ``bucket_cost_leap``, so a leap fleet packs by event estimates and
        a tick fleet by window counts). A
        hashable ``cache_key`` memoizes the compiled bank in the fleet-level
        compile cache: it must uniquely identify the *pair set* (the pairs
        themselves are unhashable); every compile knob is folded into the
        cache key automatically, so one ``cache_key`` reused with different
        ticks/pads/bucketing recompiles instead of aliasing. ``pairs`` may
        be a zero-arg callable producing the pairs — it is only invoked on
        a cache miss, keeping the memoized hit path free of generation cost
        (how :meth:`from_scenarios` defers its sampling).

        ``devices`` (a device count, device sequence, or 1-D mesh) makes
        :meth:`run` execute the bank as one SPMD program sharded over the
        scenario axis; bucketed fleets are compiled with
        ``compile_bank(shards=n_devices)`` so each bucket's scenario count
        divides the mesh (inert shard padding — results stay bitwise those
        of the unsharded fleet). The shard count (not the device identities)
        is folded into the compile cache key.
        """
        mesh = engine_lib.resolve_mesh(devices)
        shards = int(mesh.devices.size) if mesh is not None else 1
        slack = (
            workload._DEFAULT_BUCKET_SLACK if bucket_slack is None
            else float(bucket_slack)
        )
        key = (
            None
            if cache_key is None
            else (
                "pairs",
                cache_key,
                _hashable_ticks(max_ticks),
                n_buckets,
                bucket_packing,
                slack,
                tuple(bucket_counts) if bucket_counts is not None else None,
                bool(leap),  # leap selects the packing cost model
                tuple(pad_floors) if pad_floors is not None else None,
                pad_multiple,
                tuple(map(tuple, bucket_pad_floors))
                if bucket_pad_floors is not None
                else None,
                shards,
            )
        )
        bank = _cache_get(key) if key is not None else None
        if bank is None:
            pl, pp, pk = pad_floors if pad_floors is not None else (None, None, None)
            bank = compile_bank(
                list(pairs() if callable(pairs) else pairs),
                max_ticks=max_ticks,
                pad_legs=pl,
                pad_procs=pp,
                pad_links=pk,
                pad_multiple=pad_multiple,
                n_buckets=n_buckets,
                bucket_packing=bucket_packing,
                bucket_slack=slack,
                bucket_cost_leap=leap,
                bucket_counts=bucket_counts,
                bucket_pad_floors=bucket_pad_floors,
                shards=shards,
            )
            if key is not None:
                _cache_put(key, bank)
        fleet = cls(bank, lowering=lowering, leap=leap, backend=backend,
                    window=window, devices=devices)
        fleet._mesh = mesh
        return fleet

    @classmethod
    def from_scenarios(
        cls,
        families: Optional[Sequence[str]] = None,
        n: int = 8,
        seed: int = 0,
        *,
        scale: float = 1.0,
        max_ticks: TicksLike = None,
        n_buckets: int = 1,
        bucket_packing: str = "cost",
        bucket_slack: Optional[float] = None,
        bucket_counts: Optional[Sequence[int]] = None,
        pad_floors: Optional[Tuple[int, int, int]] = None,
        pad_multiple: int = 1,
        bucket_pad_floors: Optional[Sequence[Tuple[int, int, int]]] = None,
        cache: bool = True,
        lowering: Optional[str] = None,
        leap: bool = False,
        backend: Optional[str] = None,
        window: Optional[int] = None,
        devices=None,
    ) -> "Fleet":
        """Sample ``n`` scenarios from the generator registry and compile
        them. The sampling recipe (families, n, seed, scale) is hashable and
        uniquely identifies the pair set, so it becomes a
        :meth:`from_pairs` ``cache_key`` (which folds in every compile
        knob): two ``from_scenarios`` calls with one recipe share the bank
        instance (and therefore its device-array spec cache) until
        ``engine.reset_bank_trace_count`` clears the compile cache.
        """
        recipe = (
            "scenarios",
            tuple(families) if families is not None else None,
            n,
            seed,
            scale,
        )
        return cls.from_pairs(
            lambda: sample_scenarios(families, n, seed, scale=scale),
            max_ticks=max_ticks,
            n_buckets=n_buckets,
            bucket_packing=bucket_packing,
            bucket_slack=bucket_slack,
            bucket_counts=bucket_counts,
            pad_floors=pad_floors,
            pad_multiple=pad_multiple,
            bucket_pad_floors=bucket_pad_floors,
            cache_key=recipe if cache else None,
            lowering=lowering,
            leap=leap,
            backend=backend,
            window=window,
            devices=devices,
        )

    @classmethod
    def from_table(
        cls,
        table: LegTable,
        *,
        name: str = "table0",
        max_ticks: TicksLike = None,
        lowering: Optional[str] = None,
        leap: bool = False,
        backend: Optional[str] = None,
        window: Optional[int] = None,
    ) -> "Fleet":
        """Lift one compiled :class:`LegTable` into a single-scenario fleet
        (pads equal the table's own shape, so nothing is padded). This is how
        the scheduler runs population fitness as one banked batch: ``B``
        ``enabled`` masks become per-replica params of the one scenario.
        Memoized per table identity (the table object is kept alive by the
        cache entry, so the id key cannot be reused while cached).
        """
        key = ("table", id(table), _hashable_ticks(max_ticks))
        hit = _cache_get(key)
        if hit is not None and hit[0] is table:
            bank = hit[1]
        else:
            bank = bank_from_tables([table], [name], max_ticks=max_ticks)
            _cache_put(key, (table, bank))
        return cls(bank, lowering=lowering, leap=leap, backend=backend,
                   window=window)

    # -- introspection ------------------------------------------------------

    @property
    def n_scenarios(self) -> int:
        return self.bank.n_scenarios

    @property
    def names(self) -> List[str]:
        return list(self.bank.names)

    @property
    def pad_legs(self) -> int:
        return self.bank.pad_legs

    @property
    def pad_procs(self) -> int:
        return self.bank.pad_procs

    @property
    def pad_links(self) -> int:
        return self.bank.pad_links

    @property
    def pads(self) -> Tuple[int, int, int]:
        """The global ``(legs, procs, links)`` pad shape — the trace-reuse
        contract of :meth:`stream` and of fresh fleets built with these as
        ``pad_floors``."""
        return (self.pad_legs, self.pad_procs, self.pad_links)

    @property
    def resident(self) -> "ResidentBank":
        """The bank's device residency handle
        (:class:`~repro.core.residency.ResidentBank`, memoized per bank):
        the same device spec buffers :meth:`run` uses, exposed as a stepped
        window-loop surface for callers that outlive single runs (the
        ``repro.serve`` slot engine)."""
        from repro.core import residency as residency_lib

        return residency_lib.ResidentBank.of(self.bank)

    @property
    def n_buckets(self) -> int:
        return self.bank.n_buckets if isinstance(self.bank, BucketedBank) else 1

    @property
    def bucket_pad_floors(self) -> Optional[List[Tuple[int, int, int]]]:
        """Per-bucket pad shapes, reusable as ``bucket_pad_floors`` when
        compiling another fleet onto this fleet's bucket traces."""
        if not isinstance(self.bank, BucketedBank):
            return None
        return [
            (b.bank.pad_legs, b.bank.pad_procs, b.bank.pad_links)
            for b in self.bank.buckets
        ]

    @property
    def bucket_scenario_counts(self) -> Optional[Tuple[int, ...]]:
        """Unpadded per-bucket member counts in packed order, reusable as
        ``bucket_counts`` to pin another same-size fleet to this fleet's
        bucket plan (the trace-sharing companion of
        :attr:`bucket_pad_floors` under variable-size cost packing)."""
        if not isinstance(self.bank, BucketedBank):
            return None
        return self.bank.bucket_scenario_counts

    def __repr__(self) -> str:
        kind = type(self.bank).__name__
        return (
            f"Fleet({kind}: {self.n_scenarios} scenarios, pads={self.pads}, "
            f"buckets={self.n_buckets}, lowering={self.lowering!r}, "
            f"leap={self.leap}, window={self.window})"
        )

    # -- params -------------------------------------------------------------

    def params(self, **overrides: Any) -> SimParams:
        """Bank-wide :class:`SimParams` (``engine.make_bank_params`` knobs);
        the no-override base params are memoized on the fleet."""
        if not overrides:
            if self._base_params is None:
                self._base_params = make_bank_params(self.bank)
            return self._base_params
        return make_bank_params(self.bank, **overrides)

    def theta_mapper(self, protocol: str = "webdav") -> Callable[[jax.Array], SimParams]:
        """The unified calibration mapper ``f(theta) -> SimParams`` over the
        whole bank (memoized per protocol)."""
        mapper = self._mappers.get(protocol)
        if mapper is None:
            mapper = calibration_lib.make_theta_mapper(self.bank, protocol)
            self._mappers[protocol] = mapper
        return mapper

    def _resolve_params(
        self,
        params_or_theta: ParamsLike,
        protocol: str,
        bank: Optional[ScenarioBank] = None,
    ) -> SimParams:
        """``None`` -> base bank params; ``SimParams`` -> as given; a
        ``[3]`` theta vector (or per-scenario ``[N, 3]`` matrix, e.g.
        ``AmortizedPosterior.theta_star_all()``) -> the calibration mapper;
        a callable -> ``params_or_theta(bank)`` (the hook :meth:`stream`
        uses to rebuild chunk-shaped params)."""
        target = bank if bank is not None else self.bank
        if params_or_theta is None:
            if bank is None:
                return self.params()
            return make_bank_params(target)
        if isinstance(params_or_theta, SimParams):
            return params_or_theta
        if callable(params_or_theta):
            return params_or_theta(target)
        theta = jnp.asarray(params_or_theta)
        if theta.shape not in ((3,), (target.n_scenarios, 3)):
            raise TypeError(
                "params_or_theta must be SimParams, a theta [3] vector, a "
                f"per-scenario theta [{target.n_scenarios}, 3] matrix, a "
                f"callable bank -> SimParams, or None; got shape {theta.shape}"
            )
        if bank is None:
            return self.theta_mapper(protocol)(theta)
        # chunk banks union only their own protocols: a chunk without the
        # calibrated protocol gets a no-op overhead mask (same as its
        # scenarios would inside the fleet-wide union namespace)
        return calibration_lib.make_theta_mapper(
            target, protocol, missing_ok=True
        )(theta)

    # -- simulate -----------------------------------------------------------

    def run(
        self,
        params_or_theta: ParamsLike = None,
        *,
        replicas: Optional[int] = None,
        key: Optional[jax.Array] = None,
        keys: Optional[jax.Array] = None,
        protocol: str = "webdav",
        lowering: Optional[str] = None,
        leap: Optional[bool] = None,
        backend: Optional[str] = None,
        bucketed: bool = True,
        window: Optional[int] = None,
        devices: DevicesLike = None,
    ) -> SimResult:
        """Simulate every scenario x ``replicas`` stochastic replicas.

        ``params_or_theta`` is resolved by :meth:`_resolve_params`; replica
        keys are split from ``key`` (default ``PRNGKey(0)``) unless explicit
        ``[N, R, 2]`` ``keys`` are given — the replica count then comes
        from the keys, and a conflicting explicit ``replicas`` raises
        rather than being silently ignored. Dispatches to
        ``engine.simulate_bank`` with the fleet's lowering/leap/backend/
        window defaults (each overridable per call; ``window=None`` lets
        the engine pick the fused-tick window per backend and bucket —
        results are bit-identical across window sizes); results come back
        in stable scenario order regardless of bucketing. With ``devices``
        (per call or the fleet default) the bank runs as one SPMD program
        sharded over the scenario axis, bit-identical to the unsharded run.

        The host work is timed as the span ``fleet.run``
        (:mod:`repro.utils.spans`), with ``fleet.params`` (params and
        replica keys) and ``engine.dispatch`` inside it.
        """
        with spans.span("fleet.run"):
            with spans.span("fleet.params"):
                params = self._resolve_params(params_or_theta, protocol)
                keys = self._replica_keys(key, keys, replicas)
            return simulate_bank(
                self.bank,
                params,
                keys,
                backend=self.backend if backend is None else backend,
                leap=self.leap if leap is None else leap,
                lowering=self.lowering if lowering is None else lowering,
                bucketed=bucketed,
                window=self.window if window is None else window,
                mesh=self._resolve_mesh(devices),
            )

    def _replica_keys(
        self,
        key: Optional[jax.Array],
        keys: Optional[jax.Array],
        replicas: Optional[int],
    ) -> jax.Array:
        """:meth:`run`'s ``[N, R, 2]`` replica keys: split from ``key``, or
        the explicit ``keys`` checked against the fleet."""
        if keys is None:
            r = 1 if replicas is None else int(replicas)
            key = jax.random.PRNGKey(0) if key is None else key
            return jax.random.split(key, self.n_scenarios * r).reshape(
                self.n_scenarios, r, 2
            )
        if keys.ndim != 3 or keys.shape[0] != self.n_scenarios:
            # the bucketed scatter would silently clamp a short scenario axis
            raise ValueError(
                f"keys must be [n_scenarios={self.n_scenarios}, R, 2]: "
                f"{keys.shape}"
            )
        if replicas is not None and keys.shape[1] != replicas:
            raise ValueError(
                f"explicit keys carry {keys.shape[1]} replicas but "
                f"replicas={replicas} was requested"
            )
        return keys

    def stream(
        self,
        pairs: Iterable[Tuple[Grid, Campaign]],
        *,
        chunk: Optional[int] = None,
        params_or_theta: ParamsLike = None,
        replicas: int = 1,
        key: Optional[jax.Array] = None,
        protocol: str = "webdav",
        max_ticks: TicksLike = None,
        lowering: Optional[str] = None,
        leap: Optional[bool] = None,
        backend: Optional[str] = None,
        window: Optional[int] = None,
        prefetch: int = 0,
    ) -> Iterator[StreamChunk]:
        """Pipeline an iterator of ``(grid, campaign)`` pairs through
        fixed-pad chunk banks — the streaming-fleet path for campaign sets
        larger than memory.

        Every chunk of ``chunk`` pairs (default: this fleet's scenario
        count) is compiled **monolithically to this fleet's pads**, so all
        chunks share one padded shape and therefore one jit trace: chunk 1
        pays the trace, chunks 2..K cost zero retraces (observable with
        ``engine.count_bank_traces``). A scenario too large for the fleet
        pads raises instead of silently growing the pad (which would
        retrace). A final partial chunk is padded by repeating its last pair
        and sliced back to the real scenarios before yielding, keeping the
        shared shape.

        ``max_ticks`` caps each streamed scenario's simulated length:
        ``None`` (default) resolves to :func:`compile_bank`'s per-scenario
        safe upper bound, so streamed campaigns *longer* than anything in
        the compiling fleet still finish (``max_ticks`` is array data, not
        shape — per-chunk bounds cost no retrace). Pass an int to
        reproduce a fixed-bound fleet run exactly.

        Key schedule (deterministic, documented contract): per chunk,
        ``key, sub = jax.random.split(key)`` then chunk keys are
        ``jax.random.split(sub, chunk * replicas).reshape(chunk, replicas,
        2)`` — so any chunk can be reproduced standalone with
        ``simulate_bank``.

        ``params_or_theta`` follows :meth:`run`, except chunk-shaped params
        are rebuilt per chunk bank: pass ``None`` (each chunk's own
        compiled overheads/moments), a theta ``[3]`` vector, or a callable
        ``bank -> SimParams``. A fixed :class:`SimParams` is rejected — its
        leg/link content would silently misapply to other chunks' scenarios.

        ``prefetch=k`` (k >= 1) overlaps host work with device work: up to
        ``k`` upcoming chunk banks are compiled (and their device specs
        uploaded) on a background thread while the current chunk ticks, and
        the current chunk runs through
        :func:`~repro.core.engine.simulate_bank_stepped`'s donated-carry
        window loop — a host-driven program that yields the GIL at every
        window boundary, giving the compile thread real cycles. Results,
        key schedule, and the zero-retrace contract are identical to the
        synchronous path: the stepped loop is bit-identical to the fused
        while-loop at the same resolved window, and chunks 2..K reuse
        chunk 1's step trace.
        """
        # validate eagerly: the generator below only runs at first iteration
        if isinstance(params_or_theta, SimParams):
            raise TypeError(
                "stream rebuilds params per chunk bank: pass None, a theta "
                "[3] vector, or a callable bank -> SimParams instead of a "
                "fixed SimParams"
            )
        chunk = int(chunk) if chunk is not None else self.n_scenarios
        if chunk <= 0:
            raise ValueError(f"chunk must be positive: {chunk}")
        if prefetch < 0:
            raise ValueError(f"prefetch must be >= 0: {prefetch}")
        return self._stream_chunks(
            pairs, chunk, params_or_theta, replicas, key, protocol,
            max_ticks, lowering, leap, backend, window, int(prefetch),
        )

    def _build_chunk(
        self,
        block: Sequence[Tuple[Grid, Campaign]],
        chunk: int,
        max_ticks: TicksLike,
    ) -> Tuple[ScenarioBank, int]:
        """Compile one stream block into a fleet-pad chunk bank (runs on the
        prefetch thread when ``prefetch > 0``): campaign compilation, the
        pad check, and the device upload of the stacked spec arrays all
        happen here, so by the time the consumer simulates the chunk only
        the tick program remains."""
        real = len(block)
        tables = [compile_campaign(g, c) for g, c in block]
        names = [c.name for _, c in block]
        if real < chunk:  # pad the tail chunk: same shape, same trace
            # repeat the already-compiled last table — never re-pay the
            # per-campaign compile for throwaway pad scenarios
            tables += [tables[-1]] * (chunk - real)
            names += [names[-1]] * (chunk - real)
        cbank = bank_from_tables(
            tables,
            names,
            max_ticks=max_ticks,
            pad_legs=self.pad_legs,
            pad_procs=self.pad_procs,
            pad_links=self.pad_links,
        )
        if (cbank.pad_legs, cbank.pad_procs, cbank.pad_links) != self.pads:
            raise ValueError(
                f"stream chunk outgrew the fleet pads {self.pads} -> "
                f"{(cbank.pad_legs, cbank.pad_procs, cbank.pad_links)}; "
                "compile the fleet with pad_floors covering the stream"
            )
        # transfer: materialize (and memoize) the device-array spec now
        engine_lib.bank_spec(cbank)
        return cbank, real

    def _stream_chunks(
        self, pairs, chunk, params_or_theta, replicas, key, protocol,
        max_ticks, lowering, leap, backend, window, prefetch,
    ) -> Iterator[StreamChunk]:
        key = jax.random.PRNGKey(0) if key is None else key
        it = iter(pairs)
        leap = self.leap if leap is None else leap
        backend_r = self.backend if backend is None else backend
        lowering_r = self.lowering if lowering is None else lowering
        window_r = self.window if window is None else window
        # the stepped (host-driven, donated-carry) loop is the overlap
        # partner of the prefetch thread; it is bit-identical to the fused
        # while-loop program only on the banked lowering, so an explicit
        # vmap override falls back to the synchronous program per chunk
        use_stepped = (
            prefetch > 0
            and engine_lib._resolve_lowering(lowering_r) == "banked"
            and self._resolve_mesh() is None
        )

        def ready(cbank, real):
            nonlocal key
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, chunk * replicas).reshape(
                chunk, replicas, 2
            )
            cparams = self._resolve_params(params_or_theta, protocol, bank=cbank)
            if use_stepped:
                res = engine_lib.simulate_bank_stepped(
                    cbank, cparams, keys, backend=backend_r, leap=leap,
                    window=window_r,
                )
            else:
                res = simulate_bank(
                    cbank, cparams, keys, backend=backend_r, leap=leap,
                    lowering=lowering_r, window=window_r,
                    mesh=self._resolve_mesh(),
                )
            if real < chunk:
                res = jax.tree.map(lambda a: a[:real], res)
            return StreamChunk(
                bank=cbank, result=res, names=list(cbank.names[:real])
            )

        if prefetch <= 0:
            while True:
                block = list(itertools.islice(it, chunk))
                if not block:
                    return
                yield ready(*self._build_chunk(block, chunk, max_ticks))
            return

        import collections
        from concurrent.futures import ThreadPoolExecutor

        pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="fleet-stream-prefetch"
        )
        try:
            pending = collections.deque()
            for _ in range(prefetch + 1):
                block = list(itertools.islice(it, chunk))
                if not block:
                    break
                pending.append(
                    pool.submit(self._build_chunk, block, chunk, max_ticks)
                )
            while pending:
                cbank, real = pending.popleft().result()
                # top the pipeline back up *before* simulating, so the
                # compile of chunk i+prefetch overlaps the ticks of chunk i
                block = list(itertools.islice(it, chunk))
                if block:
                    pending.append(
                        pool.submit(self._build_chunk, block, chunk, max_ticks)
                    )
                yield ready(cbank, real)
        finally:
            pool.shutdown(wait=True, cancel_futures=True)

    # -- persistence --------------------------------------------------------

    def save(self, path: str) -> str:
        """Persist the compiled bank to ``path/`` as ``bank.npz`` (every
        stacked array) + ``meta.json`` (names, protocol namespace, pads,
        bucket structure, run defaults). The unpadded source
        :class:`LegTable` objects are *not* persisted — a loaded fleet
        simulates bit-identically but raises on ``scenario_table`` (oracle
        comparisons need a recompile).

        ``run_opts.resolved_window`` records what this process's
        ``window=None`` resolves to (the persisted per-backend autotune
        table; see :func:`~repro.core.engine.default_tick_window`), so a
        loaded fleet replays the *chosen* window even on a host whose own
        table would pick differently; an explicit :attr:`window` still
        dominates. Bucket entries record each sub-bank's (possibly
        shard-padded) ``scenarios`` count so :meth:`load` rebuilds the
        exact padded shapes."""
        os.makedirs(path, exist_ok=True)
        bank = self.bank
        arrays = {name: np.asarray(getattr(bank, name)) for name in _ARRAY_FIELDS}
        meta = {
            "format": 1,
            "protocol_names": list(bank.protocol_names),
            "names": list(bank.names),
            "pads": list(self.pads),
            "run_opts": {
                "lowering": self.lowering,
                "leap": self.leap,
                "backend": self.backend,
                "window": self.window,
                "resolved_window": (
                    self.window
                    if self.window is not None
                    else engine_lib.default_tick_window(self.leap)
                ),
            },
            "bucketed": isinstance(bank, BucketedBank),
        }
        if isinstance(bank, BucketedBank):
            arrays["bucket_of"] = np.asarray(bank.bucket_of)
            arrays["slot_of"] = np.asarray(bank.slot_of)
            meta["packing"] = bank.packing
            meta["buckets"] = [
                {
                    "scenario_ids": [int(i) for i in b.scenario_ids],
                    "pad_legs": b.bank.pad_legs,
                    "pad_procs": b.bank.pad_procs,
                    "pad_links": b.bank.pad_links,
                    "scenarios": b.bank.n_scenarios,
                    "cost": float(b.cost),
                    "cost_share": float(b.cost_share),
                }
                for b in bank.buckets
            ]
        np.savez_compressed(os.path.join(path, "bank.npz"), **arrays)
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f, indent=2)
        return path

    @classmethod
    def load(cls, path: str, **run_opts: Any) -> "Fleet":
        """Rebuild a fleet saved by :meth:`save`. Bucketed banks are
        restored bucket for bucket: each sub-bank is sliced back out of the
        persisted monolithic arrays (see
        :func:`~repro.core.workload.subset_bank` — bit-identical to the
        original compile) and re-padded to its persisted (shard-padded)
        scenario count. ``run_opts`` override the persisted
        lowering/leap/backend defaults; a persisted ``window=None``
        resolves to the save-time ``resolved_window``, so the autotuned
        choice round-trips across hosts."""
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        if meta.get("format") != 1:
            raise ValueError(f"unknown fleet save format: {meta.get('format')!r}")
        with np.load(os.path.join(path, "bank.npz")) as z:
            arrays = {k: z[k] for k in z.files}
        base = {name: arrays[name] for name in _ARRAY_FIELDS}
        mono = ScenarioBank(
            **base,
            protocol_names=list(meta["protocol_names"]),
            names=list(meta["names"]),
            tables=[],
        )
        bank: ScenarioBank = mono
        if meta["bucketed"]:
            buckets = []
            for info in meta["buckets"]:
                ids = np.asarray(info["scenario_ids"], np.int32)
                sub = subset_bank(
                    mono,
                    ids,
                    pad_legs=info["pad_legs"],
                    pad_procs=info["pad_procs"],
                    pad_links=info["pad_links"],
                )
                padded = int(info.get("scenarios", len(ids)))
                if padded > len(ids):
                    sub = pad_bank_scenarios(sub, count=padded)
                buckets.append(
                    BankBucket(
                        scenario_ids=ids,
                        bank=sub,
                        # .get defaults: saves from before the cost-packing
                        # format carry no cost metadata (still format 1)
                        cost=float(info.get("cost", 0.0)),
                        cost_share=float(info.get("cost_share", 0.0)),
                    )
                )
            bank = BucketedBank(
                **{
                    f.name: getattr(mono, f.name)
                    for f in dataclasses.fields(ScenarioBank)
                },
                bucket_of=arrays["bucket_of"],
                slot_of=arrays["slot_of"],
                buckets=buckets,
                packing=str(meta.get("packing", "count")),
            )
        opts = dict(meta.get("run_opts") or {})
        resolved = opts.pop("resolved_window", None)
        opts.update(run_opts)
        if opts.get("window") is None and resolved is not None:
            opts["window"] = int(resolved)
        return cls(bank, **opts)

    def save_checkpoint(
        self,
        path: str,
        ckpt: "engine_lib.BankCheckpoint",
        *,
        include_fleet: bool = True,
    ) -> str:
        """Persist a :class:`~repro.core.engine.BankCheckpoint` (from
        ``simulate_bank_stepped(checkpoint_every=..., on_checkpoint=...)``)
        to ``path/`` as ``carry.npz`` + ``checkpoint.json`` — the
        ``Fleet.save``-compatible snapshot format: with ``include_fleet``
        (default) the same directory also receives :meth:`save`'s
        ``bank.npz`` + ``meta.json`` (disjoint file names), so one
        directory restores both the fleet and its in-flight carry for
        multi-hour runs."""
        os.makedirs(path, exist_ok=True)
        np.savez_compressed(
            os.path.join(path, "carry.npz"),
            **{f: np.asarray(a) for f, a in zip(ckpt.carry._fields, ckpt.carry)},
        )
        with open(os.path.join(path, "checkpoint.json"), "w") as f:
            json.dump(
                {
                    "format": 1,
                    "windows_done": int(ckpt.windows_done),
                    "window": int(ckpt.window),
                },
                f,
                indent=2,
            )
        if include_fleet:
            self.save(path)
        return path

    @staticmethod
    def load_checkpoint(path: str) -> "engine_lib.BankCheckpoint":
        """Load a carry snapshot saved by :meth:`save_checkpoint`; pass the
        result as ``simulate_bank_stepped(..., resume=ckpt)`` (with the same
        bank/params/window — e.g. from :meth:`load` of the same directory)
        to continue the run bit-identically from the recorded window."""
        meta_path = os.path.join(path, "checkpoint.json")
        try:
            with open(meta_path) as f:
                meta = json.load(f)
        except (OSError, ValueError) as e:
            raise ValueError(
                f"cannot read checkpoint metadata {meta_path!r}: {e} — the "
                "checkpoint directory is missing or its checkpoint.json is "
                "truncated/corrupted; re-save via Fleet.save_checkpoint"
            ) from e
        if meta.get("format") != 1:
            raise ValueError(
                f"unknown checkpoint format: {meta.get('format')!r}"
            )
        carry_path = os.path.join(path, "carry.npz")
        try:
            with np.load(carry_path) as z:
                carry = engine_lib._Carry(
                    *(z[f] for f in engine_lib._Carry._fields)
                )
        except (OSError, ValueError, KeyError, zipfile.BadZipFile) as e:
            raise ValueError(
                f"cannot load checkpoint carry {carry_path!r}: {e} — the "
                "npz is truncated/corrupted or missing carry fields "
                f"{list(engine_lib._Carry._fields)}; the checkpoint cannot "
                "be resumed"
            ) from e
        return engine_lib.BankCheckpoint(
            windows_done=int(meta["windows_done"]),
            window=int(meta["window"]),
            carry=carry,
        )

    # -- calibrate ----------------------------------------------------------

    def coefficients(
        self,
        params_or_theta: ParamsLike = None,
        *,
        replicas: int = 1,
        key: Optional[jax.Array] = None,
        protocol: str = "webdav",
        leap: Optional[bool] = None,
    ) -> jax.Array:
        """Eq.-1 coefficient triples of a fleet run: ``[N, R, 3]`` (one OLS
        fit of the remote observations per (scenario, replica))."""
        res = self.run(
            params_or_theta, replicas=replicas, key=key, protocol=protocol,
            leap=leap,
        )
        n, r = self.n_scenarios, replicas
        flat = jax.tree.map(lambda a: a.reshape((n * r,) + a.shape[2:]), res)
        coefs = jax.vmap(calibration_lib._eq1_coefficients)(flat)
        return coefs.reshape(n, r, 3)

    def presimulate(
        self,
        prior: "calibration_lib.PriorBox",
        key: jax.Array,
        n_per_scenario: int,
        *,
        protocol: str = "webdav",
        batch: int = 128,
        leap: Optional[bool] = None,
        backend: Optional[str] = None,
    ):
        """``(theta, x_sim, scenario_id)`` tuples over the fleet's scenario
        variants (see :func:`repro.core.calibration.presimulate_bank`)."""
        return calibration_lib.presimulate_bank(
            self,
            prior,
            key,
            n_per_scenario,
            protocol=protocol,
            batch=batch,
            leap=self.leap if leap is None else leap,
            backend=self.backend if backend is None else backend,
        )

    def summary_features(self) -> np.ndarray:
        """Per-scenario campaign summary features ``[N, F]`` (the amortized
        calibration's context table; see
        :func:`repro.core.workload.summary_features`)."""
        return summary_features(self.bank)

    def calibrate(
        self,
        x_true: jax.Array,
        key: jax.Array,
        cfg: Optional["calibration_lib.CalibrationConfig"] = None,
        prior: Optional["calibration_lib.PriorBox"] = None,
        *,
        protocol: str = "webdav",
        batch: int = 128,
        amortized: bool = False,
    ) -> "calibration_lib.CalibrationResult | calibration_lib.AmortizedPosterior":
        """Likelihood-free calibration of theta = (overhead, mu, sigma)
        against ``x_true``, presimulating over **all** scenario variants of
        the fleet (``cfg.n_presim`` total tuples, scenario-major) so the
        learned ratio is robust to campaign shape. Classifier training, MCMC
        and the theta* extraction follow
        :func:`repro.core.calibration.calibrate`.

        ``amortized=True`` keeps the ``scenario_id`` column paired with each
        tuple and conditions the classifier on
        :meth:`summary_features` — the return value is then an
        :class:`~repro.core.calibration.AmortizedPosterior`: one trained net
        whose ``theta_star(scenario)`` / ``theta_star_all()`` serve every
        scenario family of the fleet without retraining (``x_true`` may be
        one shared ``[3]`` observation or per-scenario ``[N, 3]``).

        The banked presimulation draws single-realization coefficient
        tuples: ``cfg.n_replicates > 1`` (the per-campaign variance
        -reduction knob of :func:`~repro.core.calibration.presimulate`) is
        not supported here and logs a warning — scenario diversity is the
        fleet path's variance control."""
        cfg = cfg if cfg is not None else calibration_lib.CalibrationConfig()
        if cfg.n_replicates > 1:
            calibration_lib.log.warning(
                "Fleet.calibrate draws single-realization tuples; "
                "cfg.n_replicates=%d is ignored on the banked path",
                cfg.n_replicates,
            )
        prior = prior if prior is not None else calibration_lib.PriorBox.paper()
        key, k_pre = jax.random.split(key)
        n_per = max(1, -(-cfg.n_presim // self.n_scenarios))
        theta, x_sim, sid = self.presimulate(
            prior, k_pre, n_per, protocol=protocol,
            batch=min(batch, n_per), leap=cfg.use_leap,
        )
        return calibration_lib.calibrate(
            None,  # spec unused: the presim is supplied
            self.bank,
            x_true,
            key,
            cfg,
            prior,
            protocol=protocol,
            presim=(theta, x_sim, sid) if amortized else (theta, x_sim),
            amortized=amortized,
        )

    def validate(
        self,
        theta_star: jax.Array,
        x_true: jax.Array,
        key: jax.Array,
        *,
        n_sims: int = 64,
        protocol: str = "webdav",
        leap: Optional[bool] = None,
        backend: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Validation sweep under theta* across every scenario (see
        :func:`repro.core.calibration.validate_bank`). ``theta_star`` may be
        one shared ``[3]`` vector or the per-scenario ``[N, 3]`` matrix of
        ``AmortizedPosterior.theta_star_all()``, and ``x_true`` broadcasts
        the same way; ``leap=None`` resolves to this fleet's run default."""
        return calibration_lib.validate_bank(
            self,
            theta_star,
            x_true,
            key,
            n_sims=n_sims,
            protocol=protocol,
            leap=self.leap if leap is None else leap,
            backend=self.backend if backend is None else backend,
        )


def _hashable_ticks(max_ticks) -> Union[None, int, Tuple[int, ...]]:
    """Normalize a ``max_ticks`` spec (None / int / sequence) to a cache key."""
    if max_ticks is None:
        return None
    if np.ndim(max_ticks) == 0:
        return int(max_ticks)
    return tuple(int(m) for m in max_ticks)
