"""``SimServer`` — the synchronous in-process simulation service.

One server owns a set of warm :class:`~repro.serve.slots.SlotBank` banks
(one per pad signature), a per-signature admission queue, and the result
store. ``submit`` compiles the request to a single-scenario row and
enqueues it; ``step`` runs one **overlapped** scheduling round; ``drain``
steps until nothing is queued or resident. Results stream back per request
the round their scenario finishes, not when the whole batch drains.

A round is four phases, ordered so the host never blocks on work it
dispatched in the *same* round:

1. **ADMIT** — fill free slots from the native-signature queues, then a
   coalescing pass: a request whose native bank is cold (never built) or
   saturated is re-stacked up-tier into an existing wider bank whose
   signature dominates its pads (results are sliced back to native shape
   at retire — bitwise identical by the inert-pad + prefix-stable-RNG
   contracts). Fewer, fuller banks instead of one fragment per signature.
2. **DISPATCH** — every believed-live bank picks a window-ladder rung from
   its residual-work estimates and dispatches one async window step plus
   its post-step liveness/result snapshot. No host sync anywhere in this
   phase; JAX async dispatch keeps the device busy across banks.
3. **FETCH** — one batched ``device_get`` over the snapshots dispatched
   *last* round. This is the round's only host sync, and it waits on
   device work that has had a full round to complete.
4. **RETIRE** — free every slot the fetched snapshots prove finished,
   slicing result rows out of the snapshot buffers (never the live carry,
   so retirement cannot block on the in-flight step). Deferred liveness
   means a finished row is detected at most one round late; the extra
   window it sits through is a bit-exact no-op on its frozen carry
   (CONTRACTS.md §7/§8 — retire latency ≤ 1 round).

Parity contract: a served result is **bitwise identical** to a direct
``Fleet.run`` of the same scenario with the same theta/keys — admission
merges are masked carry re-initializations, empty slots and unused replica
lanes are inert, window steps freeze finished elements regardless of rung
choice, and every parameter row is computed through the same row-local
calibration mapper ``Fleet.run`` uses (CONTRACTS.md §8;
``tests/test_serve.py`` pins it, and ``benchmarks/serve_latency.py
--smoke`` asserts it in CI).

Under ``REPRO_DEBUG=1`` the runtime sanitizers come on: every slot-bank
template passes ``sanitize.check_bank`` and — because a bank pre-traces
its whole dispatch set at construction — every round that creates no new
bank runs inside ``sanitize.retrace_guard(budget=0)``: a steady-state
retrace is a contract violation, not a slowdown.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import calibration as calibration_lib
from repro.core import engine as engine_lib
from repro.core.engine import make_bank_params
from repro.core.workload import bank_from_tables, compile_campaign
from repro.serve.cache import (
    BankSlotCache,
    dominates,
    pad_signature,
    signature_volume,
)
from repro.serve.request import RequestResult, SimRequest
from repro.serve.slots import Admission, SlotBank
from repro.utils import spans

__all__ = ["ServeConfig", "SimServer"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Server policy.

    ``slots``/``replicas`` fix every slot bank's ``[S, R]`` shape.
    ``pad_floors`` + ``quantize`` define the pad-signature tiers requests
    route by (power-of-two brackets by default; ``quantize=False`` pins one
    fixed shape and rejects campaigns that do not fit).

    ``window`` is the base fused tick window per scheduling round; ``None``
    resolves once through the engine's per-backend default, floored at 8
    (the server's host-driven loop pays a dispatch per window, which the
    stepped engine's CPU-tuned ``K=1`` would multiply by every tick).
    ``rungs`` is the per-bank window ladder — ``None`` derives the pow2 set
    ``{W/4, W, 4W}`` from the base window. Every rung is traced once at
    bank construction and never again (per-signature trace budget =
    ``len(rungs) + 2``); results are bit-identical for every rung
    (CONTRACTS.md §7), so the per-round rung choice is purely a
    host-dispatch amortization knob driven by residual-work estimates.

    ``coalesce`` enables up-tier routing: a request whose native-signature
    bank is cold or saturated may run in a warmer, wider bank whose
    signature dominates its pads, as long as the wide bank's pad volume is
    at most ``coalesce_ratio`` times the native volume. A window executes
    every pad element, so an up-tiered row costs up to ``coalesce_ratio``
    times its native compute — the conservative default of 2 merges only
    near-equal-volume tiers, where fewer/fuller banks beat the
    over-padding; raise it when trace/bank-construction cost dominates
    device compute (many one-off signatures).
    """

    slots: int = 8
    replicas: int = 1
    pad_floors: Tuple[int, int, int] = (8, 8, 8)
    quantize: bool = True
    window: Optional[int] = None
    rungs: Optional[Tuple[int, ...]] = None
    leap: bool = False
    backend: Optional[str] = None
    warm_dir: Optional[str] = None
    coalesce: bool = True
    coalesce_ratio: float = 2.0


class _Pending(collections.namedtuple("_Pending", "admission submitted_at")):
    __slots__ = ()


class SimServer:
    """Continuous-batching simulation server (synchronous, in-process)."""

    def __init__(self, config: Optional[ServeConfig] = None, *, devices=None):
        self.config = config or ServeConfig()
        if self.config.slots < 1:
            raise ValueError(f"slots must be >= 1: {self.config.slots}")
        if self.config.replicas < 1:
            raise ValueError(f"replicas must be >= 1: {self.config.replicas}")
        self.mesh = engine_lib.resolve_mesh(devices)
        if self.mesh is not None and self.config.slots % self.mesh.devices.size:
            raise ValueError(
                f"slots={self.config.slots} must be a multiple of the mesh "
                f"size {self.mesh.devices.size} (the slot bank shards over "
                "the scenario axis)"
            )
        if self.config.window is not None:
            self.window = max(1, int(self.config.window))
        else:
            self.window = max(
                8, engine_lib._resolve_window(None, self.config.leap)
            )
        if self.config.rungs is not None:
            self.rungs = tuple(sorted(set(int(r) for r in self.config.rungs)))
        else:
            self.rungs = tuple(
                sorted({max(1, self.window // 4), self.window, self.window * 4})
            )
        if any(r < 1 for r in self.rungs):
            raise ValueError(f"window rungs must be >= 1: {self.rungs}")
        self.cache = BankSlotCache(
            self.config.slots, warm_dir=self.config.warm_dir
        )
        self.banks: Dict[tuple, SlotBank] = {}
        self.queues: Dict[tuple, Deque[_Pending]] = {}
        self.results: Dict[int, RequestResult] = {}
        self._submitted_at: Dict[int, float] = {}
        self._admitted_at: Dict[int, float] = {}
        self._seen_rids: set = set()
        self._unreturned: List[RequestResult] = []
        self.rounds = 0
        self.coalesced = 0
        # the rounds' wall time and its split over the four phases,
        # accumulated across rounds from the phases' spans
        self.wall_round_s = 0.0
        self.wall_admit_s = 0.0
        self.wall_dispatch_s = 0.0
        self.wall_sync_s = 0.0
        self.wall_retire_s = 0.0
        self._debug = engine_lib._sanitizers_wanted()

    # -- submission ---------------------------------------------------------

    def submit(self, req: SimRequest) -> int:
        """Compile and enqueue one request; returns its ``rid``.

        Compilation (campaign → leg table → single-row bank at the native
        signature, the row's params through the calibration mapper, and
        the residual-work estimate that drives the window ladder) happens
        here, at the submission edge, so the scheduling rounds stay pure
        routing + device work.
        """
        if req.rid in self._seen_rids:
            raise ValueError(f"duplicate request id {req.rid}")
        if req.n_replicas > self.config.replicas:
            raise ValueError(
                f"request {req.rid} wants {req.n_replicas} replicas but the "
                f"server's slot banks carry replicas={self.config.replicas}; "
                "raise ServeConfig.replicas"
            )
        table = compile_campaign(req.grid, req.campaign)
        sig = pad_signature(
            table,
            floors=self.config.pad_floors,
            quantize=self.config.quantize,
        )
        name = req.name if req.name is not None else f"request_{req.rid}"
        row_bank = bank_from_tables(
            [table], names=[name],
            pad_legs=sig[0], pad_procs=sig[1], pad_links=sig[2],
        )
        if req.theta is None:
            params = make_bank_params(row_bank)
        else:
            params = calibration_lib.make_theta_mapper(
                row_bank, req.protocol, missing_ok=True
            )(np.asarray(req.theta))
        if req.keys is not None:
            row_keys = np.asarray(req.keys, np.uint32)
        else:
            row_keys = np.asarray(
                jax.random.split(
                    jax.random.PRNGKey(req.seed), req.n_replicas
                ),
                np.uint32,
            )
        # unused replica lanes get zero keys but are *inert* (born-done via
        # the per-lane enabled mask), so they cost nothing and the retired
        # [n_replicas, ...] slice is unchanged
        keys = np.zeros((self.config.replicas, 2), np.uint32)
        keys[: req.n_replicas] = row_keys
        if self.config.leap:
            est = table.leap_event_estimate()
        else:
            est = table.max_ticks_upper_bound(bg_override_cap=0.0, slack=1.0)
        adm = Admission(
            request=req,
            row_bank=row_bank,
            keep_frac=np.asarray(params.keep_frac, np.float32)[0],
            bg_mu=np.asarray(params.bg_mu, np.float32)[0],
            bg_sigma=np.asarray(params.bg_sigma, np.float32)[0],
            keys=keys,
            table=table,
            native_sig=sig,
            est_units=max(1, int(math.ceil(float(est)))),
        )
        self._seen_rids.add(req.rid)
        self.queues.setdefault(sig, collections.deque()).append(
            _Pending(adm, time.perf_counter())
        )
        return req.rid

    # -- routing ------------------------------------------------------------

    def _bank_for(self, sig: tuple, seed_bank) -> SlotBank:
        bank = self.banks.get(sig)
        if bank is None:
            template = self.cache.get_or_create(sig, seed_bank)
            if self._debug:
                from repro.analysis import sanitize

                sanitize.check_bank_once(template)
            bank = SlotBank(
                sig, template, self.config.replicas,
                window=self.window, rungs=self.rungs,
                leap=self.config.leap,
                backend=self.config.backend, mesh=self.mesh,
            )
            self.banks[sig] = bank
        return bank

    def _coalesce_target(
        self, sig: tuple, taken: Optional[Dict[tuple, int]] = None
    ) -> Optional[SlotBank]:
        """The cheapest existing bank a ``sig``-native request may run in
        up-tier: signature strictly wider, dominating every pad axis, pad
        volume within ``coalesce_ratio`` of native, and — when ``taken``
        (slots already claimed this round) is given — still holding a free
        slot beyond the claims. None when no such bank exists."""
        native_vol = signature_volume(sig)
        best = None
        for bsig, bank in self.banks.items():
            if tuple(bsig) == tuple(sig) or not dominates(bsig, sig):
                continue
            if signature_volume(bsig) > self.config.coalesce_ratio * native_vol:
                continue
            if taken is not None:
                free = len(bank.free_slots()) - taken.get(tuple(bsig), 0)
                if free <= 0:
                    continue
            if best is None or signature_volume(bsig) < signature_volume(
                best.signature
            ):
                best = bank
        return best

    def _restack(self, adm: Admission, sig: tuple) -> Admission:
        """Re-stack an admission at a wider bank's pads: rebuild the row
        bank from the compiled table at ``sig`` and extend the param rows
        with the canonical inert fills (keep=1, mu=sigma=0). The widened
        row is bitwise the native row on the native extent — padded
        legs/links contribute exactly zero and the RNG stream is
        prefix-stable across link-pad widths."""
        req = adm.request
        name = req.name if req.name is not None else f"request_{req.rid}"
        row_bank = bank_from_tables(
            [adm.table], names=[name],
            pad_legs=sig[0], pad_procs=sig[1], pad_links=sig[2],
        )
        keep = np.ones(sig[0], np.float32)
        keep[: adm.keep_frac.shape[0]] = adm.keep_frac
        bg_mu = np.zeros(sig[2], np.float32)
        bg_mu[: adm.bg_mu.shape[0]] = adm.bg_mu
        bg_sigma = np.zeros(sig[2], np.float32)
        bg_sigma[: adm.bg_sigma.shape[0]] = adm.bg_sigma
        return dataclasses.replace(
            adm, row_bank=row_bank,
            keep_frac=keep, bg_mu=bg_mu, bg_sigma=bg_sigma,
        )

    def _ensure_banks(self) -> int:
        """Create slot banks for queued signatures that have none — unless
        coalescing can host the whole queue in an existing wider bank, in
        which case the cold native bank is never built. Returns how many
        banks were created (a creation round is exempt from the
        zero-retrace guard; construction pre-traces the new bank's whole
        dispatch set)."""
        created = 0
        for sig, queue in list(self.queues.items()):
            if not queue or sig in self.banks:
                continue
            if self.config.coalesce and self._coalesce_target(sig) is not None:
                continue
            self._bank_for(sig, queue[0].admission.row_bank)
            created += 1
        return created

    # -- scheduling ---------------------------------------------------------

    def _pop_for(self, queue: Deque[_Pending], now: float) -> Admission:
        pending = queue.popleft()
        adm = pending.admission
        if adm.request.n_replicas > self.config.replicas:
            # defensive: submit() rejects oversized requests before
            # queueing, so an entry like this means the queue was poked
            # externally — fail it loudly instead of letting it cycle
            # (admitted-but-never-live would spin drain)
            raise ValueError(
                f"request {adm.request.rid} asks for "
                f"{adm.request.n_replicas} replicas but the server "
                f"runs {self.config.replicas}; it can never be admitted"
            )
        rid = adm.request.rid
        self._submitted_at[rid] = pending.submitted_at
        self._admitted_at[rid] = now
        return adm

    def _admit_phase(self, now: float) -> None:
        """Native pass — every queue fills its own bank's free slots —
        then the coalescing pass: whatever is still queued (native bank
        cold or saturated) is re-stacked into a dominating wider bank with
        capacity, cheapest signature first."""
        for sig, bank in self.banks.items():
            queue = self.queues.get(sig)
            if not queue:
                continue
            entries = []
            for slot in bank.free_slots():
                if not queue:
                    break
                entries.append((slot, self._pop_for(queue, now)))
            if entries:
                bank.admit(entries)
        if not self.config.coalesce:
            return
        for sig, queue in self.queues.items():
            batches: Dict[tuple, List[Tuple[int, Admission]]] = {}
            taken: Dict[tuple, int] = {}
            while queue:
                target = self._coalesce_target(sig, taken)
                if target is None:
                    break
                tsig = tuple(target.signature)
                k = taken.get(tsig, 0)
                slot = target.free_slots()[k]
                taken[tsig] = k + 1
                adm = self._restack(self._pop_for(queue, now), tsig)
                batches.setdefault(tsig, []).append((slot, adm))
                self.coalesced += 1
            for tsig, entries in batches.items():
                self.banks[tsig].admit(entries)

    def _round(self, now: float) -> bool:
        """One overlapped scheduling round: admit → dispatch → fetch →
        retire (see the module docstring), each phase a span of
        :mod:`repro.utils.spans` inside ``serve.round``. Returns True while
        any bank still holds resident work."""
        with spans.span("serve.round") as round_:
            with spans.span("serve.admit") as admit:
                # snapshots dispatched last round — this round's only host
                # sync reads these, never the steps dispatched below
                pend = []
                for bank in self.banks.values():
                    snap = bank.pending_snapshot()
                    if snap is not None:
                        pend.append((bank, snap))
                self._admit_phase(now)
            with spans.span("serve.dispatch") as dispatch:
                for bank in self.banks.values():
                    if bank.any_believed_live():
                        bank.step(bank.choose_rung())
            with spans.span("serve.sync") as sync:
                if pend:
                    lives = jax.device_get([snap[1] for _, snap in pend])
                    for (bank, snap), live in zip(pend, lives):
                        bank.apply_snapshot(
                            snap[0], np.asarray(live, bool), snap[2]
                        )
            with spans.span("serve.retire") as retire:
                self._retire_phase(now)
        self.wall_round_s += round_.seconds
        self.wall_admit_s += admit.seconds
        self.wall_dispatch_s += dispatch.seconds
        self.wall_sync_s += sync.seconds
        self.wall_retire_s += retire.seconds
        return any(b.occupied for b in self.banks.values())

    def _retire_phase(self, now: float) -> None:
        """Free every slot the fetched snapshots prove finished and store
        its result."""
        to_retire = [
            (sig, bank, rs)
            for sig, bank in self.banks.items()
            if (rs := bank.retirable_slots())
        ]
        # one batched host fetch of the retiring banks' snapshot results —
        # per-slot slicing then runs on host arrays, not device buffers
        hosts = (
            jax.device_get([b._seen[2] for _, b, _ in to_retire])
            if to_retire else []
        )
        for (sig, bank, rs), host in zip(to_retire, hosts):
            for s in rs:
                native = tuple(bank.slot_native[s] or sig)
                done_req, rows, windows, _ticks = bank.retire(s, result=host)
                res = RequestResult(
                    rid=done_req.rid,
                    name=done_req.name or f"request_{done_req.rid}",
                    result=rows,
                    n_replicas=done_req.n_replicas,
                    signature=native,
                    slot=s,
                    submitted_at=self._submitted_at.pop(done_req.rid),
                    admitted_at=self._admitted_at.pop(done_req.rid),
                    finished_at=now,
                    windows=windows,
                )
                self.results[done_req.rid] = res
                self._unreturned.append(res)

    def step(self) -> bool:
        """One scheduling round over every slot bank. Returns True while
        any request is still queued or resident."""
        now = time.perf_counter()
        created = self._ensure_banks()
        if self._debug and self.banks and not created:
            from repro.analysis import sanitize

            with sanitize.retrace_guard(budget=0):
                busy = self._round(now)
        else:
            busy = self._round(now)
        self.rounds += 1
        return busy or any(self.queues.values())

    def poll(self, rid: int) -> Optional[RequestResult]:
        """The finished result for ``rid``, or ``None`` while it is still
        queued/running (non-destructive)."""
        if rid not in self._seen_rids:
            raise KeyError(f"unknown request id {rid}")
        return self.results.get(rid)

    def _progress_snapshot(self) -> tuple:
        """Monotone progress counters: every legitimate busy round advances
        at least one (admission bumps ``admitted``, resident work bumps
        ``windows_total``, completion bumps ``retired``/``results``)."""
        return (
            sum(b.admitted for b in self.banks.values()),
            sum(b.retired for b in self.banks.values()),
            sum(b.windows_total for b in self.banks.values()),
            len(self.results),
        )

    def drain(self, *, max_rounds: int = 1_000_000) -> List[RequestResult]:
        """Step until every submitted request has finished; returns the
        results completed since the last ``drain`` in completion order
        (each exactly once).

        Liveness guard: a busy round that advances **no** progress counter
        (no admission, no window stepped, no retirement) means some queued
        request can never be admitted — e.g. a queue entry that bypassed
        :meth:`submit` validation. Such a stall raises immediately, naming
        the stuck request ids, instead of spinning silently to
        ``max_rounds``."""
        rounds = 0
        before = self._progress_snapshot()
        while self.step():
            after = self._progress_snapshot()
            if after == before:
                stuck = [
                    p.admission.request.rid
                    for q in self.queues.values()
                    for p in q
                ]
                raise RuntimeError(
                    "drain stalled: a scheduling round reported busy but "
                    "admitted, stepped, and retired nothing — queued "
                    f"request ids {stuck} can never be admitted"
                )
            before = after
            rounds += 1
            if rounds >= max_rounds:
                raise RuntimeError(
                    f"drain did not converge within {max_rounds} scheduling "
                    "rounds — a request can neither finish nor admit"
                )
        out = self._unreturned
        self._unreturned = []
        return out

    # -- observability ------------------------------------------------------

    def metrics(self) -> dict:
        """Serving metrics: global counters, the rounds' wall time and its
        split over the four phases (the ``serve.*`` spans of this server's
        rounds), and per-signature slot-bank
        occupancy / rung-histogram / coalesce measurements."""
        return {
            "rounds": self.rounds,
            "submitted": len(self._seen_rids),
            "completed": len(self.results),
            "queued": sum(len(q) for q in self.queues.values()),
            "resident": sum(b.occupied for b in self.banks.values()),
            "window": self.window,
            "rungs": list(self.rungs),
            "coalesced": self.coalesced,
            "slots": self.config.slots,
            "replicas": self.config.replicas,
            "wall_round_s": round(self.wall_round_s, 6),
            "wall_split_s": {
                "admit": round(self.wall_admit_s, 6),
                "dispatch": round(self.wall_dispatch_s, 6),
                "sync": round(self.wall_sync_s, 6),
                "retire": round(self.wall_retire_s, 6),
            },
            "mesh_devices": (
                int(self.mesh.devices.size) if self.mesh is not None else 0
            ),
            "cache": {
                "hits": self.cache.hits,
                "misses": self.cache.misses,
                "warm_loads": self.cache.warm_loads,
            },
            "slot_banks": {
                "x".join(str(d) for d in sig): bank.metrics()
                for sig, bank in self.banks.items()
            },
        }
