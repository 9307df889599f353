"""What-if traffic: ``repro.Fleet.run`` over one deployment under many thetas.

Reads a traffic file with ``"generator": "fleet"`` and its keys:

- ``leap``: the fleet's engine mode;
- ``replicas``: rows per call, one theta drawn from the prior for each;
- ``in_flight``: calls on the device at once, the one being fetched
  included;
- ``check_rows``: rows drawn from the window's calls for the comparison
  with the reference;

and the configuration's ``workload`` (the production workload's generator
arguments), ``presim.protocol`` (the calibrated protocol), ``prior`` box and
``coef_box`` (the Eq.-1 coefficient box).

The fleet is ``Fleet.from_pairs`` over the one production workload, with
the program's default lowering and window. Each call draws its thetas and
replica keys on the device from the call's key, with one jitted function
warmed in set-up (``key, sub = split(key)`` per call, then the thetas and
keys from ``sub`` as ``presimulate`` draws them), builds per-replica
``SimParams`` ``[1, replicas, ...]`` as ``presimulate_bank`` builds them, and
calls ``Fleet.run(params, keys=...)``. Each call's whole ``SimResult`` is
fetched. ``sims_per_s`` counts the rows whose results reached the host.

Counters for the per-layer metrics, where the program has them: the
``repro.utils.spans`` table's change over the window (``fleet.run`` count
and seconds), and the engine's useful lane-steps against those it ran:
the sum of ``SimResult.steps`` over the window's rows, and for each call
the loop's trips (the largest ``ceil(steps / window)`` of its rows, since
every row is live from the first window) times the window times the rows.
A program without the span table or ``SimResult.steps`` gives none of them,
and their readers return nothing.
"""
from __future__ import annotations

import collections
import time
from typing import Dict

import numpy as np

from harness import common, reference, scenarios

#: a sampled row whose coefficients lie further than this from the
#: reference's, in units of the coefficient box, counts as off
OFF = 1e-3
RUN_SPAN = "fleet.run"


def _span_table():
    """The program's span table, or ``None`` where it has none."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    return spans


class Generator:
    spans = ("fleet.run", "fleet.params", "engine.dispatch", "client.fetch")
    trace_seconds = 10.0

    def __init__(self, cell, seed: int, tracer) -> None:
        self.cell = cell
        self.seed = int(seed)
        self.tracer = tracer
        self.leap = bool(cell.traffic["leap"])
        self.rows = int(cell.traffic["replicas"])

    def setup(self, seconds: float) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core import calibration, engine
        from repro.core.fleet import Fleet

        cfg = self.cell.config
        self.pair = scenarios.production_workload(**cfg["workload"])
        self.fleet = Fleet.from_pairs([self.pair], leap=self.leap)
        bank = self.fleet.bank
        pid = bank.protocol_names.index(cfg["presim"]["protocol"])
        mask = jnp.asarray(bank.protocol_id == pid)  # [1, T]
        keep = jnp.asarray(bank.keep_frac)  # [1, T]
        link_valid = jnp.asarray(bank.link_valid, jnp.float32)  # [1, L]
        prior = calibration.PriorBox(
            low=jnp.asarray(cfg["prior"]["low"], jnp.float32),
            high=jnp.asarray(cfg["prior"]["high"], jnp.float32),
        )
        rows = self.rows

        @jax.jit
        def draw(k):
            k, sub = jax.random.split(k)
            kt, ks = jax.random.split(sub)
            thetas = prior.from_unit(jax.random.uniform(kt, (rows, 3)))[None]  # [1, R, 3]
            keys = jax.random.split(ks, rows).reshape(1, rows, 2)
            params = engine.SimParams(
                keep_frac=jnp.where(mask[:, None, :], 1.0 - thetas[..., 0:1],
                                    keep[:, None, :]),
                bg_mu=thetas[..., 1:2] * link_valid[:, None, :],
                bg_sigma=thetas[..., 2:3] * link_valid[:, None, :],
            )
            return k, sub, params, keys

        self._draw = draw
        # the engine's window on this backend, capped as simulate_bank caps
        # it at the bank's tick bound (the next power of two)
        cap = 1 << max(0, int(np.max(bank.max_ticks)) - 1).bit_length()
        self.window_ticks = min(engine.default_tick_window(self.leap), cap)
        # warm-up: the window's own calls, from a key of another stream
        self.key = jnp.asarray(common.key(self.seed, 2))
        jax.device_get(self._dispatch()[1])
        self.key = jnp.asarray(common.key(self.seed, 1))

    def _dispatch(self):
        self.key, sub, params, keys = self._draw(self.key)
        return sub, self.fleet.run(params, keys=keys)

    def window(self, seconds: float, opened) -> Dict:
        import jax

        from repro.core import engine

        spans = _span_table()
        traces0 = engine.bank_trace_count()
        depth = int(self.cell.traffic["in_flight"])
        flight = collections.deque()
        subs, results = [], []
        before = spans.snapshot() if spans else None
        t0 = opened()
        flight.append(self._dispatch())
        while flight:
            while len(flight) < depth and time.perf_counter() - t0 < seconds:
                flight.append(self._dispatch())
            sub, out = flight.popleft()
            with self.tracer.span("client.fetch"):
                res = jax.device_get(out)
            subs.append(sub)
            results.append(res)
            if time.perf_counter() - t0 >= seconds and not flight:
                break
        wall = time.perf_counter() - t0
        after = spans.snapshot() if spans else None
        self.subs = np.asarray(jax.device_get(subs))
        self.results = results
        sims = len(results) * self.rows
        counters = {}
        if after is not None and RUN_SPAN in after:
            n0, s0 = before.get(RUN_SPAN, (0, 0.0))
            counters["fleet.run.count"] = float(after[RUN_SPAN][0] - n0)
            counters["fleet.run.seconds"] = float(after[RUN_SPAN][1] - s0)
        steps = [getattr(r, "steps", None) for r in results]
        if steps and all(s is not None for s in steps):
            w = self.window_ticks
            counters["engine.steps"] = float(sum(int(np.sum(s)) for s in steps))
            counters["engine.lane_steps"] = float(sum(
                int(np.max(-(-np.asarray(s) // w))) * w * np.asarray(s).size for s in steps))
        return {
            "attempted": sims,
            "seconds": wall,
            "metrics": {"sims_per_s": sims / wall},
            "info": {"calls": len(results), "sims": sims, "window_ticks": self.window_ticks,
                     "bank_traces": engine.bank_trace_count() - traces0},
            "counters": counters,
        }

    def release(self) -> None:
        del self._draw

    def _sample(self):
        n = len(self.results) * self.rows
        k = min(int(self.cell.traffic["check_rows"]), n)
        return sorted(common.rng(self.seed, 3).choice(n, k, replace=False))

    def _program_coefficients(self, res, row: int) -> np.ndarray:
        """Eq. 1 fitted in float64 to one row of the program's result: its
        finished remote accesses (padding carries another profile)."""
        from repro.core.workload import ProfileTag

        pick = lambda a: np.asarray(a, np.float64)[0, row]
        m = np.asarray(res.done)[0, row] & (np.asarray(res.profile)[0, row] == ProfileTag.REMOTE)
        X = np.stack([pick(res.size_mb), pick(res.conth_mb), pick(res.conpr_mb)], axis=1)[m]
        return np.linalg.lstsq(X, pick(res.transfer_time)[m], rcond=None)[0]

    def check(self, control: bool = False) -> Dict:
        """Sampled rows against the reference: the reference draws the
        call's thetas and replica keys from the call's key, simulates the
        row's theta with the row's key in float64 on the same schedule and
        fits Eq. 1 by least squares. A row's gap is the largest of its three
        coefficients' gaps in units of the coefficient box. The numbers
        compared are the median gap and the share of rows off by more than
        ``OFF``, as for the presimulation (``PERF.md``: a row whose event
        schedule rounds one leap differently on the chip is another
        realization, not a wrong one). With ``control`` the reference's
        simulation computed in bfloat16 takes the program's place."""
        import jax

        cfg = self.cell.config
        legs = reference.read_campaign(*self.pair)
        low, high = (np.asarray(cfg["prior"][b], np.float64) for b in ("low", "high"))
        box = np.asarray(cfg["coef_box"]["high"]) - np.asarray(cfg["coef_box"]["low"])
        cpu = jax.devices("cpu")[0]
        gaps = []
        drawn = {}
        for flat in self._sample():
            c, row = divmod(int(flat), self.rows)
            if c not in drawn:
                with jax.default_device(cpu):
                    kt, ks = jax.random.split(jax.numpy.asarray(self.subs[c]))
                    u = np.asarray(jax.random.uniform(kt, (self.rows, 3)), np.float64)
                    keys = np.asarray(jax.random.split(ks, self.rows))
                drawn[c] = (u, keys)
            u, keys = drawn[c]
            theta = low + u[row] * (high - low)
            over, mu, sigma = reference.theta_params(legs, theta, cfg["presim"]["protocol"])
            sim = lambda dtype: reference.simulate(
                legs, overhead=over, bg_mu=mu, bg_sigma=sigma, key=keys[row],
                schedule="leap" if self.leap else "tick", dtype=dtype,
            )
            x_ref = reference.eq1_coefficients(legs, sim(np.float64))
            x = (reference.eq1_coefficients(legs, sim(reference.CONTROL)) if control
                 else self._program_coefficients(self.results[c], row))
            gap = np.abs(x - x_ref) / box
            gaps.append(float(np.max(gap)) if np.isfinite(gap).all() else common.BIG)
        gaps = np.asarray(gaps)
        return {
            "values": {"coef_gap_median": float(np.median(gaps)),
                       "coef_off_share": float(np.mean(gaps > OFF))},
            "failed": int(np.sum(gaps >= common.BIG)),
            "missing": 0,
            "units": len(gaps),
            "info": {"coef_gap_max": float(gaps.max())},
        }
