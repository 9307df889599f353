"""Calibration traffic: presimulation of ``(theta, x)`` tuples, chunk by chunk.

Reads a traffic file with ``"generator": "presim"`` and its keys:

- ``leap``: the engine mode of the presimulation;
- ``in_flight``: chunks on the device at once, the one being fetched
  included (``presimulate`` dispatches every chunk before it fetches any);
- ``check_tuples``: tuples drawn from the window's chunks for the
  comparison with the reference;

and the configuration's ``workload`` (the production workload's
generator arguments), ``presim`` (``chunk`` thetas per chunk,
``n_replicates``, the calibrated ``protocol``), ``prior`` box and
``coef_box`` (the Eq.-1 coefficient box the classifier sees).

Each chunk is the body of ``repro.core.calibration.presimulate``: thetas
drawn from the prior and replica keys split from the chunk's key, every
theta simulated by ``calibration.simulate_coefficients`` and reduced to its
Eq.-1 coefficients. ``presimulate`` builds that body as a new jitted
function on every call, so calling it again traces and compiles again; the
generator builds the same body once, warms it in set-up, and drives it chunk
after chunk with the key chain ``presimulate`` uses (``key, sub =
split(key)`` per chunk). ``sims_per_s`` counts the tuples whose
coefficients reached the host.
"""
from __future__ import annotations

import collections
import time
from typing import Dict

import numpy as np

from harness import common, reference, roofline, scenarios


#: a sampled tuple whose coefficients lie further than this from the
#: reference's, in units of the coefficient box, counts as off
OFF = 1e-3


class Generator:
    spans = ("presim.dispatch", "presim.fetch")
    trace_seconds = 5.0  # a traced window holds some 300,000 device ops a second

    def __init__(self, cell, seed: int, tracer) -> None:
        self.cell = cell
        self.seed = int(seed)
        self.tracer = tracer
        self.leap = bool(cell.traffic["leap"])
        self.batch = int(cell.config["presim"]["chunk"])

    def setup(self, seconds: float) -> None:
        import jax

        from repro.core import calibration, engine
        from repro.core.workload import compile_campaign

        cfg = self.cell.config
        self.pair = scenarios.production_workload(**cfg["workload"])
        table = compile_campaign(*self.pair)
        spec = engine.SimSpec.from_table(table)
        mapper = calibration.make_theta_mapper(table, cfg["presim"]["protocol"])
        prior = calibration.PriorBox(
            low=jax.numpy.asarray(cfg["prior"]["low"], jax.numpy.float32),
            high=jax.numpy.asarray(cfg["prior"]["high"], jax.numpy.float32),
        )
        batch, leap = self.batch, self.leap
        n_rep = int(cfg["presim"]["n_replicates"])

        @jax.jit
        def chunk(k):
            kt, ks = jax.random.split(k)
            thetas = prior.from_unit(jax.random.uniform(kt, (batch, 3)))
            keys = jax.random.split(ks, batch)
            coefs = jax.vmap(
                lambda th, kk: calibration.simulate_coefficients(
                    spec, mapper(th), kk, n_replicates=n_rep, leap=leap,
                )
            )(thetas, keys)
            return thetas, coefs

        self._chunk = chunk
        self._split = jax.jit(lambda k: tuple(jax.random.split(k)))
        # warm-up: the window's own calls, from a key of another stream
        self.key = jax.numpy.asarray(common.key(self.seed, 2))
        jax.block_until_ready(self._dispatch())
        self.key = jax.numpy.asarray(common.key(self.seed, 1))

    def _dispatch(self):
        with self.tracer.span("presim.dispatch"):
            self.key, sub = self._split(self.key)
            return sub, self._chunk(sub)

    def window(self, seconds: float, opened) -> Dict:
        import jax

        from repro.core import engine

        traces0 = engine.bank_trace_count()
        depth = int(self.cell.traffic["in_flight"])
        flight = collections.deque()
        subs, coefs, fetched = [], [], []
        t0 = opened()
        flight.append(self._dispatch())
        while flight:
            while len(flight) < depth and time.perf_counter() - t0 < seconds:
                flight.append(self._dispatch())
            sub, out = flight.popleft()
            with self.tracer.span("presim.fetch"):
                _theta, x = jax.device_get(out)
            fetched.append(time.perf_counter())
            subs.append(sub)
            coefs.append(x)
            if time.perf_counter() - t0 >= seconds and not flight:
                break
        wall = time.perf_counter() - t0
        # a run that comes out slow shows here whether the chunks came back
        # evenly slower or with a stall between two of them
        gaps = np.diff([t0] + fetched)
        self.subs = np.asarray(jax.device_get(subs))
        self.coefs = coefs
        tuples = len(coefs) * self.batch
        return {
            "attempted": tuples,
            "seconds": wall,
            "metrics": {"sims_per_s": tuples / wall},
            "info": {"chunks": len(coefs), "tuples": tuples,
                     "chunk_gap_median_s": float(np.median(gaps)),
                     "chunk_gap_max_s": float(gaps.max()),
                     "bank_traces": engine.bank_trace_count() - traces0},
            "counters": {},
        }

    def release(self) -> None:
        del self._chunk

    def _sample(self):
        n = len(self.coefs) * self.batch
        k = min(int(self.cell.traffic["check_tuples"]), n)
        return sorted(common.rng(self.seed, 3).choice(n, k, replace=False))

    def check(self, control: bool = False) -> Dict:
        """Sampled tuples against the reference: the reference draws the
        chunk's thetas and replica keys from the chunk's key, simulates each
        theta and fits Eq. 1 by float64 least squares. A tuple's gap is the
        largest of its three coefficients' gaps in units of the coefficient
        box. The numbers compared are the median gap over the sample and
        the share of tuples off by more than ``OFF``: a tuple whose event
        schedule rounds one leap differently on the chip draws its later
        background noise from a different point of its key's stream, and
        is a different realization rather than a wrong one (``PERF.md``).
        With ``control`` the reference's simulation computed in bfloat16
        takes the program's place."""
        import jax

        cfg = self.cell.config
        legs = reference.read_campaign(*self.pair)
        low, high = (np.asarray(cfg["prior"][b], np.float64) for b in ("low", "high"))
        box = np.asarray(cfg["coef_box"]["high"]) - np.asarray(cfg["coef_box"]["low"])
        cpu = jax.devices("cpu")[0]
        gaps = []
        self.ref_ticks = []
        drawn = {}
        for flat in self._sample():
            c, row = divmod(int(flat), self.batch)
            if c not in drawn:
                with jax.default_device(cpu):
                    kt, ks = jax.random.split(jax.numpy.asarray(self.subs[c]))
                    u = np.asarray(jax.random.uniform(kt, (self.batch, 3)), np.float64)
                    keys = np.asarray(jax.random.split(ks, self.batch))
                drawn[c] = (u, keys)
            u, keys = drawn[c]
            theta = low + u[row] * (high - low)
            over, mu, sigma = reference.theta_params(legs, theta, cfg["presim"]["protocol"])
            sim = lambda dtype: reference.simulate(
                legs, overhead=over, bg_mu=mu, bg_sigma=sigma, key=keys[row],
                schedule="leap" if self.leap else "tick", dtype=dtype,
            )
            out = sim(np.float64)
            self.ref_ticks.append(out["ticks"])
            x_ref = reference.eq1_coefficients(legs, out)
            x = (reference.eq1_coefficients(legs, sim(reference.CONTROL)) if control
                 else np.asarray(self.coefs[c][row], np.float64))
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

    def work(self) -> Dict[str, float]:
        """The window's counted work (``harness.roofline``), with the mean
        ticks of the reference's sampled simulations standing for every
        tuple's (the program's timed path returns coefficients only)."""
        legs = reference.read_campaign(*self.pair)
        if not getattr(self, "ref_ticks", None):
            return {}
        n = len(self.coefs) * self.batch * int(self.cell.config["presim"]["n_replicates"])
        per_tick = roofline.flops_per_tick(legs.n_legs, legs.n_procs, legs.n_links)
        return {
            "flops": n * float(np.mean(self.ref_ticks)) * per_tick,
            "bytes": n * roofline.bytes_per_sim(legs.n_legs, legs.n_links),
        }
