"""Access-profile search traffic: ``repro.core.scheduler.ProfileSearch`` over
one campaign's super-table, searches back to back, one generation at a time.

Reads a traffic file with ``"generator": "profile_search"`` and its key:

- ``check_members``: (generation, member) pairs drawn from the window's
  generations for the comparison with the reference;

and the configuration's ``workload`` (the production workload's generator
arguments), ``links`` (the links added to its grid), ``candidates`` (the
local SE), ``search`` (population, elites, mutation probability,
generations a search, fitness weights, simulations a member) and
``theta`` (the calibrated protocol and the prior box the searches' thetas
cover).

Set-up builds the candidates with ``scheduler.profile_candidates`` and the
super-table with ``scheduler.build_super_table``, as a user would. Search
``s`` of a run takes theta from point ``s`` of the R3 low-discrepancy
sequence over the prior box (:func:`theta_of`; the same thetas in every
run, so that the searches' costs, which theta moves, are the same in every
run), and its key from the run's key chain (``chain, key = split(chain)``).
It maps theta to ``SimParams`` (the WebDAV legs' overhead, and ``(mu,
sigma)`` on every link), builds ``ProfileSearch(st, params, ...)`` and runs
it as ``optimize_profiles`` does: the first population drawn from the key,
then one generation at a time, each generation's fitness on the host
before the next is dispatched. The window ends with the generation in
flight when its time is up; ``sims_per_s`` counts the members whose
fitness reached the host.

The check reads the sampled members' legs from the simulations of the
window's own generations (``ProfileSearch.generation`` returns them on the
device; they are fetched after the window).

Counters for the per-layer metrics, where the program has them: the
``repro.utils.spans`` table's change over the window (the
``profiles.generation`` span, the ``profiles.members``,
``profiles.enabled_legs`` and ``profiles.traces`` counters), and the
engine's useful lane-steps against those it ran, from each generation's
member steps as the fleet generator reads them. A program without them
gives none, and their readers return nothing.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from harness import common, parallel, profiles, reference, roofline, scenarios

#: a sampled member whose widest gap to the reference exceeds this counts
#: as off: a per-leg gap is the transfer time's in units of the reference's
#: (at least one tick), or a concurrency accumulator's in units of the
#: leg's size; the member's fitness gap is in units of the reference's
OFF = 1e-3
GEN_SPAN = "profiles.generation"
COUNTERS = ("profiles.members", "profiles.enabled_legs", "profiles.traces")
FIELDS = ("transfer_time", "conth_mb", "conpr_mb", "start_tick", "done")
#: the R3 sequence's steps: the inverse powers of the root of x^4 = x + 1
_PHI3 = 1.2207440846057596
R3 = np.array([_PHI3 ** -1, _PHI3 ** -2, _PHI3 ** -3])


def theta_of(s: int, low, high) -> np.ndarray:
    """Theta of a run's search ``s``: point ``s`` of the R3 sequence
    (Roberts, 2018) over the box ``[low, high]``, float32."""
    u = (0.5 + (s + 1) * R3) % 1.0
    low, high = np.asarray(low, np.float64), np.asarray(high, np.float64)
    return (low + (high - low) * u).astype(np.float32)


def _span_table():
    """The program's span table, or ``None`` where it has none."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    return spans


class Generator:
    spans = (GEN_SPAN,)
    trace_seconds = 10.0

    def __init__(self, cell, seed: int, tracer) -> None:
        # the program's search; a program without it fails here, at once
        from repro.core.scheduler import ProfileSearch, profile_candidates

        self._search_cls, self._builder = ProfileSearch, profile_candidates
        self.cell = cell
        self.seed = int(seed)
        self.tracer = tracer
        self.opts = dict(cell.config["search"])
        self.rows = int(self.opts["population"])

    def setup(self, seconds: float) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core import engine, scheduler

        cfg = self.cell.config
        grid, self.campaign = scenarios.production_workload(**cfg["workload"])
        self.grid = profiles.add_links(grid, cfg["links"])
        local = cfg["candidates"]["local_storage_element"]
        accesses = self._builder(self.grid, self.campaign, local)
        self.cands = profiles.candidates(self.campaign, local)
        self.st = scheduler.build_super_table(
            self.grid, [job.worker_node for job in self.campaign.jobs], accesses)
        table = self.st.table
        self._calibrated = table.protocol_id == table.protocol_names.index(
            cfg["theta"]["protocol"])
        self._keep = np.asarray(table.keep_frac, np.float32)
        self._n_links = table.links.n_links
        self.window_ticks = None
        # warm-up: a search's first generation, from a key of another stream
        self.thetas: List[np.ndarray] = []
        self.chain = jnp.asarray(common.key(self.seed, 2))
        search, key, pop = self._start_search()
        key, _, fit, res = search.generation(pop, key)
        jax.block_until_ready(fit)
        self.fleet = search.fleet
        self.leap = bool(search.fleet.leap)
        if res.steps is not None:
            cap = 1 << max(0, int(np.max(search.fleet.bank.max_ticks)) - 1).bit_length()
            self.window_ticks = min(engine.default_tick_window(self.leap), cap)
        self.chain = jnp.asarray(common.key(self.seed, 1))

    def _params(self, theta):
        import jax.numpy as jnp

        from repro.core import engine

        keep = np.where(self._calibrated, np.float32(1.0) - theta[0], self._keep)
        links = lambda v: np.full((self._n_links,), v, np.float32)
        return engine.SimParams(keep_frac=jnp.asarray(keep, jnp.float32),
                                bg_mu=jnp.asarray(links(theta[1])),
                                bg_sigma=jnp.asarray(links(theta[2])))

    def _start_search(self):
        """A new search, as ``optimize_profiles`` starts one: its theta, key
        and first population."""
        import jax

        theta_cfg = self.cell.config["theta"]
        theta = theta_of(len(self.thetas), theta_cfg["low"], theta_cfg["high"])
        self.thetas.append(theta)
        o = self.opts
        search = self._search_cls(
            self.st, self._params(theta), population=self.rows, elite=int(o["elite"]),
            mutate_p=float(o["mutate_p"]), makespan_weight=float(o["makespan_weight"]),
            mean_weight=float(o["mean_weight"]), antithetic_sims=int(o["antithetic_sims"]),
        )
        self.chain, key = jax.random.split(self.chain)
        key, k0 = jax.random.split(key)
        pop = jax.random.randint(k0, (self.rows, self.st.n_access), 0, self.st.n_cand)
        return search, key, pop

    def window(self, seconds: float, opened) -> Dict:
        import jax

        from repro.core import engine

        spans = _span_table()
        traces0 = engine.bank_trace_count()
        generations = int(self.opts["generations"])
        self.thetas = []
        # per generation: its search, chain key and population (device),
        # fitness (host) and simulations (device)
        self.gens: List[tuple] = []
        before = spans.snapshot() if spans else None
        t0 = opened()
        ends = [t0]  # each generation's end on the host
        g = generations
        while ends[-1] - t0 < seconds:
            if g == generations:
                search, key, pop = self._start_search()
                g = 0
            key_in = key
            key, next_pop, fit, res = search.generation(pop, key)
            ends.append(time.perf_counter())
            self.gens.append((len(self.thetas) - 1, key_in, pop, fit, res))
            pop, g = next_pop, g + 1
        wall = time.perf_counter() - t0
        after = spans.snapshot() if spans else None
        sims = len(self.gens) * self.rows
        counters = {}
        if after is not None and GEN_SPAN in after:
            delta = lambda name, i=0: (after.get(name, (0, 0.0))[i]
                                       - before.get(name, (0, 0.0))[i])
            counters[GEN_SPAN + ".count"] = float(delta(GEN_SPAN))
            counters[GEN_SPAN + ".seconds"] = float(delta(GEN_SPAN, 1))
            for name in COUNTERS:
                counters[name] = float(delta(name))
            counters["profiles.legs"] = float(self.st.table.n_legs)
        if self.window_ticks:
            w = self.window_ticks
            steps = [np.sum(np.asarray(jax.device_get(r.steps)), axis=0) for *_, r in self.gens]
            counters["engine.steps"] = float(sum(int(np.sum(s)) for s in steps))
            counters["engine.lane_steps"] = float(sum(
                int(np.max(-(-s // w))) * w * s.size for s in steps))
        self.search_traces = counters.get("profiles.traces", 0.0)
        return {
            "attempted": sims,
            "seconds": wall,
            "metrics": {"sims_per_s": sims / wall},
            "info": {"searches": len(self.thetas), "generations": len(self.gens),
                     "sims": sims, "window_ticks": self.window_ticks,
                     "generation_s": [round(b - a, 6) for a, b in zip(ends, ends[1:])],
                     "search_traces": self.search_traces,
                     "bank_traces": engine.bank_trace_count() - traces0},
            "counters": counters,
        }

    def release(self) -> None:
        """Nothing to release: the check reads the window's simulations."""

    def _sample(self):
        n = len(self.gens) * self.rows
        k = min(int(self.cell.traffic["check_members"]), n)
        return sorted(common.rng(self.seed, 3).choice(n, k, replace=False))

    def _members(self):
        """The sampled members: ``(theta, assignment, key, window fitness,
        legs)`` each, the key rebuilt as the search draws it (the
        generation's ``split(key, 3)[1]``, split once per simulation of a
        member, then once per member), and the legs the member's first
        simulation in the window gave, in super-table leg order."""
        import jax

        n_sims = int(self.opts["antithetic_sims"])
        cpu = jax.devices("cpu")[0]
        out, drawn = [], {}
        for flat in self._sample():
            g, m = divmod(int(flat), self.rows)
            s, key, pop, fit, res = self.gens[g]
            if g not in drawn:
                with jax.default_device(cpu):
                    ke = jax.random.split(jax.device_put(key, cpu), 3)[1]
                    k = jax.random.split(ke, n_sims)[0]
                    keys = np.asarray(jax.random.split(k, self.rows))
                fields = jax.device_get({f: getattr(res, f) for f in FIELDS})
                drawn[g] = (keys, np.asarray(jax.device_get(pop)),
                            {f: np.asarray(v[0], np.float64) for f, v in fields.items()})
            keys, assign, legs = drawn[g]
            out.append((self.thetas[s].astype(np.float64), assign[m], keys[m],
                        float(fit[m]), {f: v[m] for f, v in legs.items()}))
        return out

    def _fitness(self, out) -> float:
        """The search's fitness of one simulation, in float64."""
        o = self.opts
        t_end = out["start_tick"] + out["transfer_time"]
        n = max(len(t_end), 1)
        return (float(o["makespan_weight"]) * float(np.max(t_end))
                + float(o["mean_weight"]) * float(np.sum(out["transfer_time"])) / n
                + 1e6 * float(np.sum(~np.asarray(out["done"], bool))))

    def check(self, control: bool = False) -> Dict:
        """Sampled members against the reference: each member's realized
        campaign (its picked candidates as an ordinary campaign, rebuilt by
        ``harness.profiles``) simulated in float64 on the schedule the
        search's fleet reports, with the member's theta and key. Per enabled
        leg, the transfer time, ``conth_mb`` and ``conpr_mb`` of the
        member's simulation in the window are compared with the
        reference's, and the member's fitness from the window with the
        reference's. The numbers compared are the median over members of
        the widest gap, and the share of members off by more than ``OFF``:
        the f32 tick engine ends a leg one tick off the reference in some
        draws (ROADMAP D9), and a member with such a leg is off. With
        ``control`` the reference computed in bfloat16 takes the program's
        place."""
        members = self._members()
        legs_of = lambda assign: np.concatenate([
            row[row >= 0] for row in
            self.st.cand_legs[np.arange(self.st.n_access), assign % self.st.cands_per_access]])
        schedule = "leap" if self.leap else "tick"
        protocol = self.cell.config["theta"]["protocol"]
        legs, tasks = [], []
        for theta, assign, key, _, _ in members:
            lg = reference.read_campaign(self.grid,
                                         profiles.realized(self.campaign, self.cands, assign))
            over, mu, sigma = reference.theta_params(lg, theta, protocol)
            kw = dict(overhead=over, bg_mu=mu, bg_sigma=sigma, key=key, schedule=schedule)
            legs.append(lg)
            tasks.append((lg, dict(kw, dtype=np.float64)))
            if control:
                tasks.append((lg, dict(kw, dtype=reference.CONTROL)))
        outs = parallel.simulate_all(tasks)
        refs = outs[::2] if control else outs
        self.ref_work = [(ref["ticks"], lg.n_legs, lg.n_procs, lg.n_links)
                         for ref, lg in zip(refs, legs)]
        gaps = []
        for r, ((_, assign, _, fit, prog), lg, ref) in enumerate(zip(members, legs, refs)):
            if control:
                got = outs[2 * r + 1]
                fit = self._fitness(got)
            else:
                got = {f: v[legs_of(assign)] for f, v in prog.items()}
            if not np.array_equal(np.asarray(got["done"], bool), ref["done"]):
                gaps.append(common.BIG)
                continue
            leg_gap = np.concatenate([
                np.abs(got["transfer_time"] - ref["transfer_time"])
                / np.maximum(ref["transfer_time"], 1.0),
                np.abs(got["conth_mb"] - ref["conth_mb"]) / lg.size,
                np.abs(got["conpr_mb"] - ref["conpr_mb"]) / lg.size,
            ])
            f_ref = self._fitness(ref)
            gap = max(float(np.max(leg_gap)), abs(fit - f_ref) / max(abs(f_ref), 1.0))
            gaps.append(gap if np.isfinite(gap) else common.BIG)
        gaps = np.asarray(gaps)
        return {
            "values": {"leg_gap_median": float(np.median(gaps)),
                       "member_off_share": float(np.mean(gaps > OFF)),
                       "search_traces": float(getattr(self, "search_traces", 0.0))},
            "failed": int(np.sum(gaps >= common.BIG)),
            "missing": 0,
            "units": len(gaps),
            "info": {"leg_gap_max": float(gaps.max())},
        }

    def work(self) -> Dict[str, float]:
        """The window's counted work (``harness.roofline``): each sampled
        member's realized campaign at the ticks the reference stepped, the
        mean standing for every member of the window (the search returns
        fitness only)."""
        if not getattr(self, "ref_work", None):
            return {}
        n = len(self.gens) * self.rows * int(self.opts["antithetic_sims"])
        flops = [t * roofline.flops_per_tick(T, P, L) for t, T, P, L in self.ref_work]
        nbytes = [roofline.bytes_per_sim(T, L) for _, T, _, L in self.ref_work]
        return {"flops": n * float(np.mean(flops)), "bytes": n * float(np.mean(nbytes))}
