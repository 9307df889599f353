"""Beyond-paper: data-access-profile optimization on top of the simulator.

The paper's stated future work is "evolutionary optimization of data access
patterns in bags of jobs with the objective to minimize the joint data
transfer time", with fitness evaluated on GDAPS. This module implements it:

- Every file access lists *candidate* realizations (profile x replica source).
- All candidates of all accesses are compiled into one static **super-table**
  (so shapes stay fixed for jit/vmap), and an assignment enables exactly one
  candidate per access via the engine's ``enabled`` mask.
- A simple (mu + lambda) evolutionary strategy mutates assignments; fitness is
  the simulated campaign makespan (optionally + mean transfer time), evaluated
  for the whole population in one banked batch of simulations.

:func:`profile_candidates` gives every access of a campaign the paper's
three realizations; :class:`ProfileSearch` is the search over one
super-table, its generation program traced once per super-table and search
settings; :func:`optimize_profiles` runs one search, a generation at a time.

This is the piece that "reduces job wait times": it picks, per job, whichever
combination of data-placement / stage-in / remote access avoids the currently
bottlenecked links.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import SimParams, SimSpec, simulate
from repro.core.fleet import Fleet, _cache_get, _cache_put
from repro.core.topology import Grid
from repro.core.workload import (
    AccessProfileKind,
    Campaign,
    FileAccess,
    Job,
    LegTable,
    Replica,
    compile_campaign,
)
from repro.utils import spans

__all__ = [
    "CandidateAccess",
    "SuperTable",
    "profile_candidates",
    "realized_campaign",
    "build_super_table",
    "super_fleet",
    "evaluate_population",
    "ProfileSearch",
    "optimize_profiles",
]

#: counter of traces of a search's program (counted at trace time only)
SEARCH_TRACES = "profiles.traces"
#: the protocols of the paper's three access profiles (Sec. 3 and Sec. 5)
REMOTE_PROTOCOL = "webdav"
STAGEIN_PROTOCOL = "xrdcp"
PLACEMENT_PROTOCOL = "gsiftp"


@dataclasses.dataclass(frozen=True)
class CandidateAccess:
    """One file access with its candidate realizations."""

    job: int  # job index within the bag
    candidates: Tuple[FileAccess, ...]


def profile_candidates(
    grid: Grid,
    campaign: Campaign,
    local_storage_element: str,
) -> List[CandidateAccess]:
    """The paper's three access profiles as candidates of every access of
    ``campaign``, in job order then access order. Candidate ``k`` of each
    access reads the same replica, released at the same tick:

    0. remote access from the replica's SE to the job's worker node
       (:data:`REMOTE_PROTOCOL`; the threads of one job share one stream);
    1. stage-in over that same link (:data:`STAGEIN_PROTOCOL`, own process);
    2. placement to ``local_storage_element`` (:data:`PLACEMENT_PROTOCOL`),
       then stage-in from there to the worker node;
       an access whose replica already sits there has no placement.

    ``grid`` must hold every link these use."""
    accesses: List[CandidateAccess] = []
    for j, job in enumerate(campaign.jobs):
        wn = job.worker_node
        for acc in job.accesses:
            src = acc.replica.storage_element
            rep = Replica(acc.replica.size_mb, src)
            t0 = acc.release_tick
            grid.link(src, wn)  # raises on a missing link
            cands = [
                FileAccess(rep, AccessProfileKind.REMOTE, REMOTE_PROTOCOL, t0),
                FileAccess(rep, AccessProfileKind.STAGE_IN, STAGEIN_PROTOCOL, t0),
            ]
            if src != local_storage_element:
                grid.link(src, local_storage_element)
                grid.link(local_storage_element, wn)
                cands.append(FileAccess(
                    rep, AccessProfileKind.DATA_PLACEMENT, PLACEMENT_PROTOCOL, t0,
                    local_storage_element=local_storage_element,
                    stagein_protocol=STAGEIN_PROTOCOL,
                ))
            accesses.append(CandidateAccess(job=j, candidates=tuple(cands)))
    return accesses


def realized_campaign(
    accesses: Sequence[CandidateAccess],
    worker_nodes: Sequence[str],
    assign: Sequence[int],
) -> Campaign:
    """The ordinary campaign one assignment of a bag stands for: job ``j``
    on ``worker_nodes[j]`` makes, in order, the chosen candidate of each of
    its accesses (``assign[i]`` modulo access ``i``'s candidate count, as
    the super-table reads it). Compiled on the super-table's grid, it
    simulates as that assignment's member of the super-table does."""
    n_jobs = max(a.job for a in accesses) + 1
    chosen: List[List[FileAccess]] = [[] for _ in range(n_jobs)]
    for acc, k in zip(accesses, np.asarray(assign)):
        chosen[acc.job].append(acc.candidates[int(k) % len(acc.candidates)])
    jobs = tuple(
        Job(worker_node=worker_nodes[j], accesses=tuple(a), name=f"job{j}")
        for j, a in enumerate(chosen)
    )
    return Campaign(jobs, name="realized")


class SuperTable(NamedTuple):
    spec: SimSpec
    table: LegTable
    # candidate -> legs mapping (ragged, padded with -1): [n_access, n_cand, 2]
    cand_legs: np.ndarray
    n_access: int
    n_cand: int
    cands_per_access: np.ndarray  # [n_access] i64 actual candidate counts


def build_super_table(
    grid: Grid,
    worker_nodes: Sequence[str],
    accesses: Sequence[CandidateAccess],
    *,
    max_ticks: Optional[int] = None,
) -> SuperTable:
    """Compile the union of all candidates into one leg table.

    Candidate k of access i maps to 1 (remote/stage-in) or 2 (placement)
    legs; ``cand_legs[i, k]`` holds their leg ids (-1 padding).
    """
    n_jobs = max(a.job for a in accesses) + 1
    jobs_accs: List[List[FileAccess]] = [[] for _ in range(n_jobs)]
    # interleave all candidates as real accesses, remembering per job which
    # (access, candidate) each appended access came from — compile_campaign
    # assigns observation ids by walking jobs in order, then each job's
    # accesses in insertion order, so this per-job record *is* the obs order
    per_job_pairs: List[List[Tuple[int, int]]] = [[] for _ in range(n_jobs)]
    for i, acc in enumerate(accesses):
        for k, cand in enumerate(acc.candidates):
            jobs_accs[acc.job].append(cand)
            per_job_pairs[acc.job].append((i, k))
    jobs = tuple(
        Job(worker_node=worker_nodes[j], accesses=tuple(a), name=f"job{j}")
        for j, a in enumerate(jobs_accs)
    )
    campaign = Campaign(jobs, name="super")
    table = compile_campaign(grid, campaign)

    n_access = len(accesses)
    n_cand = max(len(a.candidates) for a in accesses)
    cand_legs = np.full((n_access, n_cand, 2), -1, np.int64)
    # single pass over the compile-order obs walk: candidate (i, k) consumes
    # one observation (remote / stage-in -> 1 leg) or two (placement -> the
    # SE->SE leg then its dependent stage-in leg), each mapping to one leg
    legs_by_obs: List[List[int]] = [[] for _ in range(int(table.obs_id.max()) + 1)]
    for leg, obs in enumerate(table.obs_id):
        legs_by_obs[int(obs)].append(leg)
    obs_ptr = 0
    for pairs in per_job_pairs:
        for (i, k) in pairs:
            cand = accesses[i].candidates[k]
            n_obs_for_cand = (
                2 if cand.profile is AccessProfileKind.DATA_PLACEMENT else 1
            )
            legs: List[int] = []
            for _ in range(n_obs_for_cand):
                legs.extend(legs_by_obs[obs_ptr])
                obs_ptr += 1
            for s, leg in enumerate(legs[:2]):
                cand_legs[i, k, s] = leg
    spec = SimSpec.from_table(table, max_ticks=max_ticks)
    return SuperTable(
        spec=spec,
        table=table,
        cand_legs=cand_legs,
        n_access=n_access,
        n_cand=n_cand,
        cands_per_access=np.array([len(a.candidates) for a in accesses], np.int64),
    )


def _assignment_mask(st: SuperTable, assign: jax.Array) -> jax.Array:
    """assign: [n_access] int -> enabled mask over legs."""
    n_legs = st.table.n_legs
    assign = assign % jnp.asarray(st.cands_per_access)  # ragged-safe
    cand_legs = jnp.asarray(st.cand_legs)  # [A, K, 2]
    chosen = jnp.take_along_axis(
        cand_legs, assign[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [A, 2]
    flat = chosen.reshape(-1)
    onehot = jnp.zeros((n_legs + 1,), bool).at[jnp.where(flat >= 0, flat, n_legs)].set(True)
    return onehot[:n_legs]


def _mask_fitness(
    res, mask: jax.Array, makespan_weight: float, mean_weight: float
) -> jax.Array:
    """Fitness of simulated legs under an enabled mask; all reductions run
    over the trailing leg axis, so one formula scores a single assignment
    ([T] fields) or a whole population batch ([B, T] fields)."""
    m = mask.astype(jnp.float32)
    t_end = res.start_tick + res.transfer_time
    makespan = jnp.max(t_end * m, axis=-1)
    mean_t = jnp.sum(res.transfer_time * m, axis=-1) / jnp.maximum(
        jnp.sum(m, axis=-1), 1.0
    )
    # unfinished legs dominate the penalty
    unfinished = jnp.sum((~res.done) & (m > 0), axis=-1)
    return (
        makespan_weight * makespan
        + mean_weight * mean_t
        + 1e6 * unfinished.astype(jnp.float32)
    )


def _fitness(
    st: SuperTable,
    base_params: SimParams,
    assign: jax.Array,
    key: jax.Array,
    makespan_weight: float = 1.0,
    mean_weight: float = 0.1,
) -> jax.Array:
    mask = _assignment_mask(st, assign)
    params = SimParams(
        keep_frac=base_params.keep_frac,
        bg_mu=base_params.bg_mu,
        bg_sigma=base_params.bg_sigma,
        enabled=mask,
    )
    res = simulate(st.spec, params, key)
    return _mask_fitness(res, mask, makespan_weight, mean_weight)


def super_fleet(st: SuperTable) -> Fleet:
    """The single-scenario :class:`~repro.core.fleet.Fleet` view of a
    super-table (memoized in the fleet-level compile cache per table
    identity): population fitness evaluation is a bank of one scenario whose
    ``B`` candidate ``enabled`` masks ride the replica axis."""
    return Fleet.from_table(
        st.table, name="super", max_ticks=int(st.spec.max_ticks)
    )


def evaluate_population(
    st: SuperTable,
    base_params: SimParams,
    pop: jax.Array,  # [B, n_access] candidate assignments
    keys: jax.Array,  # [B, 2]
    *,
    makespan_weight: float = 1.0,
    mean_weight: float = 0.1,
    fleet: Optional[Fleet] = None,
) -> jax.Array:
    """Fitness of a whole population in **one banked batch**: the population
    is a degenerate scenario fleet — every member shares the super-table
    spec and differs only in its ``enabled`` mask — so the whole population
    runs as one :meth:`Fleet.run` dispatch ([1, B, ...]: the masks are
    per-replica params of the single scenario) instead of one ``simulate``
    call per assignment."""
    return _run_population(st, base_params, pop, keys, makespan_weight,
                           mean_weight, fleet)[0]


def _run_population(st, base_params, pop, keys, makespan_weight, mean_weight, fleet):
    """:func:`evaluate_population`'s fitness ``[B]`` and the members'
    simulations (:class:`SimResult` of ``[B, ...]`` fields)."""
    masks = jax.vmap(functools.partial(_assignment_mask, st))(pop)  # [B, T]
    fleet = fleet if fleet is not None else super_fleet(st)
    params = SimParams(
        keep_frac=jnp.asarray(base_params.keep_frac)[None],  # [1, T] shared
        bg_mu=jnp.asarray(base_params.bg_mu)[None],
        bg_sigma=jnp.asarray(base_params.bg_sigma)[None],
        enabled=masks[None],  # [1, B, T]: one mask per replica
    )
    res = fleet.run(params, keys=keys[None])
    res = jax.tree.map(lambda a: a[0], res)  # back to [B, ...]
    return _mask_fitness(res, masks, makespan_weight, mean_weight), res


def _search_program(
    st: SuperTable,
    fleet: Fleet,
    population: int,
    elite: int,
    mutate_p: float,
    makespan_weight: float,
    mean_weight: float,
    antithetic_sims: int,
) -> Callable:
    """The jitted generation program of a search, one per super-table and
    search settings, kept in the fleet-level compile cache beside the
    super-table's bank (and dropped with it)."""
    cache_key = ("profile_search", id(st), population, elite, float(mutate_p),
                 float(makespan_weight), float(mean_weight), antithetic_sims)
    hit = _cache_get(cache_key)
    if hit is not None and hit[0] is st:
        return hit[1]
    n_access, n_cand = st.n_access, st.n_cand

    def evaluate(base_params: SimParams, pop: jax.Array, key: jax.Array):
        keys = jax.random.split(key, antithetic_sims)

        def per_sim(k):
            ks = jax.random.split(k, pop.shape[0])
            return _run_population(st, base_params, pop, ks, makespan_weight,
                                   mean_weight, fleet)

        fit, res = jax.vmap(per_sim)(keys)
        return jnp.mean(fit, axis=0), res

    def next_gen(pop: jax.Array, fit: jax.Array, key: jax.Array) -> jax.Array:
        order = jnp.argsort(fit)
        elites = pop[order[:elite]]
        k1, k2, k3 = jax.random.split(key, 3)
        parents = elites[jax.random.randint(k1, (population - elite,), 0, elite)]
        flip = jax.random.uniform(k2, parents.shape) < mutate_p
        rand = jax.random.randint(k3, parents.shape, 0, n_cand)
        children = jnp.where(flip, rand, parents)
        return jnp.concatenate([elites, children], axis=0)

    legs_per_cand = jnp.asarray((st.cand_legs >= 0).sum(-1), jnp.int32)  # [A, K]

    # repro: allow[jit-cache] -- built once per super-table and settings, then kept in the fleet compile cache below
    @jax.jit
    def generation(base_params: SimParams, pop: jax.Array, key: jax.Array):
        spans.count(SEARCH_TRACES)  # executes at trace time only
        key, ke, kn = jax.random.split(key, 3)
        fit, res = evaluate(base_params, pop, ke)
        chosen = pop % jnp.asarray(st.cands_per_access)
        enabled = jnp.sum(jnp.take_along_axis(legs_per_cand[None], chosen[..., None], -1))
        return key, next_gen(pop, fit, kn), fit, enabled, res

    _cache_put(cache_key, (st, generation))
    return generation


class ProfileSearch:
    """A (mu + lambda) evolutionary search over the candidate assignments of
    one super-table, under fixed ``base_params`` (the campaign's overheads
    and background moments; the enabled mask is the search's).

    A generation is one program: the fitness of the whole population as one
    banked batch on :func:`super_fleet` (``antithetic_sims`` replicas of
    every member, averaged), then the next population: the ``elite`` best
    members, and ``population - elite`` children of elites drawn at random,
    each gene redrawn with probability ``mutate_p``. The program is traced
    once per super-table and search settings (:data:`SEARCH_TRACES` counts
    the traces) and takes ``base_params`` as an argument, so a search under
    other parameters traces nothing.

    Each generation's dispatch and fitness fetch is the span
    ``profiles.generation``; the counters ``profiles.members`` and
    ``profiles.enabled_legs`` add up the members evaluated and their enabled
    legs (:mod:`repro.utils.spans`).
    """

    def __init__(
        self,
        st: SuperTable,
        base_params: SimParams,
        *,
        population: int = 32,
        elite: int = 8,
        mutate_p: float = 0.15,
        makespan_weight: float = 1.0,
        mean_weight: float = 0.1,
        antithetic_sims: int = 1,
    ) -> None:
        if not 0 < elite <= population:
            raise ValueError(f"need 0 < elite <= population: {elite}, {population}")
        self.st = st
        self.base_params = base_params
        self.population = population
        self.fleet = super_fleet(st)
        self._generation = _search_program(
            st, self.fleet, population, elite, mutate_p, makespan_weight,
            mean_weight, antithetic_sims,
        )

    def generation(self, pop: jax.Array, key: jax.Array):
        """Evaluate ``pop`` ``[population, n_access]`` and breed the next
        population, from the chain key ``key``: the simulations' keys and the
        mutations come from ``jax.random.split(key, 3)[1:]``, and the first
        of the three is the chain's next key.

        Returns ``(key, next_pop, fitness, result)``: the chain's next key and
        the next population on the device, the fitness ``[population]`` on the
        host, and the members' simulations, a :class:`SimResult` of
        ``[antithetic_sims, population, ...]`` fields on the device."""
        with spans.span("profiles.generation"):
            key, next_pop, fit, enabled, res = self._generation(self.base_params, pop, key)
            fit, enabled = jax.device_get((fit, enabled))
        spans.count("profiles.members", self.population)
        spans.count("profiles.enabled_legs", int(enabled))
        return key, next_pop, fit, res


def optimize_profiles(
    st: SuperTable,
    base_params: SimParams,
    key: jax.Array,
    *,
    population: int = 32,
    generations: int = 12,
    elite: int = 8,
    mutate_p: float = 0.15,
    antithetic_sims: int = 1,
) -> Tuple[np.ndarray, float, List[float]]:
    """(mu + lambda) evolutionary search over candidate assignments: one
    :class:`ProfileSearch`, one generation at a time.

    Returns (best assignment [n_access], best fitness, per-generation best).
    """
    search = ProfileSearch(
        st, base_params, population=population, elite=elite, mutate_p=mutate_p,
        antithetic_sims=antithetic_sims,
    )
    key, k0 = jax.random.split(key)
    pop = jax.random.randint(k0, (population, st.n_access), 0, st.n_cand)
    history: List[float] = []
    best_fit = np.inf
    best_assign = np.asarray(pop[0])
    for g in range(generations):
        key, next_pop, fit, _ = search.generation(pop, key)
        i = int(np.argmin(fit))
        if float(fit[i]) < best_fit:
            best_fit = float(fit[i])
            best_assign = np.asarray(pop[i])
        history.append(float(np.min(fit)))
        pop = next_pop
    return best_assign, best_fit, history
