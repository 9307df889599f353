"""Beyond-paper access-profile optimizer: the evolutionary search must beat
both a fixed all-remote and a random assignment on a congested grid; the
paper's three profiles built for a campaign simulate, member by member, as
the realized campaigns do; the search keeps its results and traces once."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, scheduler
from repro.core.engine import SimParams, make_params
from repro.core.fleet import Fleet
from repro.core.refsim import reference_simulate
from repro.core.scenarios import make_scenario
from repro.core.scheduler import (
    CandidateAccess,
    build_super_table,
    optimize_profiles,
)
from repro.core.topology import Grid
from repro.core.workload import (
    AccessProfileKind,
    FileAccess,
    ProfileTag,
    Replica,
    compile_campaign,
    wlcg_production_workload,
)
from repro.utils import spans


def _scenario():
    """Grid where the WAN link to the worker node is heavily loaded but the
    SE->SE and LAN links are clear: placement should win for big files."""
    g = Grid()
    g.add_data_center("SRC")
    g.add_data_center("DST")
    g.add_storage_element("seS", "SRC")
    g.add_storage_element("seD", "DST")
    for w in range(2):
        g.add_worker_node(f"wn{w}", "DST")
    # congested WAN into the worker nodes
    for w in range(2):
        g.add_link("seS", f"wn{w}", 60.0, bg_mu=12.0, bg_sigma=1.0)
        g.add_link("seD", f"wn{w}", 400.0)
    g.add_link("seS", "seD", 500.0)

    accesses = []
    rng = np.random.RandomState(0)
    for j in range(2):
        for _ in range(3):
            size = float(rng.uniform(100.0, 400.0))
            remote = FileAccess(
                Replica(size, "seS"), AccessProfileKind.REMOTE, "webdav"
            )
            placed = FileAccess(
                Replica(size, "seS"),
                AccessProfileKind.DATA_PLACEMENT,
                "gsiftp",
                local_storage_element="seD",
            )
            accesses.append(CandidateAccess(job=j, candidates=(remote, placed)))
    return g, accesses


def _fitness_of(st, base, assign, key):
    from repro.core.scheduler import _fitness

    return float(_fitness(st, base, jnp.asarray(assign), key))


def test_super_table_masks_are_disjoint_and_complete():
    g, accesses = _scenario()
    st = build_super_table(g, ["wn0", "wn1"], accesses, max_ticks=60_000)
    # every leg belongs to exactly one candidate
    seen = np.zeros(st.table.n_legs, int)
    for i in range(st.n_access):
        for k in range(int(st.cands_per_access[i])):
            for leg in st.cand_legs[i, k]:
                if leg >= 0:
                    seen[leg] += 1
    assert (seen == 1).all()


def test_optimizer_beats_all_remote():
    g, accesses = _scenario()
    st = build_super_table(g, ["wn0", "wn1"], accesses, max_ticks=60_000)
    base = make_params(st.table)
    key = jax.random.PRNGKey(0)

    all_remote = np.zeros(st.n_access, int)  # candidate 0 = remote
    f_remote = _fitness_of(st, base, all_remote, key)

    best, f_best, hist = optimize_profiles(
        st, base, jax.random.PRNGKey(1), population=24, generations=8, elite=6
    )
    assert f_best <= f_remote, (f_best, f_remote)
    # the search must actually improve over its first generation
    assert hist[-1] <= hist[0]
    # with a congested WAN the optimum routes most files via placement
    chosen = best % np.maximum(st.cands_per_access, 1)
    assert (chosen == 1).mean() >= 0.5, chosen


# -- the paper's three profiles over a campaign: builder, search, references --


def _production_bag():
    """The paper's production workload cut to 12 accesses, with the SE -> SE
    and CERN LAN links the placement candidates need."""
    g, camp = wlcg_production_workload(n_waves=4, max_jobs=4, max_threads=2,
                                       n_observations=12)
    g.add_link("GRIF-LPNHE_SCRATCHDISK", "CERN-PROD_SCRATCHDISK", 1250.0)
    g.add_link("CERN-PROD_SCRATCHDISK", "cern-wn00", 1250.0)
    return g, camp, "CERN-PROD_SCRATCHDISK"


def _mixed_bag():
    g, camp = make_scenario("mixed-bag", seed=1)
    return g, camp, "seB"


BAGS = {"mixed-bag": _mixed_bag, "production-12": _production_bag}


def _bag(name):
    g, camp, local = BAGS[name]()
    accesses = scheduler.profile_candidates(g, camp, local)
    wns = [j.worker_node for j in camp.jobs]
    return g, camp, local, accesses, wns


@pytest.mark.parametrize("bag", sorted(BAGS))
def test_profile_candidates_legs_and_dependencies(bag):
    g, camp, local, accesses, wns = _bag(bag)
    flat = [(j, a) for j, job in enumerate(camp.jobs) for a in job.accesses]
    assert len(accesses) == len(flat)
    st = build_super_table(g, wns, accesses)
    t = st.table
    names = t.links.names
    for i, (acc, (j, orig)) in enumerate(zip(accesses, flat)):
        src, wn = orig.replica.storage_element, camp.jobs[j].worker_node
        assert acc.job == j
        kinds = [c.profile for c in acc.candidates]
        placed = src != local
        assert kinds == [AccessProfileKind.REMOTE, AccessProfileKind.STAGE_IN] + (
            [AccessProfileKind.DATA_PLACEMENT] if placed else [])
        assert st.cands_per_access[i] == len(kinds)
        for k, cand in enumerate(acc.candidates):
            legs = [leg for leg in st.cand_legs[i, k] if leg >= 0]
            assert len(legs) == (2 if k == 2 else 1)
            np.testing.assert_array_equal(t.size_mb[legs], np.float32(orig.replica.size_mb))
            np.testing.assert_array_equal(t.release[legs], orig.release_tick)
            hops = [(src, wn)] if k < 2 else [(src, local), (local, wn)]
            assert [names[x] for x in t.link_id[legs]] == hops
            expect = {0: [ProfileTag.REMOTE], 1: [ProfileTag.STAGE_IN],
                      2: [ProfileTag.PLACEMENT, ProfileTag.STAGE_IN]}[k]
            assert list(t.profile[legs]) == expect
            # the stage-in half of a placement waits for its SE -> SE leg
            assert list(t.dep[legs]) == ([-1] if k < 2 else [-1, legs[0]])
            protocols = [t.protocol_names[x] for x in t.protocol_id[legs]]
            assert protocols == {0: ["webdav"], 1: ["xrdcp"], 2: ["gsiftp", "xrdcp"]}[k]


def _members(st, n, seed):
    """``n`` assignments, the first all-remote and the rest random."""
    pop = np.random.default_rng(seed).integers(0, st.n_cand, (n, st.n_access))
    pop[0] = 0
    return pop


def _super_run(st, pop, keys, leap, mu=3.0, sigma=2.0):
    base = make_params(st.table, bg_mu=mu, bg_sigma=sigma)
    masks = jax.vmap(lambda a: scheduler._assignment_mask(st, a))(jnp.asarray(pop))
    params = SimParams(keep_frac=base.keep_frac[None], bg_mu=base.bg_mu[None],
                       bg_sigma=base.bg_sigma[None], enabled=masks[None])
    fleet = Fleet.from_table(st.table, leap=leap, max_ticks=int(st.spec.max_ticks))
    return jax.device_get(fleet.run(params, keys=keys[None])), np.asarray(masks)


def _realized_run(g, table, key, leap, max_ticks, mu=3.0, sigma=2.0):
    p = make_params(table, bg_mu=mu, bg_sigma=sigma)
    fleet = Fleet.from_table(table, leap=leap, max_ticks=max_ticks)
    params = SimParams(keep_frac=p.keep_frac[None], bg_mu=p.bg_mu[None],
                       bg_sigma=p.bg_sigma[None])
    return jax.device_get(fleet.run(params, keys=key[None, None]))


@pytest.mark.parametrize("leap", [False, True], ids=["tick", "leap"])
@pytest.mark.parametrize("bag", sorted(BAGS))
def test_super_table_member_equals_its_realized_campaign(bag, leap):
    """A member of the super-table simulates as its chosen candidates do when
    compiled as an ordinary campaign on the same grid: the same schedule,
    noise and transfers leg for leg. The concurrency accumulators are sums
    over the legs of a process or link, and ``ref.onehot_sum`` adds leg
    ``t`` in lane ``t % 16``: in the super-table the same legs sit at other
    indices, so their sums may associate differently, a few ulps apart."""
    g, camp, local, accesses, wns = _bag(bag)
    st = build_super_table(g, wns, accesses)
    pop = _members(st, 4, seed=5)
    keys = jax.random.split(jax.random.PRNGKey(11), len(pop))
    res, masks = _super_run(st, pop, keys, leap)
    for b, assign in enumerate(pop):
        table = compile_campaign(g, scheduler.realized_campaign(accesses, wns, assign))
        one = _realized_run(g, table, keys[b], leap, int(st.spec.max_ticks))
        m = masks[b]
        assert int(m.sum()) == table.n_legs
        for f in ("transfer_time", "start_tick", "done", "size_mb", "profile"):
            np.testing.assert_array_equal(np.asarray(getattr(res, f))[0, b][m],
                                          np.asarray(getattr(one, f))[0, 0], err_msg=f)
        assert int(res.ticks[0, b]) == int(one.ticks[0, 0])
        assert int(res.steps[0, b]) == int(one.steps[0, 0])
        for f in ("conth_mb", "conpr_mb"):
            np.testing.assert_allclose(np.asarray(getattr(res, f))[0, b][m],
                                       np.asarray(getattr(one, f))[0, 0],
                                       rtol=1e-6, atol=1e-3, err_msg=f)


def _engine_noise(key, n_links, n_ticks):
    """The tick engine's background draws: one split of the carried key per
    tick, ``normal(sub, [L])`` (CONTRACTS.md section 2)."""
    def step(k, _):
        k, sub = jax.random.split(k)
        return k, jax.random.normal(sub, (n_links,), jnp.float32)

    return np.asarray(jax.lax.scan(step, key, None, length=n_ticks)[1], np.float64)


@pytest.mark.parametrize("bag", sorted(BAGS))
def test_super_table_member_matches_refsim(bag):
    """Each member against the plain loop oracle on its realized campaign,
    with the engine's own noise draws: transfer times and start ticks
    exact, the concurrency accumulators to the float32 engine's rounding
    (float64 oracle; ``test_engine.py`` uses the same tolerance)."""
    g, camp, local, accesses, wns = _bag(bag)
    st = build_super_table(g, wns, accesses)
    pop = _members(st, 4, seed=6)
    keys = jax.random.split(jax.random.PRNGKey(12), len(pop))
    res, masks = _super_run(st, pop, keys, leap=False)
    for b, assign in enumerate(pop):
        table = compile_campaign(g, scheduler.realized_campaign(accesses, wns, assign))
        p = make_params(table, bg_mu=3.0, bg_sigma=2.0)
        z = _engine_noise(keys[b], table.links.n_links, int(res.ticks[0, b]) + 1)
        ref = reference_simulate(table, np.asarray(p.keep_frac), np.asarray(p.bg_mu),
                                 np.asarray(p.bg_sigma), int(st.spec.max_ticks),
                                 bg_sampler=lambda t, shape: z[t])
        m = masks[b]
        assert bool(ref["done"].all()) and bool(np.asarray(res.done)[0, b][m].all())
        for f in ("transfer_time", "start_tick"):
            np.testing.assert_array_equal(np.asarray(getattr(res, f))[0, b][m], ref[f])
        for f in ("conth_mb", "conpr_mb"):
            np.testing.assert_allclose(np.asarray(getattr(res, f))[0, b][m], ref[f],
                                       rtol=2e-5, atol=1e-3, err_msg=f)


def _parent_optimize_profiles(st, base_params, key, *, population, generations, elite,
                              mutate_p=0.15, antithetic_sims=1):
    """The search as two jitted closures built per call, one generation of
    each at a time: the form ``optimize_profiles`` had before
    ``ProfileSearch`` (kept here as the bitwise reference)."""
    from repro.core.scheduler import evaluate_population, super_fleet

    n_access, n_cand = st.n_access, st.n_cand
    key, k0 = jax.random.split(key)
    pop = jax.random.randint(k0, (population, n_access), 0, n_cand)
    fleet = super_fleet(st)

    @jax.jit
    def eval_pop(pop, key):
        keys = jax.random.split(key, antithetic_sims)

        def per_sim(k):
            ks = jax.random.split(k, pop.shape[0])
            return evaluate_population(st, base_params, pop, ks, fleet=fleet)
        return jnp.mean(jax.vmap(per_sim)(keys), axis=0)

    @jax.jit
    def next_gen(pop, fit, key):
        order = jnp.argsort(fit)
        elites = pop[order[:elite]]
        k1, k2, k3 = jax.random.split(key, 3)
        parents = elites[jax.random.randint(k1, (population - elite,), 0, elite)]
        flip = jax.random.uniform(k2, parents.shape) < mutate_p
        rand = jax.random.randint(k3, parents.shape, 0, n_cand)
        return jnp.concatenate([elites, jnp.where(flip, rand, parents)], axis=0)

    history, best_fit, best_assign = [], np.inf, np.asarray(pop[0])
    for _ in range(generations):
        key, ke, kn = jax.random.split(key, 3)
        fit = eval_pop(pop, ke)
        i = int(jnp.argmin(fit))
        if float(fit[i]) < best_fit:
            best_fit, best_assign = float(fit[i]), np.asarray(pop[i])
        history.append(float(jnp.min(fit)))
        pop = next_gen(pop, fit, kn)
    return best_assign, best_fit, history


@pytest.mark.parametrize("bag", sorted(BAGS))
def test_optimize_profiles_keeps_its_results_bitwise(bag):
    g, camp, local, accesses, wns = _bag(bag)
    st = build_super_table(g, wns, accesses)
    base = make_params(st.table, bg_mu=4.0, bg_sigma=2.0)
    kw = dict(population=12, generations=3, elite=3)
    got = optimize_profiles(st, base, jax.random.PRNGKey(21), **kw)
    want = _parent_optimize_profiles(st, base, jax.random.PRNGKey(21), **kw)
    np.testing.assert_array_equal(got[0], want[0])
    assert got[1] == want[1]
    assert got[2] == want[2]


def test_second_search_on_a_super_table_traces_nothing():
    """The search's programs are traced once per super-table and settings:
    a second search under another theta reuses them, and the engine's bank
    program too. The spans and counters add up what ran."""
    g, camp, local, accesses, wns = _bag("production-12")
    st = build_super_table(g, wns, accesses)
    kw = dict(population=8, elite=2)
    optimize_profiles(st, make_params(st.table, bg_mu=1.0, bg_sigma=1.0),
                      jax.random.PRNGKey(0), generations=2, **kw)
    before = spans.snapshot()
    traces = engine.bank_trace_count()
    second = scheduler.ProfileSearch(st, make_params(st.table, bg_mu=9.0, bg_sigma=3.0), **kw)
    pop = jax.random.randint(jax.random.PRNGKey(1), (8, st.n_access), 0, st.n_cand)
    _, next_pop, fit, res = second.generation(pop, jax.random.PRNGKey(2))
    after = spans.snapshot()
    delta = lambda name: after.get(name, (0, 0.0))[0] - before.get(name, (0, 0.0))[0]
    assert delta(scheduler.SEARCH_TRACES) == 0
    assert engine.bank_trace_count() == traces
    assert delta("profiles.generation") == 1
    assert delta("profiles.members") == 8
    chosen = np.asarray(pop) % st.cands_per_access
    n_legs = (st.cand_legs >= 0).sum(-1)[np.arange(st.n_access), chosen]
    assert delta("profiles.enabled_legs") == int(n_legs.sum())
    assert next_pop.shape == pop.shape and fit.shape == (8,)
    assert res.transfer_time.shape == (1, 8, st.table.n_legs) and res.steps.shape == (1, 8)
