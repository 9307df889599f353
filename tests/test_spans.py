"""The program's spans and counters (``repro.utils.spans``), and the banked
engine's per-element step count (``SimResult.steps``).

Spans nest and add their count and wall seconds to one table; a profiler
session records them on its host plane, where the benchmark's traced runs
read them. ``SimResult.steps`` is the banked loop's useful work: one step
per tick in tick mode, one per event leap in leap mode, the same for every
window size."""
import glob
import time

import jax
import numpy as np
import pytest

from repro.core import engine
from repro.core.engine import make_bank_params, simulate_bank
from repro.core.fleet import Fleet
from repro.core.scenarios import build_bank, sample_scenarios
from repro.core.workload import compile_campaign
from repro.serve import ServeConfig, SimRequest, SimServer
from repro.utils import spans


@pytest.fixture
def table():
    spans.reset()
    yield
    spans.reset()


def test_spans_nest_and_record_count_and_seconds(table):
    with spans.span("outer") as outer:
        for _ in range(3):
            with spans.span("inner") as inner:
                time.sleep(0.002)
    snap = spans.snapshot()
    assert snap["inner"].count == 3 and snap["outer"].count == 1
    assert inner.seconds >= 0.002
    assert snap["inner"].seconds >= 3 * 0.002
    # the outer span holds the inner ones, and its table entry is its own
    # .seconds
    assert outer.seconds >= snap["inner"].seconds
    assert snap["outer"].seconds == outer.seconds


def test_counters_snapshot_and_reset(table):
    spans.count("c")
    spans.count("c", 4)
    with spans.span("s"):
        pass
    snap = spans.snapshot()
    assert snap["c"] == spans.Entry(5, 0.0)
    spans.count("c")
    assert snap["c"].count == 5  # a snapshot is a copy
    spans.reset("c")
    assert set(spans.snapshot()) == {"s"}
    spans.reset()
    assert spans.snapshot() == {}


def test_concurrent_updates_are_not_lost(table):
    """Threads add to one counter and one span name at once: the table
    holds every update."""
    import os
    import sys
    import threading

    n_threads, n = 2 * (os.cpu_count() or 2), 2_000

    def work():
        for _ in range(n):
            spans.count("stress")
            with spans.span("stress.span"):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    snap = spans.snapshot()
    assert snap["stress"].count == n_threads * n
    assert snap["stress.span"].count == n_threads * n


def test_span_records_when_its_block_raises(table):
    with pytest.raises(ValueError):
        with spans.span("failing"):
            raise ValueError("x")
    assert spans.snapshot()["failing"].count == 1


def test_bank_trace_count_is_the_table_counter(table):
    bank = build_bank(n=2, seed=0, max_ticks=5_000)
    keys = jax.random.split(jax.random.PRNGKey(0), 2).reshape(2, 1, 2)
    engine.reset_bank_trace_count()
    spans.count("other")
    with engine.count_bank_traces() as traces:
        simulate_bank(bank, make_bank_params(bank), keys, bucketed=False)
    assert traces.count == 1
    assert engine.bank_trace_count() == 1
    assert spans.snapshot()[engine.BANK_TRACES].count == 1
    engine.reset_bank_trace_count(clear_caches=False)
    assert engine.bank_trace_count() == 0
    assert spans.snapshot()["other"].count == 1  # other entries stay


def test_fleet_run_spans_on_the_profiler_host_plane(tmp_path, table):
    """A profiler session around ``Fleet.run`` records the program's own
    spans on its host plane, on the clock of the device operations."""
    from jax.profiler import ProfileData

    fleet = Fleet.from_pairs(sample_scenarios(n=2, seed=0, scale=0.5))
    jax.block_until_ready(fleet.run(replicas=2))  # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(fleet.run(replicas=2))
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    data = ProfileData.from_file(files[-1])
    names = [e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    for name in ("fleet.run", "fleet.params", "engine.dispatch"):
        assert names.count(name) == 1, name
    snap = spans.snapshot()
    assert snap["fleet.run"].count == 2 and snap["engine.dispatch"].count == 2
    assert snap["fleet.run"].seconds >= snap["engine.dispatch"].seconds


def test_server_round_phases_are_spans(table):
    pairs = sample_scenarios(n=3, seed=0, scale=0.5)
    server = SimServer(ServeConfig(slots=2, replicas=1))
    for i, (g, c) in enumerate(pairs):
        server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
    server.drain()
    m = server.metrics()
    snap = spans.snapshot()
    split = m["wall_split_s"]
    for phase in ("admit", "dispatch", "sync", "retire"):
        assert snap["serve." + phase].count == m["rounds"]
        assert split[phase] == pytest.approx(snap["serve." + phase].seconds, abs=1e-5)
    assert snap["serve.round"].count == m["rounds"]
    assert m["wall_round_s"] == pytest.approx(snap["serve.round"].seconds, abs=1e-5)
    assert m["wall_round_s"] >= sum(split.values()) - 1e-5


# -- SimResult.steps ----------------------------------------------------------


def _stochastic_fleet_inputs(n=4, replicas=3):
    bank = build_bank(n=n, seed=1, max_ticks=20_000)
    params = make_bank_params(bank, bg_mu=0.4, bg_sigma=0.3)
    keys = jax.random.split(jax.random.PRNGKey(5), n * replicas).reshape(n, replicas, 2)
    return bank, params, keys


@pytest.mark.parametrize("backend", [None, "pallas_interpret"])
def test_steps_equal_ticks_in_tick_mode(backend):
    """On the reference scan and in the fused kernel (interpreted here)."""
    bank, params, keys = _stochastic_fleet_inputs(n=2, replicas=2)
    for window in (1, 4):
        res = simulate_bank(bank, params, keys, lowering="banked", window=window,
                            backend=backend)
        assert res.steps.shape == res.ticks.shape and res.steps.dtype == np.int32
        np.testing.assert_array_equal(np.asarray(res.steps), np.asarray(res.ticks))


def test_leap_steps_do_not_depend_on_the_window():
    bank, params, keys = _stochastic_fleet_inputs()
    runs = [simulate_bank(bank, params, keys, leap=True, lowering="banked", window=w)
            for w in (1, 4, 16)]
    steps = np.asarray(runs[0].steps)
    ticks = np.asarray(runs[0].ticks)
    assert (steps >= 1).all() and (steps <= ticks).all()
    assert (steps < ticks).any()  # leaps cover several ticks
    for res in runs[1:]:
        np.testing.assert_array_equal(np.asarray(res.steps), steps)


def test_steps_of_a_bucketed_fleet_are_in_scenario_order():
    pairs = sample_scenarios(n=6, seed=3, scale=0.5)
    key = jax.random.PRNGKey(2)
    one = Fleet.from_pairs(pairs).run(replicas=2, key=key, lowering="banked")
    buckets = Fleet.from_pairs(pairs, n_buckets=3)
    assert buckets.n_buckets > 1
    res = buckets.run(replicas=2, key=key, lowering="banked")
    np.testing.assert_array_equal(np.asarray(res.steps), np.asarray(res.ticks))
    np.testing.assert_array_equal(np.asarray(res.steps), np.asarray(one.steps))


def test_paths_without_a_step_count():
    """The per-sim engine and the vmap lowering leave ``steps`` unset."""
    (grid, campaign), = sample_scenarios(n=1, seed=0, scale=0.5)
    table = compile_campaign(grid, campaign)
    spec = engine.SimSpec.from_table(table)
    res = engine.simulate(spec, engine.make_params(table), jax.random.PRNGKey(0), leap=True)
    assert res.steps is None
    bank, params, keys = _stochastic_fleet_inputs(n=2, replicas=1)
    assert simulate_bank(bank, params, keys, lowering="vmap").steps is None
    assert simulate_bank(bank, params, keys, lowering="vmap", bucketed=False).steps is None
