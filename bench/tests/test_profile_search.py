"""The access-profile search cell at a size the CPU runs in seconds: a sound
run is correct and its per-layer metrics read the program's spans and
counters; the control and a planted fault are not correct; a program
without the search fails at once; the readers give numbers from a traced
run and nothing where the program has nothing to read."""
import json
import os
import time

import numpy as np
import pytest
import small

import run as bench_run
from harness import manifest
from harness import trace as trace_lib

SEARCH = "wlcg-prod-profiles.search"
METRICS = ("profiles.host_ms_per_generation.search", "profiles.run_idle_pct.search",
           "profiles.enabled_leg_pct.search", "engine.lane_occupancy_pct.fleet",
           "grid_tick_roofline.presim", "device.idle_pct.presim")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = small.small_root(str(tmp_path_factory.mktemp("bench")))
    configs = os.path.join(root, "bench", "configs")
    with open(os.path.join(configs, "wlcg-prod.json")) as f:
        workload = json.load(f)["workload"]  # the 12-access production bag
    small._update(os.path.join(configs, "wlcg-prod-profiles.json"), {
        "workload": workload,
        "search": {"population": 16, "elite": 4, "mutate_p": 0.15, "generations": 3,
                   "makespan_weight": 1.0, "mean_weight": 0.1, "antithetic_sims": 1}})
    small._update(os.path.join(root, "bench", "traffic", "search.json"),
                  {"check_members": 16})
    return root


def _run(root, trace=False):
    return bench_run.run(SEARCH, 2**31 + 77, 1.0, trace, require_chip=False, root=root)


def test_sound_run_is_correct_and_reads_the_program(root):
    res = _run(root, trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["search_traces"]["value"] == 0
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["profiles.host_ms_per_generation.search"] > 0
    # a member enables one leg of its access's three or four
    assert 25 <= metrics["profiles.enabled_leg_pct.search"] <= 50
    assert 0 < metrics["engine.lane_occupancy_pct.fleet"] <= 100
    # the CPU trace has no device line, so no idle share and no roofline
    assert "profiles.run_idle_pct.search" not in metrics
    assert "device.idle_pct.presim" not in metrics
    assert "grid_tick_roofline.presim" not in metrics


def test_control_fails_where_the_program_passes(root):
    cell = manifest.find_cell(SEARCH, root)
    gen = manifest.generator(cell, root).Generator(cell, 12345, trace_lib.Tracer("", False))
    gen.setup(1.0)
    gen.window(1.0, time.perf_counter)
    gen.release()
    limits = {k: v["limit"] for k, v in cell.limits.items()}
    sound = gen.check()["values"]
    control = gen.check(control=True)["values"]
    assert set(sound) == set(control)
    assert all(v <= limits[k] for k, v in sound.items()), sound
    assert control["leg_gap_median"] > limits["leg_gap_median"], control
    assert control["member_off_share"] > limits["member_off_share"], control
    work = gen.work()
    assert work["flops"] > 0 and work["bytes"] > 0


def test_odd_members_copy_even_members(root, monkeypatch):
    """Half of the population left out: each odd member of a generation gets
    the even member's simulation. The broken path is warmed in set-up, so
    the share of members off, and not a trace in the window, fails the
    run."""
    import jax

    from repro.core import fleet as fleet_mod

    real = fleet_mod.simulate_bank

    def half(bank, params, keys, **kw):
        res = real(bank, params, keys, **kw)
        even = np.arange(keys.shape[1]) & ~1
        return jax.tree.map(lambda a: None if a is None else a[:, even], res)

    monkeypatch.setattr(fleet_mod, "simulate_bank", half)
    res = _run(root)
    checks = res["checks"]
    assert not res["correct"]
    assert checks["member_off_share"]["value"] > checks["member_off_share"]["limit"], checks
    assert checks["window_traces"]["value"] == 0, checks


def test_a_fault_only_the_whole_population_has(root, monkeypatch):
    """A fault that only a bank of the whole population has, and so only the
    search's own generation program: its second half of rows gets the first
    half's simulations. The check reads the members' legs from the window's
    generations, so the share of members off fails the run."""
    import jax

    from repro.core import fleet as fleet_mod

    real = fleet_mod.simulate_bank
    population = manifest.find_cell(SEARCH, root).config["search"]["population"]

    def shifted(bank, params, keys, **kw):
        res = real(bank, params, keys, **kw)
        rows = keys.shape[1]
        if rows != population:
            return res
        src = np.arange(rows)
        src[rows // 2:] -= rows // 2
        return jax.tree.map(lambda a: None if a is None else a[:, src], res)

    monkeypatch.setattr(fleet_mod, "simulate_bank", shifted)
    res = _run(root)
    checks = res["checks"]
    assert not res["correct"]
    assert checks["member_off_share"]["value"] > checks["member_off_share"]["limit"], checks
    assert checks["window_traces"]["value"] == 0, checks


def test_a_program_without_the_search_fails_at_once(root, monkeypatch):
    """The parent of the search has no ``ProfileSearch``: the cell stops at
    its generator's construction, before any set-up."""
    from repro.core import scheduler

    monkeypatch.delattr(scheduler, "ProfileSearch")
    t0 = time.perf_counter()
    with pytest.raises(ImportError):
        _run(root)
    assert time.perf_counter() - t0 < 30


def test_readers_read_a_traced_run_and_nothing_without_the_program(root):
    events = [trace_lib.Event("span", -1, trace_lib.WINDOW, 0.0, 1e9),
              trace_lib.Event("span", -1, "profiles.generation", 0.0, 1e9),
              trace_lib.Event("op", 0, "grid_tick_bank_fused_pallas", 0.0, 8e8)]
    reduced = trace_lib.reduce(events)
    view = bench_run._RunView(reduced, {}, {}, "TPU v5 lite", 1)
    for name in METRICS[:5]:
        assert manifest.metric_reader(name, root).read(view) is None, name
    counters = {"profiles.generation.count": 4.0, "profiles.generation.seconds": 0.8,
                "profiles.members": 64.0, "profiles.enabled_legs": 64.0 * 106,
                "profiles.legs": 424.0, "engine.steps": 90.0, "engine.lane_steps": 120.0}
    work = {"flops": 197e12 * 0.2, "bytes": 0.0}
    view = bench_run._RunView(reduced, counters, work, "TPU v5 lite", 1)
    read = lambda name: manifest.metric_reader(name, root).read(view)
    assert read("profiles.host_ms_per_generation.search") == pytest.approx(200.0)
    assert read("profiles.run_idle_pct.search") == pytest.approx(20.0)
    assert read("profiles.enabled_leg_pct.search") == pytest.approx(25.0)
    assert read("engine.lane_occupancy_pct.fleet") == pytest.approx(75.0)
    assert read("grid_tick_roofline.presim") == pytest.approx(25.0)
    assert read("device.idle_pct.presim") == pytest.approx(20.0)
