"""The fleet cell at a size the CPU runs in seconds: a sound run is correct
and its per-layer metrics read the program's spans and counters; the
control and a planted fault are not correct; a program without the spans
or the step count gives no per-layer value, and no 0 in its place."""
import os
import time

import numpy as np
import pytest
import small

import run as bench_run
from harness import manifest
from harness import trace as trace_lib

FLEET = "wlcg-prod.fleet-leap"
METRICS = ("fleet.host_ms_per_run.fleet", "fleet.run_idle_pct.fleet",
           "engine.lane_occupancy_pct.fleet")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = small.small_root(str(tmp_path_factory.mktemp("bench")))
    small._update(os.path.join(root, "bench", "traffic", "fleet-leap.json"),
                  {"replicas": 16, "check_rows": 16, "in_flight": 2})
    return root


def _run(root, trace=False):
    return bench_run.run(FLEET, 2**31 + 77, 1.0, trace, require_chip=False, root=root)


def test_sound_run_is_correct_and_reads_the_program(root):
    res = _run(root, trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["fleet.host_ms_per_run.fleet"] > 0
    assert 0 < metrics["engine.lane_occupancy_pct.fleet"] <= 100
    # the CPU trace has no device line, so no idle share
    assert "fleet.run_idle_pct.fleet" not in metrics


def test_control_fails_where_the_program_passes(root):
    cell = manifest.find_cell(FLEET, root)
    gen = manifest.generator(cell, root).Generator(cell, 12345, trace_lib.Tracer("", False))
    gen.setup(1.0)
    gen.window(1.0, time.perf_counter)
    gen.release()
    limits = {k: v["limit"] for k, v in cell.limits.items()}
    sound = gen.check()["values"]
    control = gen.check(control=True)["values"]
    assert set(sound) == set(control)
    assert all(v <= limits[k] for k, v in sound.items()), sound
    assert any(v > limits[k] for k, v in control.items()), control


def test_odd_rows_copy_even_rows(root, monkeypatch):
    """Half of the replicas left out: each odd row of a call returns the
    even row's results. The broken path is warmed in set-up, so the share of
    rows off, and not a trace in the window, is what fails the run."""
    import jax

    from repro.core import fleet as fleet_mod

    real = fleet_mod.simulate_bank

    def half(bank, params, keys, **kw):
        res = real(bank, params, keys, **kw)
        even = np.arange(keys.shape[1]) & ~1
        return jax.tree.map(lambda a: a[:, even], res)

    monkeypatch.setattr(fleet_mod, "simulate_bank", half)
    res = _run(root)
    checks = res["checks"]
    assert not res["correct"]
    assert checks["coef_off_share"]["value"] > checks["coef_off_share"]["limit"], checks
    assert checks["window_traces"]["value"] == 0, checks


def test_a_program_without_spans_or_steps_reads_nothing(root):
    """Where the program has no span table and no ``SimResult.steps`` (the
    counters stay empty), every new reader returns None."""
    events = [trace_lib.Event("span", -1, trace_lib.WINDOW, 0.0, 1e9),
              trace_lib.Event("op", 0, "k", 0.0, 5e8)]
    view = bench_run._RunView(trace_lib.reduce(events), {}, {}, "TPU v5 lite", 1)
    for name in METRICS:
        assert manifest.metric_reader(name, root).read(view) is None, name
    # with the counters, the idle share charged to the program's spans
    # reads 0 where no gap fell inside them
    counters = {"fleet.run.count": 3.0, "fleet.run.seconds": 0.003,
                "engine.steps": 90.0, "engine.lane_steps": 120.0}
    view = bench_run._RunView(trace_lib.reduce(events), counters, {}, "TPU v5 lite", 1)
    read = lambda name: manifest.metric_reader(name, root).read(view)
    assert read("fleet.run_idle_pct.fleet") == 0.0
    assert read("fleet.host_ms_per_run.fleet") == pytest.approx(1.0)
    assert read("engine.lane_occupancy_pct.fleet") == pytest.approx(75.0)
