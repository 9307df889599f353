"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run at a small size on the CPU (the harness's look
for a chip skipped) with one fault planted in the program under test, where
the answer is produced: an answer altered, a step that leaves the state as
it was, half of the batch left out. The sound run beside them comes out
correct."""
import numpy as np
import pytest
import small

import run as bench_run

PRESIM = "wlcg-prod.presim-leap"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small.small_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, workload):
    return bench_run.run(workload, 2**31 + 77, 1.0, False, require_chip=False, root=root)


def test_sound_run_is_correct(root):
    res = _run(root, PRESIM)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


def _unchanged(res):
    return res._replace(transfer_time=res.transfer_time * 0, done=res.done & False,
                        ticks=res.ticks * 0, conth_mb=res.conth_mb * 0,
                        conpr_mb=res.conpr_mb * 0)


# -- the presimulation: calibration.simulate_coefficients ---------------------

def test_presim_answer_altered(root, monkeypatch):
    """The concurrency coefficients come back in each other's places."""
    from repro.core import calibration

    real = calibration.simulate_coefficients
    monkeypatch.setattr(calibration, "simulate_coefficients",
                        lambda *a, **k: real(*a, **k)[..., (0, 2, 1)])
    assert not _run(root, PRESIM)["correct"]


def test_presim_state_unchanged(root, monkeypatch):
    from repro.core import calibration

    real = calibration.simulate
    monkeypatch.setattr(calibration, "simulate", lambda *a, **k: _unchanged(real(*a, **k)))
    assert not _run(root, PRESIM)["correct"]


def test_presim_half_the_batch(root, monkeypatch):
    """The fit sees half of the simulation's observations."""
    from repro.core import calibration

    real = calibration.simulate

    def half(*a, **k):
        res = real(*a, **k)
        keep = np.arange(res.done.shape[-1]) % 2 == 0
        return res._replace(done=res.done & keep)

    monkeypatch.setattr(calibration, "simulate", half)
    assert not _run(root, PRESIM)["correct"]


def test_presim_odd_rows_copy_even_rows(root, monkeypatch):
    """Half of the vmapped chunk left out: each odd theta row returns the
    coefficients of the even row before it. The broken chunk is compiled in
    set-up, so the share of tuples off, and not a trace in the window, is
    what fails the run."""
    import jax

    from harness import manifest

    real_generator = manifest.generator

    def generator(cell, root=None):
        mod = real_generator(cell, root)
        real_setup = mod.Generator.setup

        def setup(self, seconds):
            real_setup(self, seconds)
            inner = self._chunk
            even = np.arange(self.batch) & ~1
            self._chunk = jax.jit(lambda k: (lambda th, x: (th, x[even]))(*inner(k)))
            jax.block_until_ready(self._chunk(self.key))

        monkeypatch.setattr(mod.Generator, "setup", setup)
        return mod

    monkeypatch.setattr(manifest, "generator", generator)
    res = _run(root, PRESIM)
    checks = res["checks"]
    assert not res["correct"]
    assert checks["coef_off_share"]["value"] > checks["coef_off_share"]["limit"], checks
    assert checks["window_traces"]["value"] == 0, checks
