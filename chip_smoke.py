#!/usr/bin/env python3
"""Bring-up smoke test: the simulator's main paths on a TPU, checked.

    python chip_smoke.py             # one chip: calibration, fleet, serving
    python chip_smoke.py --chips 4   # four chips: sharded fleet and server

Everything runs in this one process, which holds the chip(s). One chip runs
three phases through the entry points a user calls:

(a) calibration of the paper's production workload (106 WebDAV accesses)
    through ``Fleet.calibrate``: 65,536 presimulated tuples in leap mode, a
    few classifier epochs (the loss must be finite and fall), a short MCMC
    (acceptance in (0, 1), finite theta*). Eight posterior draws are
    re-simulated at ``bg_sigma=0`` by the calibration's own (leap) engine
    and compared with ``core/refsim.py``; their Eq.-1 coefficients with a
    float64 numpy fit;
(b) the 7-family fleet at 4,096 scenarios x 4 replicas, bucketed, through
    ``Fleet.run`` in tick and leap mode, cold then warm (the warm run must
    trace nothing), 16 sampled scenarios compared with ``core/refsim.py``;
(c) a ``SimServer`` answering requests as ``repro.launch.serve`` drives it,
    every served row bitwise equal to ``Fleet.run`` of the same request,
    and a warm replay that traces nothing.

``--chips 4`` runs only the multi-chip path and what it is compared with:
phase (b)'s fleet on ``Fleet(devices=4)``, shard-padded, against the same
fleet on one chip, and ``SimServer(devices=4)`` against ``Fleet.run`` —
both bitwise.

Each phase prints one JSON line (sizes, cold/warm wall seconds, trace
counts, and whether the compiled window step or classifier step holds a
Pallas kernel, ``tpu_custom_call``). The last line is
``{"ok": true, "device": {...}}``. Without a TPU, with kernels that would
not run as Pallas, or on any failed check the script exits non-zero and
prints no such line. These are bring-up runs, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# phase (a): the paper's production workload (Section 5) and pipeline cuts
PRESIM = 65_536  # CalibrationConfig's default (paper: 12.7M)
EPOCHS = 4  # paper: 263
MCMC_STEPS, MCMC_BURN_IN = 2_000, 500  # paper: 1M + 100k
THETA_TRUE = (0.02, 36.9, 14.4)  # launch/calibrate.py's synthetic truth
REFSIM_DRAWS = 8
# phase (b): the 7-family mix
FLEET_SCENARIOS, FLEET_REPLICAS, FLEET_BUCKETS = 4096, 4, 8
REFSIM_SCENARIOS = 16
# phase (c): launch/serve.py's server shape
SERVE_REQUESTS, SERVE_SLOTS, SERVE_REPLICAS, SERVE_RATE = 24, 8, 2, 100.0
SHARDED_SERVE_REQUESTS = 8
# test_engine.py's engine-vs-reference tolerances
CON_RTOL, CON_ATOL = 2e-5, 1e-3
EQ1_RTOL = 1e-4


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def has_kernel(jitted, *args, **kwargs) -> bool:
    """Whether the compiled program of ``jitted`` at these arguments holds a
    Pallas kernel (and not an interpret-mode or XLA stand-in)."""
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).compile().as_text()


def window_step_has_kernel(bank, params, keys, *, leap, window, mesh=None) -> bool:
    """``has_kernel`` for the banked window step at one bank's shapes."""
    from repro.core import engine

    spec = engine.bank_spec(bank)
    carry = engine._banked_init_carry(spec, params, keys)
    if mesh is None:
        return has_kernel(
            engine._banked_window_step, spec, params, carry,
            backend=None, leap=leap, window=window,
        )
    return has_kernel(
        engine._banked_window_step_sharded, spec, params, carry,
        mesh=mesh, backend=None, leap=leap, window=window,
    )


def check_refsim(res, tables, keep, mu, sigma, max_ticks, where: str) -> None:
    """Result rows ``res[i, r]`` against ``core/refsim.py`` run on
    ``tables[i]`` with row ``(i, r)`` of the params: transfer times and
    tick counts exactly, the concurrency accumulators within
    ``test_engine.py``'s tolerances."""
    import numpy as np

    from repro.core.refsim import reference_simulate

    for (i, r), table in tables.items():
        nt, nl = table.n_legs, table.n_links
        ref = reference_simulate(
            table, keep[i, r, :nt], mu[i, r, :nl], sigma[i, r, :nl],
            int(max_ticks[i]),
        )
        tag = f"{where} row ({i}, {r})"
        got = lambda f: np.asarray(getattr(res, f))[i, r, :nt]
        np.testing.assert_array_equal(
            got("transfer_time"), ref["transfer_time"], err_msg=tag
        )
        assert int(np.asarray(res.ticks)[i, r]) == int(ref["ticks"]), tag
        for f in ("conth_mb", "conpr_mb"):
            np.testing.assert_allclose(
                got(f), ref[f], rtol=CON_RTOL, atol=CON_ATOL, err_msg=f"{tag} {f}"
            )


def assert_bitwise(a, b, where: str) -> None:
    import numpy as np

    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.shape == y.shape and np.array_equal(x, y), f"{where}: {f}"


# ---------------------------------------------------------------------------
# (a) calibration
# ---------------------------------------------------------------------------

def phase_calibration(presim: int = PRESIM, epochs: int = EPOCHS,
                      mcmc: int = MCMC_STEPS, burn_in: int = MCMC_BURN_IN,
                      batch: int = 4096) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import Fleet
    from repro.core import calibration, classifier, engine
    from repro.core.workload import ProfileTag, compile_campaign, wlcg_production_workload
    from repro.train.optimizer import AdamWConfig, adamw_init

    table = compile_campaign(*wlcg_production_workload(seed=0))
    fleet = Fleet.from_table(table, leap=True)
    x_true = jnp.mean(
        fleet.coefficients(THETA_TRUE, replicas=8, key=jax.random.PRNGKey(42)),
        axis=(0, 1),
    )
    cfg = calibration.CalibrationConfig(
        n_presim=presim, epochs=epochs, n_mcmc=mcmc, burn_in=burn_in,
        batch_size=min(4096, presim // 2),
    )
    run = lambda: fleet.calibrate(x_true, jax.random.PRNGKey(0), cfg, batch=batch)
    with engine.count_bank_traces() as cold_traces:
        result, cold = timed(run)
    cold_traces = cold_traces.count
    with engine.count_bank_traces() as warm_traces:
        warm_result, warm = timed(run)
    warm_traces = warm_traces.count
    assert np.array_equal(
        np.asarray(warm_result.posterior_samples),
        np.asarray(result.posterior_samples),
    ), "calibration: warm posterior differs from cold"

    curve = np.asarray(result.epoch_loss)
    theta_star = np.asarray(result.theta_star)
    accept = float(result.accept_rate)
    assert np.isfinite(curve).all() and curve[-1] < curve[0], f"loss {curve}"
    assert 0.0 < accept < 1.0, f"MCMC acceptance {accept}"
    assert np.isfinite(theta_star).all(), f"theta* {theta_star}"

    # posterior draws re-simulated deterministically (bg_sigma = 0) by the
    # engine the calibration ran: the leap engine moves a leg's bytes in one
    # product per rate change. The tick engine subtracts a chunk per tick,
    # and on these 300-3000 MB legs its f32 rounding ends about one draw in
    # fifteen a tick away from the float64 reference (on any backend)
    draws = np.asarray(result.posterior_samples)[
        :: max(1, len(result.posterior_samples) // REFSIM_DRAWS)
    ][:REFSIM_DRAWS]
    mapped = [fleet.theta_mapper()(jnp.asarray([d[0], d[1], 0.0])) for d in draws]
    params = engine.SimParams(*[
        jnp.stack([getattr(p, f)[0] for p in mapped])[None]
        for f in ("keep_frac", "bg_mu", "bg_sigma")
    ])  # per-replica [1, draws, ...]
    keys = jax.random.split(jax.random.PRNGKey(7), REFSIM_DRAWS)[None]
    res = fleet.run(params, keys=keys, leap=cfg.use_leap)
    host = [np.asarray(getattr(params, f)) for f in ("keep_frac", "bg_mu", "bg_sigma")]
    tables = {(0, r): table for r in range(REFSIM_DRAWS)}
    check_refsim(res, tables, *host, fleet.bank.max_ticks, "calibration")

    # Eq.-1 fits on the chip against float64 numpy least squares
    flat = jax.tree.map(lambda a: a[0], res)
    coefs = np.asarray(jax.jit(jax.vmap(calibration._eq1_coefficients))(flat))
    for r in range(REFSIM_DRAWS):
        row = lambda f: np.asarray(getattr(flat, f))[r].astype(np.float64)
        m = (np.asarray(flat.done)[r]) & (np.asarray(flat.profile)[r] == ProfileTag.REMOTE)
        X = np.stack([row("size_mb"), row("conth_mb"), row("conpr_mb")], 1)[m]
        ref = np.linalg.lstsq(X, row("transfer_time")[m], rcond=None)[0]
        np.testing.assert_allclose(coefs[r], ref, rtol=EQ1_RTOL, err_msg=f"Eq.1 draw {r}")

    # the compiled programs that ran: leap window step, classifier epoch
    th = jnp.zeros((cfg.batch_size * 2, 3), jnp.float32)
    clf = classifier.ClassifierConfig()
    cparams = classifier.init_classifier(jax.random.PRNGKey(0), clf)
    opt = adamw_init(cparams, AdamWConfig(lr=clf.lr))
    return {
        "phase": "calibration",
        "workload": "wlcg_production_workload", "legs": table.n_legs,
        "presim_tuples": presim, "presim_leap": cfg.use_leap,
        "epochs": epochs, "epochs_default": 30, "epochs_paper": 263,
        "mcmc_steps": mcmc, "burn_in": burn_in, "chains": cfg.n_chains,
        "mcmc_paper": 1_000_000,
        "cold_s": cold, "warm_s": warm,
        "bank_traces_cold": cold_traces,
        "bank_traces_warm": warm_traces,
        "epoch_loss": curve.tolist(), "accept_rate": accept,
        "theta_star": theta_star.tolist(), "theta_true": list(THETA_TRUE),
        "refsim_draws_checked": REFSIM_DRAWS, "eq1_fits_checked": REFSIM_DRAWS,
        "window_step_kernel": window_step_has_kernel(
            fleet.bank, params, keys,
            leap=cfg.use_leap, window=engine._resolve_window(None, cfg.use_leap),
        ),
        "classifier_step_kernel": has_kernel(
            classifier._train_epoch, cparams, opt, th, th,
            jnp.zeros((th.shape[0], 0)), jax.random.PRNGKey(1),
            jnp.asarray(clf.lr), batch_size=cfg.batch_size,
        ),
    }


# ---------------------------------------------------------------------------
# (b) fleet
# ---------------------------------------------------------------------------

def fleet_pairs(n: int = FLEET_SCENARIOS):
    from repro.core.scenarios import sample_scenarios

    return sample_scenarios(n=n, seed=0)


def phase_fleet(n: int = FLEET_SCENARIOS, replicas: int = FLEET_REPLICAS,
                buckets: int = FLEET_BUCKETS) -> dict:
    import jax
    import numpy as np

    from repro import Fleet
    from repro.core import engine
    from repro.core.scenarios import family_names

    pairs = fleet_pairs(n)
    key = jax.random.PRNGKey(0)
    record = {"phase": "fleet", "scenarios": n, "replicas": replicas,
              "families": len(family_names())}
    rng = np.random.RandomState(0)
    for leap in (False, True):
        mode = "leap" if leap else "tick"
        fleet = Fleet.from_pairs(pairs, n_buckets=buckets, leap=leap)
        run = lambda: fleet.run(replicas=replicas, key=key)
        with engine.count_bank_traces() as cold_traces:
            out, cold = timed(run)
        cold_traces = cold_traces.count
        with engine.count_bank_traces() as warm_traces:
            again, warm = timed(run)
        warm_traces = warm_traces.count
        assert warm_traces == 0, f"{mode}: warm run traced {warm_traces}"
        assert_bitwise(out, again, f"fleet {mode} warm vs cold")
        assert np.asarray(out.done).all(), f"fleet {mode}: unfinished legs"

        # deterministic re-run (bg_sigma = 0) of the same shapes, sampled rows
        # against the reference
        params = fleet.params(bg_sigma=0.0)
        det = fleet.run(params, replicas=replicas, key=key)
        rows = rng.choice(n, REFSIM_SCENARIOS, replace=False)
        tables = {(int(i), 0): fleet.bank.scenario_table(int(i)) for i in rows}
        host = [
            np.broadcast_to(np.asarray(getattr(params, f))[:, None], (n, replicas) + getattr(params, f).shape[1:])
            for f in ("keep_frac", "bg_mu", "bg_sigma")
        ]
        check_refsim(det, tables, *host, fleet.bank.max_ticks, f"fleet {mode}")

        b0 = fleet.bank.buckets[0]
        ids = np.asarray(b0.scenario_ids)
        w = engine._clamp_window(
            engine._resolve_window(None, leap), int(b0.bank.max_ticks.max())
        )
        record[mode] = {
            "buckets": len(fleet.bank.buckets), "pads": list(fleet.pads),
            "cold_s": cold, "warm_s": warm,
            "bank_traces_cold": cold_traces,
            "bank_traces_warm": warm_traces,
            "max_ticks_realized": int(np.asarray(out.ticks).max()),
            "refsim_scenarios_checked": REFSIM_SCENARIOS,
            "window_step_kernel": window_step_has_kernel(
                b0.bank, engine.make_bank_params(b0.bank),
                jax.random.split(key, len(ids) * replicas).reshape(len(ids), replicas, 2),
                leap=leap, window=w,
            ),
        }
    return record


# ---------------------------------------------------------------------------
# (c) serving
# ---------------------------------------------------------------------------

def serve_workload(n: int, replicas: int, seed: int = 0):
    from repro.serve import synthetic_workload

    return synthetic_workload(n, rate=SERVE_RATE, seed=seed, replicas=replicas)


def serve_pass(server, workload, rid_offset: int = 0):
    """Submit ``workload`` open-loop as ``repro.launch.serve`` does and
    drain; returns ``{rid: RequestResult}``."""
    import dataclasses

    t0 = time.perf_counter()
    for arrival, req in workload:
        while time.perf_counter() - t0 < arrival:
            server.step()
        server.submit(dataclasses.replace(req, rid=req.rid + rid_offset))
        server.step()
    return {r.rid: r for r in server.drain()}


def assert_served_matches_fleet(results, workload, rid_offset: int = 0) -> None:
    import jax
    import numpy as np

    from repro import Fleet

    for _, req in workload:
        served = results[req.rid + rid_offset]
        fleet = Fleet.from_pairs([(req.grid, req.campaign)], pad_floors=served.signature)
        direct = fleet.run(
            req.theta, replicas=req.n_replicas, key=jax.random.PRNGKey(req.seed)
        )
        # the serving path counts no loop steps (SimResult.steps stays None)
        for f in (f for f in direct._fields if f != "steps"):
            a = np.asarray(getattr(direct, f))[0]
            b = np.asarray(getattr(served.result, f))
            assert np.array_equal(a, b), f"request {req.rid}: served {f} != Fleet.run"


def phase_serving(n: int = SERVE_REQUESTS) -> dict:
    import numpy as np

    from repro.core import engine
    from repro.serve import ServeConfig, SimServer

    workload = serve_workload(n, SERVE_REPLICAS)
    server = SimServer(ServeConfig(slots=SERVE_SLOTS, replicas=SERVE_REPLICAS))
    with engine.count_bank_traces() as cold_traces:
        t0 = time.perf_counter()
        cold_results = serve_pass(server, workload)
        cold = time.perf_counter() - t0
    cold_traces = cold_traces.count
    with engine.count_bank_traces() as warm_traces:
        t0 = time.perf_counter()
        warm_results = serve_pass(server, workload, rid_offset=n)
        warm = time.perf_counter() - t0
    warm_traces = warm_traces.count
    assert len(cold_results) == n and len(warm_results) == n
    assert warm_traces == 0, f"warm serving traced {warm_traces}"
    assert_served_matches_fleet(cold_results, workload)
    assert_served_matches_fleet(warm_results, workload, rid_offset=n)

    bank = next(iter(server.banks.values()))
    lat = np.asarray([r.latency for r in warm_results.values()])
    return {
        "phase": "serving", "requests": 2 * n, "slots": SERVE_SLOTS,
        "replicas": SERVE_REPLICAS, "rate_per_s": SERVE_RATE,
        "signatures": len(server.banks),
        "cold_s": cold, "warm_s": warm,
        "bank_traces_cold": cold_traces,
        "bank_traces_warm": warm_traces,
        "warm_latency_p50_s": float(np.percentile(lat, 50)),
        "served_rows_bitwise_checked": 2 * n,
        "window_step_kernel": has_kernel(
            engine._banked_window_step, bank.resident.spec, bank._params_dev,
            bank.carry, backend=None, leap=bank.leap, window=bank.window,
        ),
    }


# ---------------------------------------------------------------------------
# --chips 4: the sharded fleet and server against one chip
# ---------------------------------------------------------------------------

def phase_four_chips(devices: int = 4, n: int = FLEET_SCENARIOS,
                     replicas: int = FLEET_REPLICAS) -> dict:
    import jax

    from repro import Fleet
    from repro.core import engine
    from repro.serve import ServeConfig, SimServer

    pairs = fleet_pairs(n)
    key = jax.random.PRNGKey(0)
    record = {"phase": "four_chips", "devices": devices, "scenarios": n,
              "replicas": replicas}
    for leap in (False, True):
        mode = "leap" if leap else "tick"
        one = Fleet.from_pairs(pairs, n_buckets=FLEET_BUCKETS, leap=leap)
        sharded = Fleet.from_pairs(
            pairs, n_buckets=FLEET_BUCKETS, leap=leap, devices=devices
        )
        assert all(b.bank.n_scenarios % devices == 0 for b in sharded.bank.buckets)
        ref, one_s = timed(lambda: one.run(replicas=replicas, key=key))
        with engine.count_bank_traces() as cold_traces:
            out, cold = timed(lambda: sharded.run(replicas=replicas, key=key))
        cold_traces = cold_traces.count
        with engine.count_bank_traces() as warm_traces:
            again, warm = timed(lambda: sharded.run(replicas=replicas, key=key))
        warm_traces = warm_traces.count
        assert warm_traces == 0, f"{mode}: warm sharded run traced {warm_traces}"
        assert_bitwise(ref, out, f"{mode}: Fleet(devices={devices}) vs one chip")
        assert_bitwise(out, again, f"{mode}: sharded warm vs cold")
        b0 = sharded.bank.buckets[0]
        s0 = b0.bank.n_scenarios
        record[mode] = {
            "buckets": len(sharded.bank.buckets),
            "one_chip_cold_s": one_s, "cold_s": cold, "warm_s": warm,
            "bank_traces_cold": cold_traces, "bank_traces_warm": warm_traces,
            "bitwise_vs_one_chip": True,
            "window_step_kernel": window_step_has_kernel(
                b0.bank, engine.make_bank_params(b0.bank),
                jax.random.split(key, s0 * replicas).reshape(s0, replicas, 2),
                leap=leap, window=engine._resolve_window(None, leap),
                mesh=sharded._resolve_mesh(),
            ),
        }

    workload = serve_workload(SHARDED_SERVE_REQUESTS, SERVE_REPLICAS, seed=1)
    server = SimServer(
        ServeConfig(slots=SERVE_SLOTS, replicas=SERVE_REPLICAS), devices=devices
    )
    t0 = time.perf_counter()
    results = serve_pass(server, workload)
    record["serve_s"] = time.perf_counter() - t0
    assert len(results) == len(workload)
    assert_served_matches_fleet(results, workload)
    record["served_rows_bitwise_checked"] = len(workload)
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fleet and server paths")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    if ops._resolve(None) != "pallas":
        fail(f"kernel backend resolves to {ops._resolve(None)!r}, not 'pallas'")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} device(s)")

    if args.chips == 4:
        emit(phase_four_chips())
    else:
        for phase in (phase_calibration, phase_fleet, phase_serving):
            emit(phase())
    emit({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }})


if __name__ == "__main__":
    main()
