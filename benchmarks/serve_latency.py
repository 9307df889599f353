"""Serving harness: ``repro.serve.SimServer`` latency + steady throughput.

Drives the seeded open-loop synthetic workload (Poisson arrivals,
heterogeneous campaigns from the scenario-family registry) against a
persistent server and measures what a batch script cannot: per-request
latency under continuous batching. Two baselines frame the steady-state
scenarios/sec:

- **batch-of-one** — one ``Fleet.run`` dispatch per request with every
  trace pre-warmed (the architecture a request API naively inherits;
  its real-world cold cost — a multi-second trace per new campaign
  shape — is what signature routing amortizes away, so the warm number
  reported here is its best case).
- **warm batch** — one warm ``Fleet.run`` over the whole request set at
  once. The server must stay >= 0.8x of the default (monolithic-bank)
  batch throughput — asserted on full runs. The bucketed
  (``n_buckets=8``) batch is also reported un-asserted: it is the
  engine's tuned offline ceiling, and the gap between it and the served
  rate is slot-occupancy waste — exactly the measurement the ROADMAP
  straggler-bucket cost model consumes (see ``metrics.slot_banks``).

    PYTHONPATH=src python benchmarks/serve_latency.py \
        [--requests 64] [--slots 8] [--rate 200] [--out BENCH_serve.json]

    PYTHONPATH=src python benchmarks/serve_latency.py --smoke   # CI guard

Every run (smoke included) asserts the serving contracts of
CONTRACTS.md §8 across three modes — batch, sharded, and warm-restart:
served results **bitwise equal** a direct ``Fleet.run`` of the same
scenario, and the steady phase — after one warm-up probe per pad
signature, submitted widest-first so up-tier coalescing (when enabled)
finds its wide banks already warm — admits every remaining request with
**zero** banked-engine retraces (a bank pre-traces its whole ladder at
construction). On a multi-device host (the CI 8-virtual-device job) the
server itself runs sharded (``devices=``), so the same assertions cover
the sharded overlap-scheduling path (a one-device run reports that the
sharded mode was skipped), and every run restarts a server against a
``warm_dir`` store and asserts the restart loads templates and retraces
nothing. ``--smoke`` writes ``BENCH_serve_smoke.json``; the tracked
``BENCH_serve.json`` is only rewritten by full runs. The report carries
the overlap scheduler's observability surface — per-bank rung
histograms, the coalesce count, the admit/dispatch/sync/retire wall
split of the scheduling rounds — plus per-slot occupancy, idle-window
fraction, and realized ticks per signature bank (the measurement inputs
of the ROADMAP straggler-bucket cost model); the smoke asserts those
fields exist in every mode's report. Full runs additionally assert the
throughput floors: ``serve_vs_warm_batch >= 0.8``,
``serve_vs_bucketed_batch >= 0.7``, and steady ``p99_ms <= 826``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SMOKE = dict(requests=24, slots=4, replicas=1, rate=500.0, scale=0.5,
             rungs=None, coalesce=True)  # default 3-rung ladder + up-tier
                                         # coalescing: CI exercises both
                                         # overlap-scheduler paths
FULL = dict(requests=64, slots=2, replicas=4, rate=200.0, scale=4.0,
            window=64, rungs=(16, 64), coalesce=False)
# Measured on the tracked workload (64 heavy requests, 9 signatures, one
# shared CPU device): live occupancy never exceeds ~2 rows per bank while
# a window executes every slot lane, frozen or not — so 2 slots at W=64
# with the W/4 down-rung (fast slot turnover near completions) beats
# every wider/deeper variant (slots=4 W=128 runs 2.3x slower). The 4W
# up-rung and up-tier coalescing are both disabled here: on a single
# compute-bound device they concentrate the hottest queue's tail and
# push steady p99 past the 826 ms floor (spill "capacity" in another
# bank's idle lanes is an illusion when all banks serialize on one
# device; the smoke keeps both paths covered).
SMOKE_BUCKETED_FLOOR = 0.05  # smoke-size serve/bucketed ratio guard: the
                             # tiny workload is pure host overhead against
                             # a compile-excluded device ceiling, so the
                             # absolute ratio stays far below full runs


def _percentiles(xs):
    import numpy as np

    a = np.asarray(xs, np.float64)
    return {
        "p50_ms": round(float(np.percentile(a, 50)) * 1e3, 2),
        "p90_ms": round(float(np.percentile(a, 90)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 2),
        "mean_ms": round(float(a.mean()) * 1e3, 2),
    }


def _assert_parity(server, req, signature):
    """Served row == direct ``Fleet.run`` of the same scenario, bitwise."""
    import jax
    import numpy as np

    from repro.core.fleet import Fleet

    res = server.poll(req.rid)
    assert res is not None, f"request {req.rid} not served"
    fleet = Fleet.from_pairs(
        [(req.grid, req.campaign)], pad_floors=signature
    )
    direct = fleet.run(
        req.theta, replicas=req.n_replicas, key=jax.random.PRNGKey(req.seed)
    )
    # the serving path counts no loop steps (SimResult.steps stays None)
    for f in (f for f in direct._fields if f != "steps"):
        a = np.asarray(getattr(direct, f))[0]
        b = np.asarray(getattr(res.result, f))
        assert np.array_equal(a, b), (
            f"served request {req.rid} diverged from Fleet.run in {f!r}"
        )


def serve_section(args, workload, sig_of, *, devices=None, warm_dir=None):
    """Probe-warm a server, run the steady open-loop phase, assert the
    zero-retrace contract, and return (report-dict, server, results)."""
    from repro.core import engine
    from repro.serve import ServeConfig, SimRequest, SimServer
    from repro.serve.cache import signature_volume

    slots = args.slots
    if devices is not None and slots % devices:
        slots = ((slots // devices) + 1) * devices
    server = SimServer(
        ServeConfig(
            slots=slots,
            replicas=args.replicas,
            window=args.window,
            rungs=getattr(args, "rungs", None),
            coalesce=getattr(args, "coalesce", True),
            warm_dir=warm_dir,
        ),
        devices=devices,
    )

    # -- warm-up: one probe per distinct pad signature, widest first --------
    # A bank pre-traces its whole dispatch set (admission merge + one step
    # per ladder rung + snapshot) at construction, so one probe per
    # signature suffices. Volume-descending order makes the wide banks
    # exist before the narrow signatures route, so coalescing consolidates
    # the narrow traffic up-tier instead of fragmenting one bank per
    # signature.
    probe_of = {}
    for _, req in workload:
        probe_of.setdefault(sig_of[req.rid], req)
    rid = 1_000_000
    for sig, req in sorted(
        probe_of.items(), key=lambda kv: -signature_volume(kv[0])
    ):
        server.submit(
            SimRequest(
                rid=rid, grid=req.grid, campaign=req.campaign,
                theta=req.theta, n_replicas=req.n_replicas,
                seed=req.seed + 7919, name=f"probe_{rid}",
            )
        )
        rid += 1
    t0 = time.perf_counter()
    server.drain()
    warmup_s = time.perf_counter() - t0

    # -- steady phase: open-loop submission, zero retraces ------------------
    t0 = time.perf_counter()
    with engine.count_bank_traces() as traces:
        for arrival, req in workload:
            while time.perf_counter() - t0 < arrival:
                server.step()
            server.submit(req)
            server.step()
        results = server.drain()
    steady_wall = time.perf_counter() - t0
    assert traces.count == 0, (
        f"steady state retraced {traces.count}x across {len(workload)} "
        "admissions — slot admission changed a trace signature"
    )
    assert sorted(r.rid for r in results) == [r.rid for _, r in workload], (
        "drain lost or duplicated steady-phase requests"
    )

    n = len(workload)
    m = server.metrics()
    rung_hist = {}
    for bank_m in m["slot_banks"].values():
        for k, v in bank_m["rung_windows"].items():
            rung_hist[k] = rung_hist.get(k, 0) + v
    report = {
        "devices": devices or 1,
        "slots": slots,
        "window": server.window,
        "rungs": m["rungs"],
        "rung_windows": rung_hist,
        "coalesced": m["coalesced"],
        "banks": len(server.banks),
        "signatures": len(probe_of),
        "wall_split_s": m["wall_split_s"],
        "warmup_probes": rid - 1_000_000,
        "warmup_s": round(warmup_s, 3),
        "steady_wall_s": round(steady_wall, 3),
        "steady_scenarios_per_s": round(n / steady_wall, 2),
        "steady_retraces": traces.count,
        "latency": _percentiles([r.latency for r in results]),
        "queue_delay": _percentiles([r.queue_delay for r in results]),
    }
    return report, server, results


# observability fields the CI smoke asserts on every mode's report (batch,
# sharded, warm-restart): the rung histogram, the coalesce count, and the
# dispatch-vs-sync wall split of the overlapped rounds
REQUIRED_OBS_FIELDS = ("rungs", "rung_windows", "coalesced", "wall_split_s")


def _assert_obs_fields(section: dict, name: str) -> None:
    missing = [f for f in REQUIRED_OBS_FIELDS if f not in section]
    assert not missing, f"{name} report is missing {missing}"


def warm_restart_section(args, workload, sig_of):
    """Serve a subset cold through a ``warm_dir`` store, restart the server
    on the same store, and assert the restart is warm: slot templates load
    from disk, the whole run (bank construction included) retraces nothing,
    and served rows keep bitwise ``Fleet.run`` parity."""
    import tempfile

    from repro.core import engine
    from repro.serve import ServeConfig, SimServer

    sub = workload[: min(8, len(workload))]
    with tempfile.TemporaryDirectory() as warm:
        cfg = ServeConfig(
            slots=args.slots, replicas=args.replicas, window=args.window,
            warm_dir=warm,
        )
        cold = SimServer(cfg)
        for _, req in sub:
            cold.submit(req)
        cold.drain()

        restarted = SimServer(cfg)
        t0 = time.perf_counter()
        with engine.count_bank_traces() as traces:
            for _, req in sub:
                restarted.submit(req)
            results = restarted.drain()
        wall = time.perf_counter() - t0
        assert restarted.cache.warm_loads >= 1, (
            "warm restart loaded no slot template from the warm store"
        )
        assert traces.count == 0, (
            f"warm restart retraced {traces.count}x — the restarted banks "
            "must reuse every cached trace"
        )
        assert sorted(r.rid for r in results) == sorted(
            req.rid for _, req in sub
        )
        for _, req in sub[:2]:
            _assert_parity(restarted, req, sig_of[req.rid])
        m = restarted.metrics()
        rung_hist = {}
        for bank_m in m["slot_banks"].values():
            for k, v in bank_m["rung_windows"].items():
                rung_hist[k] = rung_hist.get(k, 0) + v
        return {
            "requests": len(sub),
            "warm_loads": restarted.cache.warm_loads,
            "steady_retraces": traces.count,
            "wall_s": round(wall, 3),
            "rungs": m["rungs"],
            "rung_windows": rung_hist,
            "coalesced": m["coalesced"],
            "wall_split_s": m["wall_split_s"],
        }


def _build_workload(args):
    from repro.core.workload import compile_campaign
    from repro.serve import ServeConfig, synthetic_workload
    from repro.serve.cache import pad_signature

    workload = synthetic_workload(
        args.requests, rate=args.rate, seed=args.seed, scale=args.scale,
        replicas=args.replicas,
    )
    floors = ServeConfig().pad_floors
    sig_of = {
        req.rid: pad_signature(
            compile_campaign(req.grid, req.campaign), floors=floors
        )
        for _, req in workload
    }
    return workload, sig_of


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrival rate (requests/s)")
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for k, v in (SMOKE if args.smoke else FULL).items():
        if getattr(args, k, None) is None:
            setattr(args, k, v)
    if args.out is None:
        args.out = "BENCH_serve_smoke.json" if args.smoke else "BENCH_serve.json"

    import jax

    from repro.core.fleet import Fleet
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    t_start = time.time()
    workload, sig_of = _build_workload(args)
    pairs = [(req.grid, req.campaign) for _, req in workload]
    n = len(pairs)

    # -- served: sharded over this process's devices when it has several ---
    devices = jax.device_count() if jax.device_count() > 1 else None
    if devices is None:
        print("sharded serving skipped: 1 device; the server runs unsharded",
              file=sys.stderr)
    serve_report, server, results = serve_section(
        args, workload, sig_of, devices=devices
    )

    # parity: every request on smoke, a seeded sample on full runs
    sample = workload if args.smoke else workload[:: max(1, n // 8)]
    for _, req in sample:
        _assert_parity(server, req, sig_of[req.rid])

    # -- baseline 1: warm batch Fleet.run over the whole request set --------
    fleet = Fleet.from_pairs(pairs)
    run = lambda: fleet.run(replicas=args.replicas)
    t0 = time.time()
    jax.block_until_ready(run())
    batch_cold = time.time() - t0
    batch_warm = float("inf")
    for _ in range(3):
        t0 = time.time()
        jax.block_until_ready(run())
        batch_warm = min(batch_warm, time.time() - t0)

    # the tuned offline ceiling: same set, max_ticks-bucketed sub-banks
    bucketed = Fleet.from_pairs(pairs, n_buckets=8)
    jax.block_until_ready(bucketed.run(replicas=args.replicas))
    bucketed_warm = float("inf")
    for _ in range(3):
        t0 = time.time()
        jax.block_until_ready(bucketed.run(replicas=args.replicas))
        bucketed_warm = min(bucketed_warm, time.time() - t0)

    # -- baseline 2: batch-of-one — one warm Fleet.run per request ----------
    ones = [
        Fleet.from_pairs([p], pad_floors=sig_of[req.rid])
        for p, (_, req) in zip(pairs, workload)
    ]
    for f in ones:  # warm every trace (signatures shared across requests)
        jax.block_until_ready(f.run(replicas=args.replicas))
    t0 = time.time()
    for f in ones:
        jax.block_until_ready(f.run(replicas=args.replicas))
    batch1_warm = time.time() - t0

    report = {
        "requests": n,
        "replicas": args.replicas,
        "rate_per_s": args.rate,
        "scale": args.scale,
        "seed": args.seed,
        "served": serve_report,
        "batch_cold_s": round(batch_cold, 3),
        "batch_warm_s": round(batch_warm, 4),
        "batch_warm_scenarios_per_s": round(n / batch_warm, 2),
        "batch_bucketed_warm_s": round(bucketed_warm, 4),
        "batch_bucketed_scenarios_per_s": round(n / bucketed_warm, 2),
        "serve_vs_bucketed_batch": round(
            serve_report["steady_scenarios_per_s"] / (n / bucketed_warm), 2
        ),
        "batch_of_one_warm_s": round(batch1_warm, 3),
        "batch_of_one_scenarios_per_s": round(n / batch1_warm, 2),
        "serve_vs_batch_of_one": round(
            serve_report["steady_scenarios_per_s"] / (n / batch1_warm), 2
        ),
        "serve_vs_warm_batch": round(
            serve_report["steady_scenarios_per_s"] / (n / batch_warm), 2
        ),
        "metrics": server.metrics(),
    }
    report["warm_restart"] = warm_restart_section(args, workload, sig_of)
    report["total_s"] = round(time.time() - t_start, 1)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))

    assert serve_report["steady_retraces"] == 0
    _assert_obs_fields(report["served"], "served")
    _assert_obs_fields(report["warm_restart"], "warm_restart")
    if args.smoke:
        # modest smoke floor: the tiny workload (light rows, 1 replica)
        # maximizes host overhead per unit of device work, so the served /
        # bucketed ratio sits far below the full-run number — the floor
        # guards against scheduler regressions, not absolute throughput.
        # Only meaningful unsharded: on a virtual-device host the server
        # pays shard_map collectives for zero real parallelism while the
        # bucketed baseline runs unsharded, so that leg asserts parity /
        # retraces / observability, not throughput.
        if serve_report["devices"] == 1:
            assert report["serve_vs_bucketed_batch"] >= SMOKE_BUCKETED_FLOOR, (
                f"smoke serve_vs_bucketed_batch "
                f"{report['serve_vs_bucketed_batch']} fell below the "
                f"{SMOKE_BUCKETED_FLOOR} floor"
            )
    else:
        assert report["serve_vs_warm_batch"] >= 0.8, (
            f"steady served throughput is {report['serve_vs_warm_batch']}x "
            "the warm batch Fleet.run ceiling (contract: >= 0.8x)"
        )
        assert report["serve_vs_bucketed_batch"] >= 0.7, (
            f"steady served throughput is {report['serve_vs_bucketed_batch']}x"
            " the bucketed-batch ceiling (contract: >= 0.7x after the "
            "overlap-scheduling rework)"
        )
        assert serve_report["latency"]["p99_ms"] <= 826, (
            f"steady p99 {serve_report['latency']['p99_ms']} ms regressed "
            "past the pre-rework 826 ms"
        )


if __name__ == "__main__":
    main()
