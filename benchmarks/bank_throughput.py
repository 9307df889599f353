"""Fleet throughput harness: banked engine vs per-scenario Python loop.

The loop baseline is what the pre-bank architecture forced on every consumer
of scenario diversity: one ``simulate_batch`` dispatch per (grid, campaign)
pair, each distinct campaign shape paying its own jit trace. The fleet runs
the identical fleet x replicas through one padded trace per work-cost-packed
sub-bank (``repro.Fleet`` — the façade this harness now drives end to end:
compile with shared pad floors, run, stream).

    PYTHONPATH=src python benchmarks/bank_throughput.py \
        [--scenarios 64] [--replicas 4] [--buckets 8] [--out BENCH_bank.json]

    PYTHONPATH=src python benchmarks/bank_throughput.py --smoke   # CI guard

Emits ``BENCH_bank.json`` with cold (trace included — the cost scenario
diversity actually incurs) and warm (all traces cached) walls, per-bucket
warm throughput (tick bound, realized final tick, resolved window, cost
share), the packing-efficiency section (``bucket_packing``: per-bucket
modelled costs, the packing budget, and the cost-normalized throughput
spread), the fused-window sweep (``window_sweep``) with
``fused_vs_per_tick_speedup`` (auto window vs window=1 on the bucketed
fleet), the manual-banked-kernel vs vmap lowering delta on the monolithic
bank, streaming-fleet walls, and the speedups future PRs must not regress:
``speedup_warm`` (bucketed warm vs cached loop), ``speedup_fresh_fleet``
(steady-state scenario diversity), ``bank_fresh_fleet_retraces`` and
``stream_retraces_after_first`` (both must stay 0 for fixed pad/bucket
shapes). Windowed-vs-per-tick and bucketed-vs-monolithic **bitwise**
parity are asserted on every run.

Per-bucket throughput metric: buckets deliberately carry *equal work*, not
equal scenario counts, so raw scenarios/sec is no longer comparable across
buckets (a 3-scenario long-tail bucket at pad 58 does as much work as a
19-scenario bucket at pad 10). ``scenarios_per_sec`` therefore reports
**cost-normalized equivalent scenarios/sec** — the bucket's dispatch-
shifted share of the fleet's modelled work, expressed in whole-fleet
scenarios, divided by its wall (``n * cost_share / warm_s``) — which is
flat across buckets exactly when the packing equalized real per-bucket
walls; the raw member count rate is kept as ``scenarios_per_sec_raw``.
The min/max spread of the normalized rate is asserted <= 1.5x on every
run (the count-packed plan it replaced measured 4.4x).
``--smoke`` runs a tiny fleet through every section and every assertion,
writing the report to ``BENCH_smoke.json`` (the tracked
``BENCH_bank.json`` is only rewritten by full runs).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# sharded-section grids: scenario counts x device counts. The section runs
# in this process over its own devices (a CPU run gets several from
# XLA_FLAGS=--xla_force_host_platform_device_count=N); device counts above
# what the process has are left out, and with fewer than 2 the section is
# skipped
SHARDED_FULL_S = [256, 4096, 65536]
SHARDED_FULL_D = [1, 2, 4, 8]
SHARDED_SMOKE_S = [32]
SHARDED_SMOKE_D = [1, 2]
SHARDED_BASE = 16  # distinct scenarios tiled up to each S
SHARDED_REPLICAS = 4
SHARDED_SCALE = 1.0  # workload scale: rows must be heavy enough that
                     # per-row compute (not per-window dispatch) dominates,
                     # or per-shard early exit can't pay for D extra loops
SHARDED_PARITY_MAX_S = 4096  # bitwise sharded-vs-unsharded check cap


def _tile_bank(bank, order, reps):
    """Tile a small bank into a large one: rows reordered by ``order`` then
    each repeated ``reps`` times **consecutively** (np.repeat), so scenarios
    of similar simulated length land in contiguous runs. Under shard_map
    that contiguity is what device-local early exit converts into speedup:
    a shard holding only short scenarios stops dispatching windows long
    before the shard holding the stragglers. Source tables are dropped
    (names are tiled); everything else is a dense-array op."""
    import numpy as np

    from repro.core.workload import ScenarioBank

    arrays = {}
    for f in dataclasses.fields(ScenarioBank):
        if f.name in ("protocol_names", "names", "tables"):
            continue
        arrays[f.name] = np.repeat(
            np.asarray(getattr(bank, f.name))[order], reps, axis=0
        )
    names = [
        f"{bank.names[i]}#{j}" for i in order for j in range(reps)
    ]
    return ScenarioBank(
        **arrays,
        protocol_names=list(bank.protocol_names),
        names=names,
        tables=[],
    )


def sharded_cell(d: int, s: int, seed: int) -> dict:
    """One cell of the ``sharded`` section: time the S-scenario tiled fleet
    on a ``d``-device mesh of this process and return its JSON entry."""
    import jax
    import numpy as np

    from repro.core.engine import make_bank_params, simulate_bank
    from repro.core.scenarios import sample_scenarios
    from repro.core.workload import compile_bank

    R = SHARDED_REPLICAS
    pairs = sample_scenarios(n=SHARDED_BASE, seed=seed, scale=SHARDED_SCALE)
    base = compile_bank(pairs)
    # ascending tick bound -> contiguous length clusters after tiling
    order = np.argsort(np.asarray(base.max_ticks), kind="stable")
    bank = _tile_bank(base, order, max(1, s // SHARDED_BASE))
    params = make_bank_params(bank)
    keys = jax.random.split(jax.random.PRNGKey(seed), s * R).reshape(s, R, 2)

    run = lambda: simulate_bank(
        bank, params, keys, leap=True, bucketed=False, mesh=d
    )
    t0 = time.time()
    jax.block_until_ready(run())
    cold = time.time() - t0
    warm = float("inf")
    for _ in range(3):
        t0 = time.time()
        out = run()
        jax.block_until_ready(out)
        warm = min(warm, time.time() - t0)

    parity = s <= SHARDED_PARITY_MAX_S
    if parity:
        ref = simulate_bank(bank, params, keys, leap=True, bucketed=False)
        for f in out._fields:
            a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(out, f))
            assert np.array_equal(a, b), (
                f"sharded (D={d}) vs unsharded mismatch in {f}"
            )
    return {
        "scenarios": s,
        "devices": d,
        "cold_s": round(cold, 3),
        "warm_s": round(warm, 4),
        "scenarios_per_sec": round(s / warm, 2),
        "parity_checked": parity,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", type=int, default=64)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--buckets", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-ticks", type=int, default=None,
                    help="uniform tick cap; default: each scenario's own "
                         "(bandwidth-aware) safe upper bound, which is what "
                         "makes max_ticks bucketing meaningful")
    ap.add_argument("--leap", action=argparse.BooleanOptionalAction, default=True)
    ap.add_argument("--stream-chunks", type=int, default=4,
                    help="chunks the streaming section splits the fleet into")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fleet, all sections + assertions; writes "
                         "BENCH_smoke.json instead of the tracked report")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.smoke:
        args.scenarios, args.replicas, args.buckets = 8, 2, 2
        args.stream_chunks = 2
    if args.out is None:
        args.out = "BENCH_smoke.json" if args.smoke else "BENCH_bank.json"

    import jax
    import numpy as np

    from repro import Fleet
    from repro.core import engine as engine_lib
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.core.engine import (
        SimSpec,
        count_bank_traces,
        make_params,
        reset_bank_trace_count,
        simulate_batch,
    )
    from repro.core.scenarios import sample_scenarios

    n, r, k = args.scenarios, args.replicas, args.buckets
    pairs = sample_scenarios(n=n, seed=args.seed)
    pairs2 = sample_scenarios(n=n, seed=args.seed + 7919)  # a fresh fleet
    # shared global pad floors so both fleets hit one monolithic trace ...
    probe1 = Fleet.from_pairs(pairs, max_ticks=args.max_ticks)
    probe2 = Fleet.from_pairs(pairs2, max_ticks=args.max_ticks)
    pads = tuple(max(a, b) for a, b in zip(probe1.pads, probe2.pads))
    # ... and shared per-bucket pad floors so both fleets reuse every bucket
    # trace.  Cost packing realizes a *variable* bucket count, so the
    # cross-fleet join pins fleet 2 to fleet 1's packing plan via
    # ``bucket_counts`` (per-bucket group sizes in packed order) — the two
    # plans then have identical bucket counts and member counts, and the
    # per-bucket pad floors can be joined elementwise
    b1 = Fleet.from_pairs(pairs, max_ticks=args.max_ticks, n_buckets=k,
                          pad_floors=pads, leap=args.leap)
    counts = b1.bucket_scenario_counts
    b2 = Fleet.from_pairs(pairs2, max_ticks=args.max_ticks, n_buckets=k,
                          pad_floors=pads, bucket_counts=counts,
                          leap=args.leap)
    bucket_floors = [
        tuple(max(a, b) for a, b in zip(x, y))
        for x, y in zip(b1.bucket_pad_floors, b2.bucket_pad_floors)
    ]
    fleet = Fleet.from_pairs(
        pairs, max_ticks=args.max_ticks, n_buckets=k, pad_floors=pads,
        bucket_counts=counts, bucket_pad_floors=bucket_floors, leap=args.leap,
    )
    fleet2 = Fleet.from_pairs(
        pairs2, max_ticks=args.max_ticks, n_buckets=k, pad_floors=pads,
        bucket_counts=counts, bucket_pad_floors=bucket_floors, leap=args.leap,
    )
    bank, bank2 = fleet.bank, fleet2.bank
    keys = jax.random.split(jax.random.PRNGKey(args.seed), n * r).reshape(n, r, 2)

    def timed(fn):
        t0 = time.time()
        out = fn()
        jax.block_until_ready(out)
        return out, time.time() - t0

    def timed_warm(fn, repeats: int = 5):
        """Best-of-N wall for warm (all-traces-cached) sections: the warm
        dispatches are ~10s of ms, where single-shot timings are dominated
        by scheduler noise. Applied identically to the loop baseline and
        the fleet, so the speedup ratios stay honest."""
        best = float("inf")
        out = None
        for _ in range(repeats):
            out, dt = timed(fn)
            best = min(best, dt)
        return out, best

    # ---- per-scenario Python loop (the pre-bank architecture) -------------
    tables = bank.tables
    specs = [
        SimSpec.from_table(t, max_ticks=int(bank.max_ticks[i]))
        for i, t in enumerate(tables)
    ]
    params_i = [make_params(t) for t in tables]

    def run_loop():
        return [
            simulate_batch(specs[i], params_i[i], keys[i], leap=args.leap).ticks
            for i in range(n)
        ]

    _, loop_cold = timed(run_loop)  # pays one trace per distinct campaign shape
    _, loop_warm = timed_warm(run_loop)

    # ---- monolithic bank: vmap lowering vs manual banked tick body --------
    run_mono = lambda lowering: fleet.run(
        keys=keys, lowering=lowering, bucketed=False
    )
    timed(lambda: run_mono("vmap"))
    _, vmap_mono_warm = timed_warm(lambda: run_mono("vmap"))
    mono_res, _ = timed(lambda: run_mono("banked"))
    _, banked_mono_warm = timed_warm(lambda: run_mono("banked"))

    # ---- bucketed fleet (the warm-path fix) -------------------------------
    reset_bank_trace_count()
    run_fleet = lambda: fleet.run(keys=keys)
    with count_bank_traces() as cold_traces:
        bank_res, bank_cold = timed(run_fleet)
    _, bank_warm = timed_warm(run_fleet)
    bank_traces = cold_traces.count

    # cost-packed sub-banks must stay an implementation detail: the scattered
    # result is asserted **bitwise** equal to the monolithic bank on every run
    for f in ("transfer_time", "conth_mb", "conpr_mb", "done", "ticks",
              "start_tick"):
        a = np.asarray(getattr(bank_res, f))
        b = np.asarray(getattr(mono_res, f))
        assert (a == b).all(), (
            f"bucketed vs monolithic mismatch in {f}: max |delta| = "
            f"{np.abs(a.astype(np.float64) - b.astype(np.float64)).max()}"
        )

    # ---- windowed vs per-tick: parity (bitwise) + the fused speedup -------
    # parity is asserted at an explicit K>1 (not the auto default, which
    # resolves to 1 on CPU hosts and would compare a program to itself);
    # the reported window is the one the timed runs actually resolved
    # (REPRO_TICK_WINDOW included), not just the backend default
    window = engine_lib._resolve_window(None, args.leap)
    res_k1 = fleet.run(keys=keys, window=1)
    res_kw = fleet.run(keys=keys, window=16)
    for f in ("transfer_time", "conth_mb", "conpr_mb", "done", "ticks",
              "start_tick"):
        for name, res in (("auto", bank_res), ("K=16", res_kw)):
            a = np.asarray(getattr(res, f))
            b = np.asarray(getattr(res_k1, f))
            assert (a == b).all(), (
                f"windowed ({name}) vs per-tick (K=1) mismatch in {f}: "
                f"max |delta| = "
                f"{np.abs(a.astype(np.float64) - b.astype(np.float64)).max()}"
            )
    _, bank_warm_k1 = timed_warm(lambda: fleet.run(keys=keys, window=1))

    sweep_ks = [1, 16] if args.smoke else [1, 4, 8, 16, 32, 64]
    window_sweep = []
    for kw in sweep_ks:
        run_k = lambda kw=kw: fleet.run(keys=keys, window=kw)
        timed(run_k)  # pay the per-window-size trace outside the timing
        _, warm_k = timed_warm(run_k)
        window_sweep.append({"window": kw, "warm_s": round(warm_k, 4)})
    # seed the persisted autotuner table from the full sweep (smoke fleets
    # are too small/noisy to trust); default_tick_window() reads this back
    window_table_path = None
    if not args.smoke:
        best_k = min(window_sweep, key=lambda e: e["warm_s"])["window"]
        mode = "leap" if args.leap else "tick"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        window_table_path = os.path.relpath(str(engine_lib.record_window_sweep(
            jax.default_backend(), **{mode: best_k}
        )), repo)

    # per-bucket warm throughput: each sub-bank timed as its own dispatch.
    # Buckets carry equal *work*, not equal counts, so ``scenarios_per_sec``
    # is cost-normalized (the bucket's dispatch-shifted share of the fleet's
    # modelled work in whole-fleet-scenario units, over its wall); the raw
    # member-count rate rides along as ``scenarios_per_sec_raw``
    bank_ticks = np.asarray(bank_res.ticks)  # [N, R] realized final ticks
    subs = []
    for bucket in bank.buckets:
        sub_fleet = Fleet(bucket.bank, leap=args.leap)
        ids = np.asarray(bucket.scenario_ids)
        subs.append((bucket, sub_fleet, keys[ids]))
        jax.block_until_ready(sub_fleet.run(keys=keys[ids]))  # warm
    # best-of-N with the buckets *interleaved* (round-robin), not timed as
    # per-bucket blocks: host scheduler drift then hits every bucket's
    # sample set equally instead of landing wholesale on whichever bucket
    # owned the slow stretch — the per-bucket spread is a tracked
    # assertion, so its estimator must not absorb block-local noise
    best = [float("inf")] * len(subs)
    for _ in range(25):
        for i, (_, sub_fleet, sub_keys) in enumerate(subs):
            _, dt = timed(lambda f=sub_fleet, sk=sub_keys: f.run(keys=sk))
            best[i] = min(best[i], dt)
    per_bucket = []
    for (bucket, sub_fleet, _), sub_warm in zip(subs, best):
        sub = bucket.bank
        bound = int(sub.max_ticks.max())
        ids = np.asarray(bucket.scenario_ids)
        per_bucket.append({
            "scenarios": len(bucket.scenario_ids),
            "pad_legs": sub.pad_legs,
            "pad_procs": sub.pad_procs,
            "pad_links": sub.pad_links,
            "tick_bound": bound,
            "realized_ticks": int(bank_ticks[ids].max()),
            # the window the engine actually resolved for this bucket
            "window": engine_lib._clamp_window(window, bound),
            "cost": round(bucket.cost, 1),
            "cost_share": round(bucket.cost_share, 4),
            "warm_s": round(sub_warm, 4),
            "scenarios_per_sec": round(n * bucket.cost_share / sub_warm, 2),
            "scenarios_per_sec_raw": round(
                len(bucket.scenario_ids) / sub_warm, 2),
        })

    # packing efficiency: what the cost model planned vs. what it realized.
    # ``cost_budget`` is the per-bucket close threshold the packer swept
    # with (slack x total/k); ``spread_warm`` is the min/max ratio of the
    # cost-normalized per-bucket rate — 1.0 means the model predicted every
    # bucket's wall perfectly; ``spread_warm_raw`` is the same ratio on raw
    # member counts, which equal-work packing deliberately does NOT equalize
    from repro.core import workload as workload_lib
    norm_rates = [e["scenarios_per_sec"] for e in per_bucket]
    raw_rates = [e["scenarios_per_sec_raw"] for e in per_bucket]
    total_cost = sum(b.cost for b in bank.buckets)
    slack = workload_lib._DEFAULT_BUCKET_SLACK
    packing_section = {
        "mode": bank.packing,
        "slack": slack,
        "cost_step_base": workload_lib._COST_STEP_BASE,
        "cost_dispatch_base": workload_lib._COST_DISPATCH_BASE,
        "n_buckets_hint": k,
        "n_buckets_realized": len(bank.buckets),
        "cost_budget": round(slack * total_cost / min(k, n), 1),
        "bucket_scenarios": [len(b.scenario_ids) for b in bank.buckets],
        "bucket_costs": [round(b.cost, 1) for b in bank.buckets],
        "bucket_cost_shares": [round(b.cost_share, 4) for b in bank.buckets],
        "spread_warm": round(max(norm_rates) / min(norm_rates), 2),
        "spread_warm_raw": round(max(raw_rates) / min(raw_rates), 2),
    }

    # ---- a FRESH fleet: the steady-state cost of scenario diversity -------
    # every new fleet re-pays the loop's per-shape traces; the bucketed
    # fleet reuses every per-bucket-shape trace
    specs2 = [
        SimSpec.from_table(t, max_ticks=int(bank2.max_ticks[i]))
        for i, t in enumerate(bank2.tables)
    ]
    params2_i = [make_params(t) for t in bank2.tables]
    _, loop_fresh = timed(lambda: [
        simulate_batch(specs2[i], params2_i[i], keys[i], leap=args.leap).ticks
        for i in range(n)
    ])
    with count_bank_traces() as fresh_traces:
        _, bank_fresh = timed(lambda: fleet2.run(keys=keys))
    fresh_retraces = fresh_traces.count

    # ---- streaming fleets: iterator of campaigns, one shared trace --------
    # the ROADMAP streaming item: chunked fixed-pad banks through the
    # monolithic-pad trace; after the first chunk, retraces must stay 0
    chunk = max(1, n // args.stream_chunks)
    stream_kw = dict(chunk=chunk, key=jax.random.PRNGKey(args.seed),
                     max_ticks=args.max_ticks)
    drain = lambda: [c.result.ticks for c in fleet.stream(iter(pairs2), **stream_kw)]
    with count_bank_traces() as stream_first:
        _, stream_cold = timed(drain)
    stream_first_traces = stream_first.count
    with count_bank_traces() as stream_rest:
        _, stream_warm = timed_warm(drain)
    stream_retraces = stream_rest.count

    # ---- sharded fleet: scenarios/sec vs device count ---------------------
    # in-process over this process's devices; cells assert bitwise
    # sharded-vs-unsharded parity at S <= SHARDED_PARITY_MAX_S
    n_dev = len(jax.devices())
    sharded_s = SHARDED_SMOKE_S if args.smoke else SHARDED_FULL_S
    sharded_d = [
        d for d in (SHARDED_SMOKE_D if args.smoke else SHARDED_FULL_D)
        if d <= n_dev
    ]
    sharded_speedup = None
    if n_dev < 2:
        sharded_section = {"skipped": f"{n_dev} device(s); needs 2 or more"}
        print("sharded section skipped: fewer than 2 devices", file=sys.stderr)
    else:
        sharded_entries = []
        for s in sharded_s:
            for d in sharded_d:
                entry = sharded_cell(d, s, args.seed)
                sharded_entries.append(entry)
                print(f"sharded S={s} D={d}: "
                      f"{entry['scenarios_per_sec']} scen/s", file=sys.stderr)
        s_top = max(sharded_s)
        tp = {
            e["devices"]: e["scenarios_per_sec"]
            for e in sharded_entries if e["scenarios"] == s_top
        }
        sharded_speedup = round(tp[max(sharded_d)] / tp[min(sharded_d)], 2)
        sharded_section = {
            "base_scenarios": SHARDED_BASE,
            "replicas": SHARDED_REPLICAS,
            "scale": SHARDED_SCALE,
            "leap": True,
            "device_counts": sharded_d,
            "entries": sharded_entries,
            "speedup_at_max_devices": sharded_speedup,
            "speedup_fleet_scenarios": s_top,
        }

    # simulated work: sum over (scenario, replica) of real legs x ticks run
    legs = np.asarray(bank.n_legs, np.float64)
    bank_ticks = np.asarray(bank_res.ticks, np.float64)  # [N, R]
    work = float((legs[:, None] * bank_ticks).sum())

    # identically-shaped buckets share one jit trace, so the cold trace count
    # equals the number of *distinct* bucket shapes, not the bucket count.
    # The shape key is everything the jit cache keys on per bucket: the
    # padded scenario count (shard padding included, hence n_scenarios
    # rather than len(scenario_ids)), the replica axis (a singleton
    # long-tail bucket is widened across replicas — the engine folds
    # ``_replica_fold(r)`` replicas onto the scenario axis, so its trace
    # runs at ``(fold, r // fold)`` instead of ``(1, r)``), the three pad
    # axes, and the *clamped* window static argument
    def _bucket_shape_key(b):
        s_b, r_eff = b.bank.n_scenarios, r
        if s_b == 1 and len(b.scenario_ids) == 1 and r > 1:
            fold = engine_lib._replica_fold(r)
            s_b, r_eff = fold, r // fold
        return (s_b, r_eff, b.bank.pad_legs, b.bank.pad_procs,
                b.bank.pad_links,
                engine_lib._clamp_window(window, int(b.bank.max_ticks.max())))

    distinct_shapes = len({_bucket_shape_key(b) for b in bank.buckets})

    report = {
        "n_scenarios": n,
        "n_replicas": r,
        "n_buckets": len(bank.buckets),
        "pad_legs": bank.pad_legs,
        "pad_procs": bank.pad_procs,
        "pad_links": bank.pad_links,
        "leap": bool(args.leap),
        "window": window,
        "window_table": window_table_path,
        "bank_traces": bank_traces,
        "bank_distinct_bucket_shapes": distinct_shapes,
        "loop_cold_s": round(loop_cold, 3),
        "loop_warm_s": round(loop_warm, 3),
        "bank_cold_s": round(bank_cold, 3),
        "bank_warm_s": round(bank_warm, 3),
        "bank_warm_k1_s": round(bank_warm_k1, 3),
        "fused_vs_per_tick_speedup": round(bank_warm_k1 / bank_warm, 2),
        # loud, machine-readable flag when the auto-resolved window loses
        # to per-tick K=1 — a stale/missing window-table entry, not noise,
        # is the usual cause; a sub-1 ratio must never pass silently
        "window_regression_warning": (
            None if bank_warm_k1 >= bank_warm else (
                f"auto window K={window} ({bank_warm:.3f}s warm) loses to "
                f"per-tick K=1 ({bank_warm_k1:.3f}s): the persisted window "
                "table is stale for this platform — re-record it with a "
                "full (non-smoke) bench run"
            )
        ),
        "window_sweep": window_sweep,
        "vmap_mono_warm_s": round(vmap_mono_warm, 3),
        "banked_mono_warm_s": round(banked_mono_warm, 3),
        "banked_vs_vmap_speedup": round(vmap_mono_warm / banked_mono_warm, 2),
        "realized_ticks": int(bank_ticks.max()),
        "bucket_packing": packing_section,
        "per_bucket_warm": per_bucket,
        "scenarios_per_sec_loop_cold": round(n / loop_cold, 2),
        "scenarios_per_sec_bank_cold": round(n / bank_cold, 2),
        "scenarios_per_sec_loop_warm": round(n / loop_warm, 2),
        "scenarios_per_sec_bank_warm": round(n / bank_warm, 2),
        "leg_ticks_per_sec_bank_warm": round(work / bank_warm, 0),
        "leg_ticks_per_sec_loop_warm": round(work / loop_warm, 0),
        "loop_fresh_fleet_s": round(loop_fresh, 3),
        "bank_fresh_fleet_s": round(bank_fresh, 3),
        "bank_fresh_fleet_retraces": fresh_retraces,
        "stream_chunk": chunk,
        "stream_cold_s": round(stream_cold, 3),
        "stream_warm_s": round(stream_warm, 3),
        "stream_retraces_after_first": stream_retraces,
        "sharded": sharded_section,
        "speedup_cold": round(loop_cold / bank_cold, 2),
        "speedup_warm": round(loop_warm / bank_warm, 2),
        "speedup_fresh_fleet": round(loop_fresh / bank_fresh, 2),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    assert bank_traces == distinct_shapes, (
        f"bucketed fleet traced {bank_traces} times for "
        f"{distinct_shapes} distinct bucket shapes"
    )
    assert fresh_retraces == 0, "fresh fleet must reuse every bucket trace"
    assert stream_first_traces == 1, (
        f"cold stream must trace exactly once (all chunks share one "
        f"fixed-pad shape), traced {stream_first_traces}"
    )
    assert stream_retraces == 0, (
        "streamed chunks must reuse the first chunk's trace"
    )
    assert packing_section["spread_warm"] <= 1.5, (
        f"cost-normalized per-bucket throughput spread "
        f"{packing_section['spread_warm']}x exceeds 1.5x: the work cost "
        f"model no longer predicts per-bucket walls "
        f"(rates: {sorted(norm_rates)})"
    )
    if not args.smoke and sharded_speedup is not None:
        assert sharded_speedup > 1.0, (
            f"sharding the S={s_top} fleet over {max(sharded_d)} devices "
            f"must beat 1 device, got {sharded_speedup}x"
        )
    if report["speedup_warm"] < 1.0:
        print(
            f"WARNING: warm bucketed fleet ({bank_warm:.3f}s) still trails the "
            f"cached per-scenario loop ({loop_warm:.3f}s)", file=sys.stderr,
        )
    if report["window_regression_warning"]:
        print(
            f"WARNING: {report['window_regression_warning']}", file=sys.stderr,
        )


if __name__ == "__main__":
    main()
