#!/usr/bin/env python3
"""Run one benchmark cell once.

    python bench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for. The cell's pieces are found by name (``bench/harness/manifest.py``).
A run builds its inputs from ``--seed``, warms every program it will use
(set-up), measures for ``--seconds`` with nothing compiled inside the
window, then checks what the window produced against the float64 reference
(``bench/harness/reference.py``).

Standard output: earlier lines are JSON records of the window (units,
traces, compiles and the engine's bank traces inside the window);
the last line is the result, ``{"correct", "attempted", "failed",
"metrics", "device", ["breakdown"], "checks"}``. With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window and from the
program's counters. The last lines of standard error repeat each compared
number beside its limit. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    pass


def _setup_jax():
    # the compile cache sits at a fixed path in the checkout: the path is
    # part of the cache key, and the program's own entry points use it too
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # the TPU runtime logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def _device_record(jax, n_used: int) -> dict:
    devices = jax.devices()
    peak = 0
    for d in devices[:n_used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, root: str = ROOT) -> dict:
    """One run of cell ``workload``; returns the result record."""
    jax = _setup_jax()
    from harness import common, manifest
    from harness import trace as trace_lib

    cell = manifest.find_cell(workload, root)
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {devices[0].platform!r}")
        if len(devices) < cell.chips:
            raise NoChip(f"the cell asks for {cell.chips} chips, JAX sees {len(devices)}")

    tracer = trace_lib.Tracer(TRACE_DIR, trace)
    gen = manifest.generator(cell, root).Generator(cell, seed, tracer)
    counter = common.CompileCounter()
    gen.setup(seconds)
    # set-up's objects stay alive through the window: take them out of the
    # collector's generations so that no collection of them lands inside it
    gc.collect()
    gc.freeze()
    opened = {}

    def open_window() -> float:
        opened["t"] = time.perf_counter()
        counter.active = True
        return opened["t"]

    # a traced run traces a short window where the generator asks for one: the
    # trace is read in this process, and must be read within the run's time
    traced_s = getattr(gen, "trace_seconds", None) if trace else None
    with tracer.window():
        win = gen.window(min(seconds, traced_s) if traced_s else seconds, open_window)
    counter.active = False
    setup_s = opened["t"] - T_START
    device = _device_record(jax, cell.chips)
    info = dict(win["info"])
    info.update(window_s=win["seconds"], setup_s=setup_s,
                jaxpr_traces=counter.traces, backend_compiles=counter.compiles)
    _emit({"window": info})

    reduced = None
    if trace:
        events = tracer.events(gen.spans)
        reduced = trace_lib.reduce(events, devices=range(cell.chips))
        _emit({"trace": {"device_lines": tracer.lines, "ops_in_window": reduced.n_ops,
                         "ops": sum(e.kind == "op" for e in events)}})
    gen.release()
    checked = gen.check()
    work = gen.work() if trace and hasattr(gen, "work") else {}

    values = dict(checked["values"])
    values["missing"] = float(checked["missing"])
    values["window_traces"] = float(counter.traces + counter.compiles
                                    + info.get("bank_traces", 0) + info.get("banks_built", 0))
    verdict = common.judge(values, cell.limits)
    limit = lambda k: cell.limits.get(k, {}).get("limit")
    failed = checked["missing"] + checked["failed"]
    if checked.get("info"):
        _emit({"check": checked["info"]})

    if trace:
        run_view = _RunView(reduced, win["counters"], work, device["kind"], cell.chips)
        metrics = {}
        for m in cell.per_layer:
            value = manifest.metric_reader(m["name"], root).read(run_view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.mean_busy_s
        device["window_s"] = reduced.window_s
    else:
        values_e2e = dict(win["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values_e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    result = {"correct": bool(verdict), "attempted": int(win["attempted"]),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": trace_lib.top(reduced.ops_s, scale=1.0 / cell.chips),
            "idle_gaps": trace_lib.top(reduced.idle_gaps_s),
        }
    result["checks"] = {k: {"value": v, "limit": limit(k)} for k, v in values.items()}
    return result


class _RunView:
    """What a per-layer metric reader sees of a traced run."""

    def __init__(self, reduced, counters, work, device_kind, n_devices) -> None:
        self.trace = reduced
        self.counters = counters
        self.work = work
        self.device_kind = device_kind
        self.n_devices = n_devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in result["checks"].items():
        ok = c["limit"] is not None and c["value"] <= c["limit"]
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {'ok' if ok else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
