"""A copy of the benchmark at a size the CPU runs in seconds, for tests."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

SMALL = {
    "wlcg-prod": {"presim": {"chunk": 16, "n_replicates": 1, "protocol": "webdav"},
                  "workload": {"n_waves": 4, "wave_period_ticks": 900, "max_jobs": 4,
                               "max_threads": 2, "min_size_mb": 300.0,
                               "max_size_mb": 3000.0, "n_observations": 12,
                               "link_bandwidth": 1250.0, "bg_update_period": 60,
                               "seed": 0}},
}
TRAFFIC = {
    "presim-leap": {"check_tuples": 16},
}


def small_root(tmp: str) -> str:
    """``tmp`` laid out as a checkout with ``BENCHMARK.json`` and a small
    copy of the cells' files; the generators and readers are the real ones."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    dst = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "limits", "metrics", "generators"):
        if os.path.isdir(os.path.join(BENCH, sub)):
            shutil.copytree(os.path.join(BENCH, sub), os.path.join(dst, sub))
    for name, over in SMALL.items():
        _update(os.path.join(dst, "configs", name + ".json"), over)
    for name, over in TRAFFIC.items():
        _update(os.path.join(dst, "traffic", name + ".json"), over)
    return tmp


def _update(path: str, over: dict) -> None:
    with open(path) as f:
        data = json.load(f)
    data.update(over)
    with open(path, "w") as f:
        json.dump(data, f)
