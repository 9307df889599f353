"""The benchmark's workload generator, kept with the benchmark.

A copy of the paper's Section-5 WLCG production workload generator
(``repro.core.workload.wlcg_production_workload``), so that the inputs a
cell runs cannot move when the program's generator changes. It builds the
program's public input types (``Grid``, ``Campaign``).

Every draw comes from ``np.random.RandomState(seed)`` in the same order as
the original, so the copy equals the program's workload at the commit it
was taken from (``bench/tests/test_scenarios.py`` checks that while the
original exists).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from repro.core.topology import Grid
from repro.core.workload import AccessProfileKind, Campaign, FileAccess, Job, Replica

Pair = Tuple[Grid, Campaign]

REMOTE = AccessProfileKind.REMOTE


def production_workload(
    *,
    n_waves: int = 26,
    wave_period_ticks: int = 900,
    max_jobs: int = 12,
    max_threads: int = 4,
    min_size_mb: float = 300.0,
    max_size_mb: float = 3000.0,
    n_observations: int = 106,
    link_bandwidth: float = 1250.0,
    bg_update_period: int = 60,
    seed: int = 0,
) -> Pair:
    """The paper's Section-5 WLCG production workload: 1-12 concurrent jobs
    on one CERN worker node open WebDAV streams of 300 MB-3 GB files at
    ``GRIF-LPNHE_SCRATCHDISK`` once per 15 minutes (26 waves), up to 4
    files per job, until 106 accesses are drawn; one shared 1,250 MB/tick
    (10 Gbit/s) link."""
    rng = np.random.RandomState(seed)
    grid = Grid()
    grid.add_data_center("CERN")
    grid.add_data_center("GRIF-LPNHE")
    grid.add_storage_element("GRIF-LPNHE_SCRATCHDISK", "GRIF-LPNHE")
    grid.add_storage_element("CERN-PROD_SCRATCHDISK", "CERN")
    for j in range(max_jobs):
        grid.add_worker_node(f"cern-wn{j:02d}", "CERN")
    grid.add_link(
        "GRIF-LPNHE_SCRATCHDISK", "cern-wn00",
        bandwidth=link_bandwidth, bg_update_period=bg_update_period,
    )
    per_job: List[List[FileAccess]] = [[] for _ in range(max_jobs)]
    n_obs = 0
    for wave in range(n_waves):
        if n_obs >= n_observations:
            break
        t0 = wave * wave_period_ticks
        for j in range(int(rng.randint(1, max_jobs + 1))):
            if n_obs >= n_observations:
                break
            for _ in range(int(rng.randint(1, max_threads + 1))):
                if n_obs >= n_observations:
                    break
                size = float(rng.uniform(min_size_mb, max_size_mb))
                per_job[j].append(FileAccess(
                    Replica(size, "GRIF-LPNHE_SCRATCHDISK"), REMOTE, "webdav",
                    release_tick=t0,
                ))
                n_obs += 1
    jobs = tuple(
        Job("cern-wn00", tuple(accs), name=f"job{j}")
        for j, accs in enumerate(per_job) if accs
    )
    return grid, Campaign(jobs, name="wlcg-prod-20180428")
