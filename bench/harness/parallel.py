"""The reference's simulations of a check, run in worker processes.

``reference.simulate`` is a Python loop over ticks, a fraction of a second
for a production campaign; a check of 64 members runs 64 of them. Where
the machine has cores to spare they run in spawned worker processes that
use JAX on the CPU only (their noise draws), so the chips stay the
run's. Each simulation is the same call with the same arguments either
way, so the results do not depend on where it ran."""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
from typing import Dict, List, Sequence

import numpy as np

#: simulations a worker should have, at least, for a worker to pay off
PER_WORKER = 8
MAX_WORKERS = 8


def _simulate(args) -> Dict[str, np.ndarray]:
    from harness import reference

    legs, kwargs = args
    return reference.simulate(legs, **kwargs)


def workers_for(n: int) -> int:
    return max(1, min(MAX_WORKERS, (os.cpu_count() or 1) - 2, n // PER_WORKER))


def simulate_all(tasks: Sequence[tuple]) -> List[Dict[str, np.ndarray]]:
    """``reference.simulate(legs, **kwargs)`` for each ``(legs, kwargs)``."""
    workers = workers_for(len(tasks))
    if workers == 1:
        return [_simulate(t) for t in tasks]
    saved = os.environ.get("JAX_PLATFORMS")
    # the workers are spawned while the pool takes its tasks
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        pool = concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn"))
        futures = [pool.submit(_simulate, t) for t in tasks]
    finally:
        if saved is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved
    with pool:
        return [f.result() for f in futures]
