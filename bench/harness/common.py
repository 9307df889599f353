"""Pieces every generator shares: seeds, keys, the in-window compile counter,
and the verdict on the compared numbers."""
from __future__ import annotations

import threading
from typing import Dict, Optional

import numpy as np

#: stands for a gap that is not a number (a singular fit): as far off as a
#: gap can be
BIG = 1e30
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of a run's seed (any size of
    whole number)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def key(seed: int, *stream: int) -> np.ndarray:
    """A raw ``[2]`` uint32 threefry key for one stream of the seed."""
    return rng(seed, *stream).integers(0, 2**32, size=2, dtype=np.uint32)


class CompileCounter:
    """Counts jaxpr traces and backend compiles while ``active``: a window
    must show none."""

    def __init__(self) -> None:
        import jax

        self.active = False
        self.traces = 0
        self.compiles = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_kw) -> None:
        if not self.active:
            return
        with self._lock:
            if name == TRACE_EVENT:
                self.traces += 1
            elif name == COMPILE_EVENT:
                self.compiles += 1


def judge(values: Dict[str, float], limits: Dict[str, dict]) -> Optional[bool]:
    """Whether every compared number is within its limit; ``None`` when a
    number has no limit yet (the cell is not ready to be judged)."""
    ok = True
    for name, v in values.items():
        if name not in limits:
            return None
        ok &= bool(v <= float(limits[name]["limit"]))
    return ok
