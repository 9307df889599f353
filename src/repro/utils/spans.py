"""Host spans and counters of the program, in one process-wide table.

``span(name)`` times a block of host code. It opens a
``jax.profiler.TraceAnnotation`` of the same name, so that a profiler
session started by the caller (``jax.profiler.trace``, or a benchmark's
traced run) shows the block on its host plane, on the clock of the
device's operations. It also adds the block's wall time and a count of 1 to
the table under ``name``::

    with spans.span("fleet.run") as s:
        ...
    s.seconds  # the block's wall time

``count(name, n)`` adds ``n`` to a counter of the same table. ``snapshot()``
copies the table; a reader takes the difference of two snapshots around
the work it measures.

There is no switch: without a profiler session a span costs two clock
reads, an annotation the runtime drops and one locked dict update. Spans sit
at per-call host boundaries only, never inside a jitted function or a
per-event loop (one that runs while a function is traced times the trace).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, NamedTuple, Optional

import jax

__all__ = ["Entry", "Span", "count", "reset", "snapshot", "span"]


class Entry(NamedTuple):
    """One name's totals: how many spans closed (or what a counter added up
    to) and their wall seconds (0 for a counter)."""

    count: int
    seconds: float


_lock = threading.Lock()
_table: Dict[str, Entry] = {}


def _add(name: str, n: int, seconds: float) -> None:
    with _lock:
        c, s = _table.get(name, (0, 0.0))
        _table[name] = Entry(c + n, s + seconds)


class Span:
    """A timed block (use :func:`span`); ``seconds`` is set on exit."""

    __slots__ = ("name", "seconds", "_t0", "_annotation")

    def __init__(self, name: str) -> None:
        self.name = name
        self.seconds: Optional[float] = None

    def __enter__(self) -> "Span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _add(self.name, 1, self.seconds)


def span(name: str) -> Span:
    """A context manager that times its block under ``name``."""
    return Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _add(name, int(n), 0.0)


def snapshot() -> Dict[str, Entry]:
    """A copy of the table: name -> :class:`Entry`."""
    with _lock:
        return dict(_table)


def reset(*names: str) -> None:
    """Empty the table, or drop only the entries ``names``."""
    with _lock:
        if not names:
            _table.clear()
        for name in names:
            _table.pop(name, None)
