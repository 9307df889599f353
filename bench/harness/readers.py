"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

A reader returns ``None`` where its run has nothing to read; it never
stands a 0 in for a share it could not measure."""
from __future__ import annotations

from typing import Optional

from harness import roofline


def idle_pct(run) -> Optional[float]:
    """The device's idle share of the traced window, mean over the chips
    the cell uses."""
    t = run.trace
    if t is None or t.window_s <= 0 or t.n_ops == 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)


def roofline_pct(run) -> Optional[float]:
    """The counted work's least time on the cell's chips over the device
    time of every operation in the window (the union of their intervals,
    summed over the chips)."""
    t, w = run.trace, run.work
    if t is None or not w or t.mean_busy_s <= 0:
        return None
    share, _bound = roofline.roofline_share(
        w["flops"], w["bytes"], t.mean_busy_s, run.n_devices, run.device_kind
    )
    return share


def counter(run, name: str) -> Optional[float]:
    v = run.counters.get(name)
    return None if v is None or v != v else float(v)
