"""Work counted from a simulation's shape, for the roofline shares.

The work of one simulation is what the paper's tick needs on the
simulation's own (unpadded) legs ``T``, processes ``P`` and links ``L``,
times the ticks the reference steps. Per tick, counting each add,
multiply, divide, compare, min and max as one operation:

- background resample of every link: multiply, add, max, select (4 L);
- which legs are active: release compare, dependency test, two ands (4 T);
- threads per process (T adds), busy processes per link (P compares and
  P adds), the fair-share divisor (add, max, max per link: 3 L), and the
  per-process bandwidth (L divides);
- each leg's chunk: divide by its threads, overhead multiply and subtract,
  clip to what is left (4 T);
- traffic per process and per link (2 T adds), the two concurrency
  accumulators (4 T: subtract and add each), the remaining bytes (T) and
  the completion test (T).

That is ``20 T + 2 P + 8 L`` operations a tick. The least memory traffic a
simulation needs is to read its campaign once (size, release, dependency,
process, link and overhead of each leg: 24 bytes; bandwidth, two moments
and period of each link: 16 bytes) and to write its results once (transfer
time, start tick, two accumulators and the done flag of each leg: 20 bytes;
the tick count: 4 bytes): ``44 T + 16 L + 4`` bytes.

The count is the same whatever implements the tick (one tick at a time,
fused windows or event leaps), so a share moves only when the same work
takes less device time. A leap implementation that covers many ticks in
one step is credited with every tick it covers.
"""
from __future__ import annotations

import json
import os
from typing import Tuple

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def flops_per_tick(n_legs: int, n_procs: int, n_links: int) -> int:
    return 20 * n_legs + 2 * n_procs + 8 * n_links


def bytes_per_sim(n_legs: int, n_links: int) -> int:
    return 44 * n_legs + 16 * n_links + 4


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, device_s: float, n_devices: int,
                   device_kind: str) -> Tuple[float, str]:
    """The least time the chips could take for the work, over the device
    time it took, in percent; and which bound sets that least time."""
    p = peaks(device_kind)
    t_flops = flops / (p["flops_per_s"] * n_devices)
    t_bytes = nbytes / (p["hbm_bytes_per_s"] * n_devices)
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / device_s, bound
