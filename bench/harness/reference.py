"""The plain reference the benchmark checks the program against.

A straightforward float64 implementation of the paper's transfer mechanism
(GDAPS, Begy et al. 2019, Sections 3-4), written from the campaign itself
and independent of the program's compiled tables, engine and kernels. It
follows ``core/refsim.py``, the program's loop-based oracle, with the loops
over legs written as numpy vector operations:

    chunk  = (link.bandwidth / max(campaign_procs + background_load, 1))
             / job_threads
    chunk -= chunk * protocol.overhead

Links are uni-directional and fairly shared by the processes on them; a
remote access is a thread of its job's streaming process on that link, a
stage-in has its own process, and a placement is an SE -> SE leg followed
by a dependent stage-in leg, each with its own process. A link's background
load is ``max(mu + sigma * z, 0)`` processes, resampled every
``bg_update_period`` ticks.

The standard normals ``z`` are the documented random stream of a
simulation (``CONTRACTS.md`` section 2): the simulation's key is split once
per loop iteration, ``key, sub = split(key)``, and ``z = normal(sub, [L])``
in float32 over the links in sorted ``(src, dst)`` order. A loop iteration
is one tick, or one event under the event-leap schedule: the next iteration
starts at the first tick at which a leg completes, a pending leg is
released, or a link with ``sigma > 0`` resamples. The reference keeps to
that schedule (``schedule="leap"``) so that its draws are the program's;
between events every rate is constant, so a step of ``dt`` ticks moves
``dt - 1`` whole chunks and one final, possibly clipped, chunk, exactly as
``dt`` single ticks would.

``dtype`` selects the arithmetic: float64 is the reference, and a lower
precision (``ml_dtypes.bfloat16``) gives the control that a sound program
must beat.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import ml_dtypes
import numpy as np

from repro.core.workload import AccessProfileKind

PLACEMENT, STAGE_IN, REMOTE = 0, 1, 2
DONE_EPS = 1e-6  # a leg with at most this many MB left has finished
#: the control's arithmetic: the precision below the configurations' float32
CONTROL = ml_dtypes.bfloat16


@dataclasses.dataclass
class Legs:
    """One campaign read leg by leg, in job order then access order (a
    placement gives its SE -> SE leg, then its stage-in leg)."""

    size: np.ndarray  # [T] MB
    release: np.ndarray  # [T] first eligible tick
    dep: np.ndarray  # [T] index of the leg that must finish first, or -1
    proc: np.ndarray  # [T] process id
    link: np.ndarray  # [T] link index into the sorted link names
    overhead: np.ndarray  # [T] protocol overhead fraction
    profile: np.ndarray  # [T] PLACEMENT / STAGE_IN / REMOTE
    protocol: List[str]  # [T]
    n_procs: int
    bandwidth: np.ndarray  # [L] MB/tick
    bg_mu: np.ndarray  # [L]
    bg_sigma: np.ndarray  # [L]
    bg_period: np.ndarray  # [L] ticks

    @property
    def n_legs(self) -> int:
        return int(self.size.shape[0])

    @property
    def n_links(self) -> int:
        return int(self.bandwidth.shape[0])


def read_campaign(grid, campaign) -> Legs:
    """The legs, processes and links of ``campaign`` on ``grid``."""
    names = sorted(grid.links)
    index = {n: i for i, n in enumerate(names)}
    rows: List[Tuple] = []  # size, release, dep, proc, link, overhead, profile, protocol
    n_procs = 0
    for job in campaign.jobs:
        wn = job.worker_node
        streams: Dict[int, int] = {}
        for acc in job.accesses:
            rep = acc.replica
            over = grid.protocols[acc.protocol].overhead
            if acc.profile is AccessProfileKind.REMOTE:
                link = index[(rep.storage_element, wn)]
                if link not in streams:
                    streams[link] = n_procs
                    n_procs += 1
                rows.append((rep.size_mb, acc.release_tick, -1, streams[link], link,
                             over, REMOTE, acc.protocol))
            elif acc.profile is AccessProfileKind.STAGE_IN:
                rows.append((rep.size_mb, acc.release_tick, -1, n_procs,
                             index[(rep.storage_element, wn)], over, STAGE_IN,
                             acc.protocol))
                n_procs += 1
            else:
                local = acc.local_storage_element
                if local is None:
                    dc = grid.worker_nodes[wn].data_center
                    local = grid.data_centers[dc].storage_elements[0]
                first = len(rows)
                rows.append((rep.size_mb, acc.release_tick, -1, n_procs,
                             index[(rep.storage_element, local)], over, PLACEMENT,
                             acc.protocol))
                rows.append((rep.size_mb, acc.release_tick, first, n_procs + 1,
                             index[(local, wn)],
                             grid.protocols[acc.stagein_protocol].overhead, STAGE_IN,
                             acc.stagein_protocol))
                n_procs += 2
    cols = list(zip(*rows))
    links = [grid.links[n] for n in names]
    return Legs(
        size=np.asarray(cols[0], np.float64),
        release=np.asarray(cols[1], np.int64),
        dep=np.asarray(cols[2], np.int64),
        proc=np.asarray(cols[3], np.int64),
        link=np.asarray(cols[4], np.int64),
        overhead=np.asarray(cols[5], np.float64),
        profile=np.asarray(cols[6], np.int64),
        protocol=list(cols[7]),
        n_procs=n_procs,
        bandwidth=np.asarray([l.bandwidth for l in links], np.float64),
        bg_mu=np.asarray([l.bg_mu for l in links], np.float64),
        bg_sigma=np.asarray([l.bg_sigma for l in links], np.float64),
        bg_period=np.asarray([l.bg_update_period for l in links], np.int64),
    )


def theta_params(legs: Legs, theta, protocol: str = "webdav"):
    """``theta = (overhead, mu, sigma)``, the paper's calibration target:
    the overhead of every ``protocol`` leg, and the background-load moments
    of every link. Returns ``(overhead [T], bg_mu [L], bg_sigma [L])``."""
    over, mu, sigma = (float(v) for v in theta)
    calibrated = np.asarray([p == protocol for p in legs.protocol])
    overhead = np.where(calibrated, over, legs.overhead)
    return (overhead, np.full(legs.n_links, mu), np.full(legs.n_links, sigma))


_BLOCK = 256


@functools.lru_cache(maxsize=None)
def _noise_block(n_links: int):
    """A jitted run of ``_BLOCK`` split-and-draw steps from a key."""
    import jax
    import jax.numpy as jnp

    def step(k, _):
        k, sub = jax.random.split(k)
        return k, jax.random.normal(sub, (n_links,), jnp.float32)

    return jax.jit(lambda k: jax.lax.scan(step, k, None, length=_BLOCK))


def noise_stream(key) -> Callable[[int], np.ndarray]:
    """The simulation's standard normals: each call splits the carried key
    once and returns the ``[L]`` float32 normals drawn from the sub-key.
    Drawn in blocks on the host's CPU device, so that the reference never
    touches the accelerator."""
    import jax

    try:
        device = jax.devices("cpu")[0]
    except RuntimeError:
        device = None
    state = {"key": np.asarray(key, np.uint32), "block": None, "i": _BLOCK}

    def draw(n_links: int) -> np.ndarray:
        if state["i"] == _BLOCK:
            with jax.default_device(device):
                k, z = _noise_block(n_links)(jax.numpy.asarray(state["key"]))
            state["key"], state["block"], state["i"] = np.asarray(k), np.asarray(z), 0
        state["i"] += 1
        return state["block"][state["i"] - 1]

    return draw


def simulate(
    legs: Legs,
    *,
    overhead: Optional[np.ndarray] = None,
    bg_mu: Optional[np.ndarray] = None,
    bg_sigma: Optional[np.ndarray] = None,
    key=None,
    schedule: str = "tick",
    dtype=np.float64,
    max_ticks: int = 10_000_000,
) -> Dict[str, np.ndarray]:
    """Simulate one campaign; returns per-leg ``transfer_time``, ``start_tick``,
    ``conth_mb``, ``conpr_mb``, ``done``, the simulation's ``ticks`` and the
    loop iterations it took (``steps``).

    ``overhead``, ``bg_mu`` and ``bg_sigma`` default to the campaign's own.
    ``key`` (a ``[2]`` uint32 key) drives the background noise; it may be
    omitted only where every link has ``sigma = 0``. ``schedule`` is
    ``"tick"`` or ``"leap"`` (see the module docstring)."""
    if schedule not in ("tick", "leap"):
        raise ValueError(f"schedule must be 'tick' or 'leap': {schedule!r}")
    T, L, P = legs.n_legs, legs.n_links, legs.n_procs
    cast = lambda a: np.asarray(a, dtype)
    overhead = legs.overhead if overhead is None else overhead
    mu = cast(legs.bg_mu if bg_mu is None else bg_mu)
    sigma = cast(legs.bg_sigma if bg_sigma is None else bg_sigma)
    stochastic = np.asarray(sigma, np.float64) > 0
    if stochastic.any() and key is None:
        raise ValueError("a link with sigma > 0 needs a key for its noise")
    draw = noise_stream(key) if key is not None else (lambda n: np.zeros(n, np.float32))
    keep = cast(1.0 - cast(overhead))
    bandwidth = cast(legs.bandwidth)
    one = cast(1.0)
    zero = cast(0.0)
    proc_link = np.zeros(P, np.int64)
    proc_link[legs.proc] = legs.link

    remaining = cast(legs.size)
    done = np.zeros(T, bool)
    started = np.zeros(T, bool)
    t_start = np.zeros(T, np.int64)
    t_end = np.zeros(T, np.int64)
    conth = np.zeros(T, dtype)
    conpr = np.zeros(T, dtype)
    bg = np.zeros(L, dtype)
    has_dep = legs.dep >= 0
    dep = np.maximum(legs.dep, 0)

    t = steps = 0
    while t < max_ticks and not done.all():
        z = cast(draw(L))
        due = t % legs.bg_period == 0
        bg = np.where(due, np.maximum(mu + sigma * z, zero), bg).astype(dtype)

        active = ~done & (legs.release <= t) & (~has_dep | done[dep])
        threads = np.bincount(legs.proc[active], minlength=P)
        procs_on_link = np.bincount(proc_link[threads > 0], minlength=L)
        denom = np.maximum(cast(procs_on_link) + np.maximum(bg, zero), one)
        per_proc = (bandwidth / denom).astype(dtype)
        chunk = (per_proc[legs.link] / cast(np.maximum(threads[legs.proc], 1))).astype(dtype)
        rate = np.where(active, chunk - chunk * (one - keep), zero).astype(dtype)

        dt = 1
        if schedule == "leap":
            with np.errstate(divide="ignore", invalid="ignore"):
                ttc = np.where(active & (rate > 0),
                               np.ceil(np.asarray(remaining, np.float64)
                                       / np.asarray(rate, np.float64)), np.inf)
            pending = ~done & (legs.release > t)
            t_rel = np.where(pending, legs.release - t, np.inf)
            t_bg = np.where(stochastic, legs.bg_period - t % legs.bg_period, np.inf)
            step = min(ttc.min(initial=np.inf), t_rel.min(initial=np.inf),
                       t_bg.min(initial=np.inf))
            dt = int(max(step, 1)) if np.isfinite(step) else 1
            dt = min(dt, max_ticks - t)

        # dt - 1 whole chunks, then one final chunk clipped to what is left
        mid = (remaining - rate * cast(dt - 1)).astype(dtype)
        xfer = np.minimum(mid, rate).astype(dtype)
        proc_rate = cast(np.bincount(legs.proc, weights=rate, minlength=P))
        link_rate = cast(np.bincount(legs.link, weights=rate, minlength=L))
        proc_xfer = cast(np.bincount(legs.proc, weights=xfer, minlength=P))
        link_xfer = cast(np.bincount(legs.link, weights=xfer, minlength=L))
        own_proc, own_link = proc_rate[legs.proc], link_rate[legs.link]
        own_proc_f, own_link_f = proc_xfer[legs.proc], link_xfer[legs.link]
        n = cast(dt - 1)
        conth = np.where(active, conth + (own_proc - rate) * n + (own_proc_f - xfer),
                         conth).astype(dtype)
        conpr = np.where(active, conpr + (own_link - own_proc) * n
                         + (own_link_f - own_proc_f), conpr).astype(dtype)
        remaining = np.where(active, mid - xfer, remaining).astype(dtype)

        first = active & ~started
        t_start[first] = t
        started |= active
        finished = active & (np.asarray(remaining, np.float64) <= DONE_EPS)
        t_end[finished] = t + dt
        done |= finished
        t += dt
        steps += 1

    return {
        "transfer_time": np.where(done, t_end - t_start, 0).astype(np.float64),
        "start_tick": t_start.astype(np.float64),
        "conth_mb": np.asarray(conth, np.float64),
        "conpr_mb": np.asarray(conpr, np.float64),
        "done": done,
        "ticks": t,
        "steps": steps,
    }


def eq1_coefficients(legs: Legs, out: Dict[str, np.ndarray]) -> np.ndarray:
    """The paper's Eq. 1 summary statistic, ``T ~ 0 + a S + b ConTh +
    c ConPr`` by least squares over the finished remote accesses."""
    m = out["done"] & (legs.profile == REMOTE)
    X = np.stack([legs.size, out["conth_mb"], out["conpr_mb"]], axis=1)[m]
    return np.linalg.lstsq(X, out["transfer_time"][m], rcond=None)[0]
