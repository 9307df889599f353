"""Shared test fixtures: small grids/campaigns for the engine tests, plus an
optional-``hypothesis`` shim so property-based tests skip (rather than fail at
collection) when the dependency is absent."""
from __future__ import annotations

import re
from typing import List, Tuple

import numpy as np
import pytest

# re-exported for the property-based tests (`from helpers import given, ...`)
__all__ = ["HAVE_HYPOTHESIS", "given", "settings", "st"]

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # pragma: no cover - exercised in minimal envs
    HAVE_HYPOTHESIS = False

    class _StrategyStub:
        """Any ``st.<strategy>(...)`` call returns an inert placeholder."""

        def __getattr__(self, name):
            return lambda *args, **kwargs: None

    st = _StrategyStub()

    def settings(*args, **kwargs):
        return lambda fn: fn

    def given(*args, **kwargs):
        def deco(fn):
            # zero-arg stand-in: pytest must not see the property arguments
            # as fixtures, so the original signature is deliberately dropped
            def skipper():
                pytest.skip("hypothesis not installed")

            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            return skipper

        return deco

from repro.core.topology import Grid
from repro.core.workload import (
    AccessProfileKind,
    Campaign,
    FileAccess,
    Job,
    LegTable,
    Replica,
    compile_campaign,
)


def small_grid(
    bw_se_se: float = 100.0,
    bw_se_wn: float = 200.0,
    bw_wan: float = 50.0,
    bg=(0.0, 0.0),
    period: int = 16,
) -> Grid:
    g = Grid()
    g.add_data_center("A")
    g.add_data_center("B")
    g.add_storage_element("seA", "A")
    g.add_storage_element("seB", "B")
    g.add_worker_node("wn0", "B")
    g.add_worker_node("wn1", "B")
    g.add_link("seA", "seB", bw_se_se, bg[0], bg[1], period)
    g.add_link("seB", "wn0", bw_se_wn, bg[0], bg[1], period)
    g.add_link("seA", "wn0", bw_wan, bg[0], bg[1], period)
    g.add_link("seB", "wn1", bw_se_wn, bg[0], bg[1], period)
    g.add_link("seA", "wn1", bw_wan, bg[0], bg[1], period)
    return g


def mixed_campaign(seed: int = 0, n_jobs: int = 3, n_accesses: int = 4) -> Tuple[Grid, Campaign, LegTable]:
    """Random mixed-profile campaign on the small grid."""
    rng = np.random.RandomState(seed)
    g = small_grid()
    jobs: List[Job] = []
    for j in range(n_jobs):
        wn = f"wn{j % 2}"
        accs: List[FileAccess] = []
        for _ in range(n_accesses):
            kind = rng.randint(3)
            size = float(rng.uniform(20.0, 400.0))
            release = int(rng.randint(0, 30))
            if kind == 0:
                accs.append(
                    FileAccess(
                        Replica(size, "seA"),
                        AccessProfileKind.DATA_PLACEMENT,
                        "gsiftp",
                        release_tick=release,
                        local_storage_element="seB",
                    )
                )
            elif kind == 1:
                accs.append(
                    FileAccess(
                        Replica(size, "seB"),
                        AccessProfileKind.STAGE_IN,
                        "xrdcp",
                        release_tick=release,
                    )
                )
            else:
                accs.append(
                    FileAccess(
                        Replica(size, "seA"),
                        AccessProfileKind.REMOTE,
                        "webdav",
                        release_tick=release,
                    )
                )
        jobs.append(Job(wn, tuple(accs), name=f"j{j}"))
    camp = Campaign(tuple(jobs))
    table = compile_campaign(g, camp)
    return g, camp, table


_HLO_DEF = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]")
_HLO_SHAPE = re.compile(r"^\w+\[([\d,]*)\]")


def gather_index_shapes(hlo: str) -> List[Tuple[int, ...]]:
    """The shape of the index operand of every ``gather`` in an HLO module's
    text, whether the text prints operand shapes inline (a lowering) or
    only operand names (a compiled module)."""
    defs = {}
    for line in hlo.splitlines():
        m = _HLO_DEF.match(line)
        if m:
            defs[m.group(1)] = m.group(2)
    shapes = []
    for line in hlo.splitlines():
        if " gather(" not in line:
            continue
        index = line.split(" gather(", 1)[1].split(", ")[1].split(")")[0]
        m = _HLO_SHAPE.match(index)
        dims = m.group(1) if m else defs[index.split()[-1].lstrip("%")]
        shapes.append(tuple(int(d) for d in dims.split(",") if d))
    return shapes


def pallas_grids(jaxpr) -> List[Tuple[int, ...]]:
    """The grid of every ``pallas_call`` in a jaxpr, nested jaxprs (jit,
    loops, custom rules) included, in the order they appear."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if isinstance(sub, ClosedJaxpr):
                    grids += pallas_grids(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    grids += pallas_grids(sub)
    return grids
