"""The benchmark's copy of the workload generator draws what the program's did."""
import pytest

from harness import reference, scenarios


def test_production_workload_matches_the_program():
    workload = pytest.importorskip("repro.core.workload")
    g1, c1 = scenarios.production_workload(seed=0)
    g2, c2 = workload.wlcg_production_workload(seed=0)
    assert c1 == c2 and g1.links == g2.links
    legs = reference.read_campaign(g1, c1)
    assert legs.n_legs == 106 and legs.n_links == 1
