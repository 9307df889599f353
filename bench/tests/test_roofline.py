"""The work count behind the roofline shares, and the table of peaks."""
import json

import numpy as np
import pytest

from harness import reference, roofline, scenarios


def test_counts_at_known_shapes():
    assert roofline.flops_per_tick(106, 11, 1) == 20 * 106 + 2 * 11 + 8
    assert roofline.bytes_per_sim(106, 1) == 44 * 106 + 16 + 4
    assert roofline.flops_per_tick(1, 1, 1) == 30


def test_share_and_bound():
    p = roofline.peaks("TPU v5 lite")
    # work that takes exactly 1 ms at the flop peak of one chip
    share, bound = roofline.roofline_share(p["flops_per_s"] * 1e-3, 0.0, 1e-3, 1, "TPU v5 lite")
    assert share == pytest.approx(100.0) and bound == "flops"
    share, bound = roofline.roofline_share(0.0, p["hbm_bytes_per_s"] * 1e-3, 4e-3, 1,
                                           "TPU v5 lite")
    assert share == pytest.approx(25.0) and bound == "bytes"
    # four chips share the work
    share, _ = roofline.roofline_share(p["flops_per_s"] * 1e-3, 0.0, 1e-3, 4, "TPU v5 lite")
    assert share == pytest.approx(25.0)


def test_unknown_device_kind_fails():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_peaks_table_has_its_source():
    with open(roofline.PEAKS) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    assert table["devices"]["TPU v5 lite"]["flops_per_s"] == 197e12
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_count_is_the_same_for_tick_and_leap():
    """The count reads ticks, and the reference's tick and event-leap
    schedules step the same ticks to the same results: a leap
    implementation is credited with the ticks it covers, no more."""
    for seed in range(6):
        grid, camp = scenarios.production_workload(
            n_waves=4, max_jobs=4, max_threads=2, n_observations=12,
            link_bandwidth=250.0 * (seed + 1), seed=seed)
        legs = reference.read_campaign(grid, camp)
        tick = reference.simulate(legs, schedule="tick")
        leap = reference.simulate(legs, schedule="leap")
        assert tick["ticks"] == leap["ticks"]
        np.testing.assert_array_equal(tick["transfer_time"], leap["transfer_time"])
        np.testing.assert_allclose(leap["conth_mb"], tick["conth_mb"], rtol=1e-9, atol=1e-6)
        assert leap["steps"] <= tick["steps"]
