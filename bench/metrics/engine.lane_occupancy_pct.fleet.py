"""The banked engine's useful lane-steps over those its loop ran, in
percent: the sum of ``SimResult.steps`` over the window's rows, over each
call's loop trips times the window times the rows (program counters)."""
from harness.readers import counter


def read(run):
    useful, ran = counter(run, "engine.steps"), counter(run, "engine.lane_steps")
    if useful is None or not ran:
        return None
    return 100.0 * useful / ran
