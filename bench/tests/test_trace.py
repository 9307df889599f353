"""The reduction from trace events to window, busy time, ops and idle gaps."""
import glob
import json
import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(kind, dev, name, start_ms, dur_ms):
    return trace.Event(kind, dev, name, start_ms * 1e6, dur_ms * 1e6)


def test_known_answer():
    events = [
        ev("span", -1, trace.WINDOW, 0, 100),
        ev("span", -1, "presim.dispatch", 0, 10),
        ev("span", -1, "presim.fetch", 10, 50),
        ev("span", -1, "presim.dispatch", 60, 40),
        ev("op", 0, "kernel", 5, 20),      # 5-25
        ev("op", 0, "copy", 20, 10),       # 20-30, overlaps the kernel
        ev("op", 0, "kernel", 90, 20),     # 90-110, clipped to 100
        ev("op", 1, "kernel", 0, 50),
        ev("op", 0, "kernel", -20, 10),    # before the window
    ]
    r = trace.reduce(events)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s[0] == pytest.approx(0.035)  # 5-30 and 90-100
    assert r.busy_s[1] == pytest.approx(0.05)
    assert r.mean_busy_s == pytest.approx(0.0425)
    # self time: the copy at 20-30 starts inside the kernel at 5-25, and
    # takes 5 ms of it
    assert r.ops_s["kernel"] == pytest.approx(0.015 + 0.01 + 0.05)
    assert r.ops_s["copy"] == pytest.approx(0.01)
    # device 0 idles 0-5 (run), 30-90 (midpoint 60: the second run opened
    # at 60, the innermost span there); device 1 idles 50-100 (midpoint 75)
    assert r.idle_gaps_s["presim.dispatch"] == pytest.approx((0.005 + 0.06 + 0.05) / 2)
    assert sum(r.idle_gaps_s.values()) + r.mean_busy_s == pytest.approx(r.window_s)
    assert trace.top(r.ops_s, 1) == [["kernel", pytest.approx(0.075)]]


def test_nested_ops_count_their_self_time():
    events = [
        ev("span", -1, trace.WINDOW, 0, 100),
        ev("op", 0, "while", 10, 80),
        ev("op", 0, "kernel", 20, 30),
        ev("op", 0, "fusion", 25, 5),   # inside the kernel
        ev("op", 0, "kernel", 60, 20),
    ]
    r = trace.reduce(events)
    assert r.busy_s[0] == pytest.approx(0.08)
    assert r.ops_s == {"while": pytest.approx(0.03), "kernel": pytest.approx(0.045),
                       "fusion": pytest.approx(0.005)}
    assert sum(r.ops_s.values()) == pytest.approx(r.busy_s[0])


def test_short_names():
    assert trace.short_name("%grid_tick_bank_fused_pallas.45 = (s32[2]) custom-call(x)") \
        == "grid_tick_bank_fused_pallas"
    assert trace.short_name("%while.209 = (s32[2531,4]) while(%t)") == "while"
    assert trace.short_name("%copy-start = (u32[2]) copy-start(u32[2] %key.1)") == "copy-start"
    assert trace.short_name("fusion") == "fusion"


def test_untraced_gap_and_one_window():
    events = [ev("span", -1, trace.WINDOW, 0, 10), ev("op", 0, "k", 0, 4)]
    r = trace.reduce(events)
    assert r.idle_gaps_s == {trace.UNTRACED: pytest.approx(0.006)}
    with pytest.raises(ValueError):
        trace.reduce(events + [ev("span", -1, trace.WINDOW, 20, 10)])


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "trace_*.json"))))
def test_recorded_trace(path):
    """A trace recorded on the chip (``bench/tools/record_trace.py``): the
    window's busy and idle time add up, and every device in it was busy."""
    with open(path) as f:
        rec = json.load(f)
    r = trace.reduce(trace.from_json(rec["events"]), devices=range(rec["chips"]))
    for d, busy in r.busy_s.items():
        assert 0 < busy <= r.window_s
    assert sum(r.idle_gaps_s.values()) + r.mean_busy_s == pytest.approx(r.window_s)
    assert sum(r.ops_s.values()) == pytest.approx(sum(r.busy_s.values()), rel=0.01)
    assert r.n_ops == rec["ops_in_window"]


def test_read_xplane_host_spans(tmp_path):
    """A trace recorded here on the CPU: the window and the spans opened
    inside it are read back from the ``.xplane.pb`` file."""
    import jax
    import jax.numpy as jnp

    tracer = trace.Tracer(str(tmp_path / "tr"), True)
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with tracer.window():
        with tracer.span("presim.dispatch"):
            f(jnp.ones(4)).block_until_ready()
    events = tracer.events(("presim.dispatch",))
    names = [e.name for e in events if e.kind == "span"]
    assert names.count(trace.WINDOW) == 1 and names.count("presim.dispatch") == 1
    assert not os.path.exists(tmp_path / "tr")
