"""Device idle time charged to the host work of ``Fleet.run`` (the spans
``fleet.run``, ``fleet.params`` and ``engine.dispatch``), in percent of the
traced window (profiler trace). Nothing where the program opens no such
span."""
from harness.readers import counter

SPANS = ("fleet.run", "fleet.params", "engine.dispatch")


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or t.n_ops == 0 or not counter(run, "fleet.run.count"):
        return None
    return 100.0 * sum(t.idle_gaps_s.get(name, 0.0) for name in SPANS) / t.window_s
