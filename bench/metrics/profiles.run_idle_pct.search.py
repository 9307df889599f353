"""Device idle time charged to the search's generations, in percent of the
traced window (profiler trace): the gaps inside the span
``profiles.generation``, the only program span open while a generation
runs (the spans of ``Fleet.run`` open while the generation's program is
traced, not while it runs). Nothing where the program opens no such
span."""
from harness.readers import counter


def read(run):
    t = run.trace
    if (t is None or t.window_s <= 0 or t.n_ops == 0
            or not counter(run, "profiles.generation.count")):
        return None
    return 100.0 * t.idle_gaps_s.get("profiles.generation", 0.0) / t.window_s
