"""Host milliseconds per ``Fleet.run`` call in the traced window: the
program's ``fleet.run`` span, seconds over count (``repro.utils.spans``
table, its change over the window)."""
from harness.readers import counter


def read(run):
    n, s = counter(run, "fleet.run.count"), counter(run, "fleet.run.seconds")
    if not n or s is None:
        return None
    return 1e3 * s / n
