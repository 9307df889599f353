"""Host wall milliseconds per generation of the access-profile search in the
traced window: the program's ``profiles.generation`` span (a generation's
dispatch and the wait for its fitness), its seconds over its count
(``repro.utils.spans`` table, its change over the window). One generation
is in flight at a time, so the span holds the device's time a
generation."""
from harness.readers import counter


def read(run):
    n, s = counter(run, "profiles.generation.count"), counter(run, "profiles.generation.seconds")
    if not n or s is None:
        return None
    return 1e3 * s / n
