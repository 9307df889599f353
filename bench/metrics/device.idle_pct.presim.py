"""Device idle share of the traced window, in percent (profiler trace)."""
from harness.readers import idle_pct as read  # noqa: F401
