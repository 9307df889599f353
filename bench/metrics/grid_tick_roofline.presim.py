"""The simulation's counted work over the device time of every operation in
the traced window, as a share of the chip's roofline, in percent
(``harness/roofline.py`` counts the work; profiler trace for the time)."""
from harness.readers import roofline_pct as read  # noqa: F401
