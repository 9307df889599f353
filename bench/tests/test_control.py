"""The control fails the comparison that sound runs pass.

The control is the reference put in the program's place and computed in
bfloat16, the precision below the float32 the configurations state. At a
size a test can hold, a sound run's numbers stay within every limit of its
cell, and the control's break at least one."""
import time

import pytest
import small

from harness import manifest
from harness import trace as trace_lib


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small.small_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload", ["wlcg-prod.presim-leap"])
def test_control_fails_where_the_program_passes(root, workload):
    cell = manifest.find_cell(workload, root)
    gen = manifest.generator(cell, root).Generator(cell, 12345, trace_lib.Tracer("", False))
    gen.setup(1.0)
    gen.window(1.0, time.perf_counter)
    gen.release()
    limits = {k: v["limit"] for k, v in cell.limits.items()}
    sound = gen.check()["values"]
    control = gen.check(control=True)["values"]
    assert sound and set(sound) == set(control)
    assert all(v <= limits[k] for k, v in sound.items()), sound
    assert any(v > limits[k] for k, v in control.items()), control
