import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

# the tests compile for the CPU: keep the checkout's compile cache for chip runs
jax.config.update("jax_enable_compilation_cache", False)
