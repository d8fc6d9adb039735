"""CPU tests of the benchmark itself: smoke-size models, Pallas kernels in
interpret mode.  Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``
from the repository root."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
