"""Self-checks of the benchmark, run on the CPU:

    python -m pytest benchmarks/lutbench/selftest

They sit outside the repository's test suite (``pytest.ini`` collects
``tests/`` only)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
LUTBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(LUTBENCH))
for p in (LUTBENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
