"""The benchmark's own tests run on the CPU: `pytest benchmark/tests`.
They are not part of the repository's tier-1 suite."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.dirname(_BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
