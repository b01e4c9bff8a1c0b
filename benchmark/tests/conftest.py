"""The benchmark's own tests run on the CPU: python -m pytest benchmark/tests -q"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.dirname(_BENCH)]
