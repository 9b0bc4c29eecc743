"""The benchmark's own tests: CPU only, run by hand
(`python -m pytest chipbench/tests -q -p no:cacheprovider`)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]
