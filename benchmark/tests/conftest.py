"""CPU tests of the benchmark's own code (not tier-1: the driver's test
command collects ``tests/`` only). Run them with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider``."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
