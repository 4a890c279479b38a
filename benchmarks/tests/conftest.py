"""``pytest benchmarks/tests`` from the root of the repo: CPU rehearsals
of the benchmark, not collected by the tier-1 command (``tests/``)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
