"""CPU tests of the benchmark's own machinery.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest znbench/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 suite (``tests/``): a benchmark
PR adds nothing there.  Nothing here is a measurement: every run below
is a toy-size rehearsal on the CPU and says so in its result line.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
