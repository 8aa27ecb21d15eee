"""The load generator with the test-only generators on its path: what
``test_seam.py`` starts in ``benchmark.loadgen``'s place (a child
process does not see the test's own monkeypatches)."""

import os
import sys

import benchmark.generators
from benchmark import loadgen

benchmark.generators.__path__.append(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "generators"))
sys.exit(loadgen.main())
