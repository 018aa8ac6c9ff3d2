"""Run by hand from the repo's root, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

They are not among the repo's tier-1 tests (``tests/``).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
