"""CPU rehearsals of the benchmark's own arithmetic. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Tier-1 is ``tests/`` and does not collect this directory.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)
