"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (the "fake pod" — SURVEY.md §4:
multi-node is simulated as multi-device/multi-process on one host). These env
vars must be set before the first `import jax` anywhere in the test process.
"""

import os
import sys

# Tests run on the CPU whatever the session env selects: chip_smoke.py and
# benchmark/run.py are the chip surfaces, not the test suite.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)

# Make the repo importable for spawned worker subprocesses too.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
os.environ["PYTHONPATH"] = _REPO + os.pathsep + os.environ.get("PYTHONPATH", "")
