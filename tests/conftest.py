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


import pytest  # noqa: E402


def pytest_generate_tests(metafunc):
    """The cases of ``served.Contract`` and ``served.CellPrograms`` take their
    values (the reference's faults, the prompt lengths, the cell's programs)
    from the entry their class names."""
    cases = getattr(metafunc.cls, "cases", None)
    for arg, values in (cases() if cases else {}).items():
        if arg in metafunc.fixturenames:
            metafunc.parametrize(arg, values, ids=lambda v: (
                "-".join(map(str, v)) if isinstance(v, tuple) else str(v)))


@pytest.fixture(scope="module")
def topo():
    """A described ``v5e:2x2`` (section 2 of the on-chip-measurement guide):
    the TPU's compiler is installed here. Skipped only where the topology
    cannot be described (no libtpu)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: no TPU compiler
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # A compile for a described chip is written to JAX's persistent cache
    # but cannot be read back without the chip; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The serving engine chooses the decode program's attention from the
    backend it sees, which here is the CPU whatever the compile is for: let
    it see the TPU the program is compiled for (the guide's "steer in the
    test"), so that what is compiled is what the chip runs."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
