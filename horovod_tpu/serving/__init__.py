"""Serving plane: continuous-batching decode over a TP-sharded paged KV
cache, with queue-depth autoscaling through the elastic driver.

Layout (docs/serving.md is the architecture doc):

- :mod:`.scheduler`    — jax-free continuous batcher + refcounted pages
- :mod:`.autoscale`    — jax-free queue-depth policy for the driver
- :mod:`.prefix_cache` — jax-free radix tree of shared page-aligned prefixes
- :mod:`.speculate`    — jax-free drafters + the spec accept/reject rule
- :mod:`.kv_cache`     — paged K/V arrays, heads sharded on the TP axis
- :mod:`.engine`       — jit'd prefill / decode / chunk steps with block tables
- :mod:`.programs`     — the loop's executables kept across starts (where JAX's
  persistent compile cache is on)
- :mod:`.loop`         — the serve loop: Poisson load, latency spans, gauges

Lazy submodule access keeps the jax-free halves (scheduler, autoscale,
prefix_cache, speculate) importable — by the elastic driver and by the
pure-numpy tests — without pulling jax into the process.
"""

import importlib

_SUBMODULES = ("scheduler", "autoscale", "prefix_cache", "speculate",
               "kv_cache", "engine", "programs", "loop")


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
