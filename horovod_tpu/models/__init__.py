"""Model zoo for benchmarks and examples.

Reference parity: the reference ships benchmark/example models under
`examples/` (`examples/tensorflow2/tensorflow2_synthetic_benchmark.py` uses
Keras ResNet-50; `examples/pytorch/` has BERT fine-tuning). Here the models
are first-class package members because they are also the vehicles for the
TPU-native parallelism demos (tensor/sequence/expert sharding) that the
reference's pure-DP design never needed.

- :mod:`.resnet` — ResNet-50 v1.5 in flax (headline images/sec benchmark).
- :mod:`.transformer` — decoder-style Transformer with optional MoE, written
  in pure JAX with an explicit parameter pytree and a mirrored
  PartitionSpec pytree (dp/tp/sp/ep shardings over a Mesh).
"""

import importlib

from . import transformer  # noqa: F401


def __getattr__(name):
    # ``resnet`` pulls in flax (0.65 s of import): loaded on first use, so
    # that a transformer trainer or server does not pay for it at start.
    if name == "resnet":
        return importlib.import_module(".resnet", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
