"""ResNet-50 v1.5 in flax — the headline benchmark model.

Reference parity: the reference's throughput story is ResNet-50 images/sec
(`examples/tensorflow2/tensorflow2_synthetic_benchmark.py`, which pulls
`tf.keras.applications.ResNet50`; `docs/benchmarks.rst` scaling chart).
This is a fresh flax implementation, bfloat16 compute / float32 params —
the TPU-native dtype split (MXU eats bf16; BN stats and the optimizer state
stay fp32 for stability).

v1.5 variant: the 3x3 conv in the bottleneck carries the stride (not the
1x1), matching what the common benchmark numbers measure.
"""

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1), name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), strides=(self.strides,) * 2,
                      name="conv2")(y)
        y = self.norm(name="bn2")(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1), name="conv3")(y)
        y = self.norm(scale_init=nn.initializers.zeros, name="bn3")(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 strides=(self.strides,) * 2,
                                 name="proj")(residual)
            residual = self.norm(name="proj_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    # "classic": the standard 7x7/2 stem. "s2d": space-to-depth stem — the
    # input is rearranged 2x2xC -> 4C channels and the stem becomes a 4x4/1
    # conv on (112,112,12). Mathematically the same function class (the 7x7x3
    # kernel embeds into the 4x4x12 kernel zero-padded — the MLPerf-closed
    # weight transform); on TPU it quadruples the stem's MXU lane utilization
    # (C_in 3 -> 12 against 128 lanes), worth ~8% end-to-end at batch 128.
    stem: str = "classic"
    # "flax": nn.BatchNorm, the one value (the Pallas and the bf16-stats
    # variants went in PR 47 with the sweep that judged them).
    norm: str = "flax"

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        if self.norm != "flax":
            raise ValueError(f"ResNet norm={self.norm!r}: only 'flax'")
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                       param_dtype=jnp.float32)
        x = x.astype(self.dtype)
        if self.stem == "s2d":
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2,
                                                      4 * c)
            # Explicit ((2,1),(2,1)) padding makes the embedding exact: s2d
            # output (i,j) then covers full-res rows 2i-4..2i+3, a superset
            # of the classic pad-3 7x7 window rows 2i-3..2i+3, so the 7x7x3
            # kernel maps into the 4x4x12 kernel with zero padding. (SAME
            # would pad (1,2) and drop row 2i-3 — a shifted, non-equivalent
            # stem.)
            x = conv(self.num_filters, (4, 4),
                     padding=[(2, 1), (2, 1)], name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), strides=(2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = BottleneckBlock(self.num_filters * 2 ** i, strides,
                                    conv=conv, norm=norm,
                                    name=f"stage{i + 1}_block{j + 1}")(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


def ResNet50(num_classes: int = 1000, dtype=jnp.bfloat16,
             stem: str = "classic", norm: str = "flax") -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), num_classes=num_classes,
                  dtype=dtype, stem=stem, norm=norm)


def ResNet101(num_classes: int = 1000, dtype=jnp.bfloat16,
              stem: str = "classic", norm: str = "flax") -> ResNet:
    return ResNet(stage_sizes=(3, 4, 23, 3), num_classes=num_classes,
                  dtype=dtype, stem=stem, norm=norm)


def create_train_state(rng, image_size: int = 224, num_classes: int = 1000,
                       dtype=jnp.bfloat16, model=None, stem: str = "classic",
                       norm: str = "flax"):
    """Init params/batch_stats on a dummy batch. Returns (model, variables)."""
    model = model or ResNet50(num_classes=num_classes, dtype=dtype, stem=stem,
                              norm=norm)
    dummy = jnp.ones((1, image_size, image_size, 3), jnp.float32)
    variables = jax.jit(partial(model.init, train=False))(rng, dummy)
    return model, variables


def cross_entropy_loss(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
