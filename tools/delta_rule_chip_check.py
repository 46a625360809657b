#!/usr/bin/env python3
"""The chunked delta rule against the recurrence, on the device it is started
on, at one layer's published widths (``transformer._delta_blocks`` against a
``lax.scan`` of ``transformer._delta_step``: 64 heads of 128, 2,048 positions,
a non-zero entering state, float32): the largest difference of the outputs
and of the state leaving, and where the first lies; on a TPU also the Pallas
kernel of the chunked form, ``ops/pallas_kda.kda_chunk_scan``, against the
same recurrence (``kernel_*``). One JSON line, also appended to
``chiprun_out/delta_rule_chip_check.jsonl``.

    python3 tools/delta_rule_chip_check.py [--positions 2048] [--seed 0]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402

from horovod_tpu.models import transformer as tfm     # noqa: E402
from horovod_tpu.ops import pallas_kda                # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--positions", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    S, H, d = args.positions, args.heads, args.head_dim
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 7)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    # Queries and keys as the layer makes them: unit vectors behind a SiLU,
    # which leaves them a positive mean (k_i . k_j about 0.3 for every pair).
    q = unit(jax.nn.silu(jax.random.normal(ks[0], (1, S, H, d)))) / np.sqrt(d)
    k = unit(jax.nn.silu(jax.random.normal(ks[1], (1, S, H, d))))
    v = jax.nn.silu(jax.random.normal(ks[2], (1, S, H, d)))
    # A channel's log decay as init_params draws it: step log-uniform over
    # (0.001, 0.1) times a rate uniform over (1, 16).
    step = jnp.exp(jax.random.uniform(ks[3], (1, S, H, d), jnp.float32,
                                      np.log(1e-3), np.log(1e-1)))
    g = -step * jax.random.uniform(ks[4], (H, 1), jnp.float32, 1.0, 16.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (1, S, H)))
    state = jax.random.normal(ks[6], (1, H, d, d))

    chunked = jax.jit(lambda *a: tfm._delta_blocks(*a, 64))

    @jax.jit
    def recurrence(q, k, v, g, beta, state):
        def token(s, xs):
            o, s = tfm._delta_step(*(x[:, None] for x in xs), s)
            return s, o[:, 0]

        s, o = jax.lax.scan(token, state, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), s

    forms = [("chunked", chunked), ("recurrence", recurrence)]
    if jax.default_backend() == "tpu":
        forms.append(("kernel", pallas_kda.kda_chunk_scan))
    found = {}
    for name, fn in forms:
        out = jax.block_until_ready(fn(q, k, v, g, beta, state))
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(q, k, v, g, beta, state))
        found[name] = out
        found[name + "_ms"] = (time.perf_counter() - t0) * 1e3
    (o1, s1), (o2, s2) = found["chunked"], found["recurrence"]
    diff = np.abs(np.asarray(o1) - np.asarray(o2))
    at = np.unravel_index(diff.argmax(), diff.shape)
    line = {"device": jax.devices()[0].device_kind, "positions": S,
            "heads": H, "head_dim": d, "seed": args.seed,
            "out_abs_max": float(np.abs(np.asarray(o2)).max()),
            "out_diff_max": float(diff.max()),
            "out_diff_at": {"position": int(at[1]), "head": int(at[2]),
                            "channel": int(at[3])},
            "state_abs_max": float(np.abs(np.asarray(s2)).max()),
            "state_diff_max": float(np.abs(np.asarray(s1)
                                           - np.asarray(s2)).max()),
            "chunked_ms": found["chunked_ms"],
            "recurrence_ms": found["recurrence_ms"]}
    if "kernel" in found:
        o3, s3 = found["kernel"]
        line.update(
            kernel_out_diff_max=float(np.abs(np.asarray(o3)
                                             - np.asarray(o2)).max()),
            kernel_state_diff_max=float(np.abs(np.asarray(s3)
                                               - np.asarray(s2)).max()),
            kernel_ms=found["kernel_ms"])
    print(json.dumps(line))
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "delta_rule_chip_check.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
