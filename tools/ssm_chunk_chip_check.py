#!/usr/bin/env python3
"""A fill's state-space recurrence on the device it is started on, at the
published widths of the two configurations that have one: the Pallas kernel
``ops/pallas_ssm.ssm_chunk_scan`` against ``transformer._ssd_blocks`` (the
definition), a non-zero entering state, operands in bfloat16 as the mixer
hands them over. Per shape one JSON line: the largest difference of ``y``
and of the state leaving (over the largest value), and the milliseconds of
``--layers`` calls chained through their state inside one program, each
call on operands of its own (a layer's: XLA shares nothing between them;
host clock around ``block_until_ready``, the least of five), for the plain
form and for each ``--variant`` (keywords of the kernel:
``heads_pack=16,tile=128``; on the CPU the kernel is interpreted and the
times say nothing). Lines are also appended to
``chiprun_out/ssm_chunk_chip_check.jsonl``.

    python3 tools/ssm_chunk_chip_check.py [--shape granite] [--positions 512]
        [--variant heads_pack=8] [--variant tile=256] [--seed 0]
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402

from horovod_tpu.models import transformer as tfm     # noqa: E402
from horovod_tpu.ops import pallas_ssm                # noqa: E402

# (heads, head_dim, groups, state_size, block, state-space layers)
SHAPES = {"granite": (64, 64, 1, 128, 256, 36),
          "nemotron": (128, 64, 8, 128, 128, 5)}


def _operands(S, H, P, G, N, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.bfloat16
    x = jax.nn.silu(jax.random.normal(ks[0], (1, S, H, P))).astype(dt)
    # A head's step and rate as init_params draws them: softplus of a bias
    # whose step is log-uniform over (0.001, 0.1); rates over (1, 16).
    step = jnp.exp(jax.random.uniform(ks[1], (1, S, H), jnp.float32,
                                      np.log(1e-3), np.log(1e-1)))
    rate = -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)
    b_in = jax.nn.silu(jax.random.normal(ks[3], (1, S, G, N))).astype(dt)
    c_out = jax.nn.silu(jax.random.normal(ks[4], (1, S, G, N))).astype(dt)
    state = jax.random.normal(ks[5], (1, H, P, N))
    return x, step, rate, b_in, c_out, state


def _chained(recur):
    """A call a layer of the stacked operands, each entering on the state
    the last one left, the outputs summed so that none is dead."""
    def run(x, step, rate, b_in, c_out, state):
        total = jnp.zeros((), jnp.float32)
        for i in range(x.shape[0]):
            y, state = recur(x[i], step[i], rate[i], b_in[i], c_out[i], state)
            total = total + jnp.sum(y)
        return total, state
    return jax.jit(run)


def _ms(fn, args):
    jax.block_until_ready(fn(*args))
    best = np.inf
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--positions", type=int, action="append")
    ap.add_argument("--variant", action="append", default=[])
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    variants = [dict((k, int(v)) for k, v in
                     (kv.split("=") for kv in text.split(",") if kv))
                for text in args.variant or [""]]
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for shape in args.shape or sorted(SHAPES):
        H, P, G, N, block, layers = SHAPES[shape]
        layers = args.layers or layers
        for S in args.positions or [512]:
            ops = _operands(S, H, P, G, N, args.seed)
            per_layer = [_operands(S, H, P, G, N, args.seed + 1 + i)[:5]
                         for i in range(layers)]
            stacked = tuple(jnp.stack(v) for v in zip(*per_layer)) + ops[5:]
            plain = functools.partial(tfm._ssd_blocks, block=block)
            want = jax.jit(plain)(*ops)
            line = {"device": jax.devices()[0].device_kind, "shape": shape,
                    "positions": S, "layers": layers, "seed": args.seed,
                    "plain_ms": _ms(_chained(plain), stacked),
                    "variants": []}
            for kw in variants:
                kernel = functools.partial(
                    pallas_ssm.ssm_chunk_scan,
                    **{"block": block, "interpret": not on_tpu, **kw})
                got = jax.jit(kernel)(*ops)
                line["variants"].append(dict(
                    kw, y_rel=_rel(got[0], want[0]),
                    state_rel=_rel(got[1], want[1]),
                    ms=_ms(_chained(kernel), stacked)))
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, "ssm_chunk_chip_check.jsonl"),
                      "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
