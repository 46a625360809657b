"""JAX data-parallel synthetic benchmark (reference:
examples/pytorch/pytorch_synthetic_benchmark.py shape, on the JAX binding):
every rank trains the same MLP on synthetic data; gradients ride the
native core's fused allreduce; rank 0 reports images/sec.

Run: tpurun -np 4 python examples/jax_synthetic_benchmark.py

The in-jit gradient allreduce lowers to a host callback (``io_callback``);
examples/jax_mesh_train.py is the pure-XLA in-mesh path.
"""
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd

hvd.init()
r, s = hvd.rank(), hvd.size()

BATCH = int(os.environ.get("BATCH", 64))
STEPS = int(os.environ.get("STEPS", 50))
DIM = int(os.environ.get("DIM", 256))

rng = np.random.default_rng(r)
params = {
    "w1": jnp.asarray(np.random.default_rng(0).normal(
        0, 0.02, (DIM, DIM)), jnp.float32),
    "w2": jnp.asarray(np.random.default_rng(1).normal(
        0, 0.02, (DIM, 1)), jnp.float32),
}
params = hvd.broadcast_parameters(params, root_rank=0)
tx = hvd.DistributedOptimizer(optax.adam(1e-3), name="bench.grads")
opt_state = tx.init(params)


def loss_fn(p, x, y):
    h = jax.nn.relu(x @ p["w1"])
    return jnp.mean((h @ p["w2"] - y) ** 2)


@jax.jit
def step(p, o, x, y):
    loss, g = jax.value_and_grad(loss_fn)(p, x, y)
    updates, o = tx.update(g, o, p)
    return optax.apply_updates(p, updates), o, loss


x = jnp.asarray(rng.normal(size=(BATCH, DIM)), jnp.float32)
y = jnp.asarray(rng.normal(size=(BATCH, 1)), jnp.float32)
p, o = params, opt_state
p, o, _ = step(p, o, x, y)  # compile
t0 = time.perf_counter()
for _ in range(STEPS):
    p, o, loss = step(p, o, x, y)
jax.block_until_ready(loss)
dt = time.perf_counter() - t0
if r == 0:
    print(f"{s} ranks: {BATCH * STEPS * s / dt:.1f} samples/sec total "
          f"(loss {float(loss):.4f})")
hvd.shutdown()
