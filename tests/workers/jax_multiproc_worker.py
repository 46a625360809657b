"""Worker: multi-process global mesh — the cross-process ICI data plane
(SURVEY.md §7 stage 5; reference analog: NCCLAllreduce in
horovod/common/ops/nccl_operations.cc where one process per device joins a
NCCL communicator).

tpurun's slot env provisions a jax.distributed coordinator
(HVD_JAX_COORD_ADDR); hvd.init() joins it, so jax.devices() spans every
process and in-jit collectives (psum / pmean) cross process boundaries ON
DEVICE, while the native TCP core still carries the control-plane
collectives in the same process.
"""
import os  # noqa: F401

# Per-process "chips": 2 virtual CPU devices each (the fake pod, SURVEY §4).
from horovod_tpu.jax.distributed import force_cpu_platform

force_cpu_platform(2)

import functools  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu.jax as hvd  # noqa: E402
from horovod_tpu import parallel  # noqa: E402

hvd.init()
r, s = hvd.rank(), hvd.size()

# --- the mesh spans processes
assert hvd.is_multiprocess(), "jax.distributed mesh did not form"
assert jax.process_count() == s, (jax.process_count(), s)
n_local = len(jax.local_devices())
assert len(jax.devices()) == s * n_local, jax.devices()

mesh = hvd.global_mesh()  # one 'data' axis over every chip in the job
assert mesh.shape["data"] == s * n_local

# --- in-jit psum crosses process boundaries on device
@jax.jit
@functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                   out_specs=P("data"), check_vma=False)
def summed(x):
    return jax.lax.psum(x, "data") * jnp.ones_like(x)

local = np.full((n_local, 1), float(r + 1), np.float32)
out = summed(hvd.shard_local_batch(local, mesh))
got = float(np.asarray(out.addressable_shards[0].data).ravel()[0])
expect = float(n_local * sum(range(1, s + 1)))
assert got == expect, (got, expect)

# --- full DP train step over the global mesh: gradient pmean on device
d, k = 5, 4  # features, rows per device
N = s * n_local * k  # global batch

rng = np.random.default_rng(0)  # every process can reconstruct the full set
X = rng.normal(size=(N, d)).astype(np.float32)
Y = (X @ np.arange(d).astype(np.float32))[:, None]
lo, hi = r * n_local * k, (r + 1) * n_local * k  # this process's shard

w0 = {"w": jnp.zeros((d, 1), jnp.float32)}
tx = optax.sgd(0.1)

def loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)

step = parallel.make_train_step(loss_fn, tx, mesh)
params = parallel.data_parallel.replicate(w0, mesh)
opt_state = parallel.data_parallel.replicate(tx.init(w0), mesh)

batch = hvd.shard_local_batch((X[lo:hi], Y[lo:hi]), mesh)
params, opt_state, loss = step(params, opt_state, batch)

# Expected: one SGD step with the gradient of the mean loss over the GLOBAL
# batch (pmean of per-shard grads == global mean for equal shard sizes).
w = np.zeros((d, 1), np.float32)
g = np.zeros_like(w)
for i in range(s * n_local):
    xs, ys = X[i * k:(i + 1) * k], Y[i * k:(i + 1) * k]
    g += 2.0 * xs.T @ (xs @ w - ys) / k
g /= s * n_local
w_expect = w - 0.1 * g

w_got = np.asarray(
    jax.tree.map(lambda a: a.addressable_shards[0].data, params)["w"])
assert np.allclose(w_got, w_expect, atol=1e-5), (w_got.ravel(),
                                                 w_expect.ravel())

# --- host metadata sync helper
ranks = hvd.process_allgather(np.asarray([r], np.int32))
assert sorted(ranks.ravel().tolist()) == list(range(s)), ranks

# --- the TCP core control plane composes in the same process
y = hvd.allreduce(jnp.full((4,), float(r + 1)), op=hvd.Sum, name="core.x")
assert np.allclose(np.asarray(y), sum(range(1, s + 1))), y

hvd.shutdown()
print(f"rank {r}: multiprocess mesh PASS", flush=True)
