"""Worker: the minimum end-to-end slice (SURVEY.md §7 stage 4) — JAX
gradients leave the device, ride the core's negotiation + fused TCP ring,
and come back averaged; DistributedOptimizer + broadcast_parameters drive a
real training loop across processes."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # a test worker never takes a chip

import numpy as np

import jax

cpu = jax.devices("cpu")[0]
jax.config.update("jax_default_device", cpu)

import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

import horovod_tpu.jax as hvd  # noqa: E402

hvd.init()
r, s = hvd.rank(), hvd.size()

# --- eager allreduce of a jax array through the core
x = jnp.full((8,), float(r + 1))
y = hvd.allreduce(x, op=hvd.Sum, name="eager.x")
assert np.allclose(np.asarray(y), sum(range(1, s + 1))), y

# --- allreduce inside jit lowers to io_callback through the same core
@jax.jit
def jitted(v):
    return hvd.allreduce(v * 2.0, op=hvd.Average, name="jit.x") + 1.0

out = jitted(jnp.full((4,), float(r)))
expected = 2.0 * np.mean(np.arange(s)) + 1.0
assert np.allclose(np.asarray(out), expected), (out, expected)

# --- the full core-bridged op set, eager AND in-jit (VERDICT r2 #10)
# allgather (eager, ragged dim0 allowed)
g = hvd.allgather(jnp.full((r + 1, 2), float(r)), name="core.ag")
assert np.asarray(g).shape == (s * (s + 1) // 2, 2)

# allgather in-jit (uniform dim0 declared at trace time)
@jax.jit
def jit_ag(v):
    return hvd.allgather(v, name="jit.ag")

ga = jit_ag(jnp.full((2, 3), float(r)))
assert np.asarray(ga).shape == (2 * s, 3)
exp = np.concatenate([np.full((2, 3), float(i)) for i in range(s)])
assert np.allclose(np.asarray(ga), exp)

# broadcast in-jit
@jax.jit
def jit_bc(v):
    return hvd.broadcast(v, root_rank=s - 1, name="jit.bc")

bc = jit_bc(jnp.full((4,), float(r + 1)))
assert np.allclose(np.asarray(bc), float(s))

# alltoall: eager ragged + in-jit uniform
out, rs = hvd.alltoall(jnp.arange(s * 2, dtype=jnp.float32) + 100 * r,
                       splits=[2] * s, name="core.a2a")
assert np.asarray(out).shape == (2 * s,) and (np.asarray(rs) == 2).all()

@jax.jit
def jit_a2a(v):
    # splits=None: bare tensor (same convention as the tf/torch bindings)
    return hvd.alltoall(v, name="jit.a2a")

o = np.asarray(jit_a2a(jnp.arange(s * 3, dtype=jnp.float32) + 100 * r))
# row block j of rank r's input lands at rank j, position r
for j in range(s):
    assert np.allclose(o[j * 3:(j + 1) * 3],
                       np.arange(r * 3, (r + 1) * 3) + 100 * j), (r, j, o)

# reducescatter: eager + in-jit with uneven dim0 (remainder to first ranks)
m = jnp.ones((s * 2 + 1, 3), jnp.float32) * (r + 1)
rsout = hvd.reducescatter(m, op=hvd.Sum, name="core.rs")
rows = (s * 2 + 1) // s + (1 if r < (s * 2 + 1) % s else 0)
assert np.asarray(rsout).shape == (rows, 3)
assert np.allclose(np.asarray(rsout), sum(range(1, s + 1)))

@jax.jit
def jit_rs(v):
    return hvd.reducescatter(v, op=hvd.Average, name="jit.rs")

rsj = jit_rs(jnp.ones((s * 2 + 1, 3), jnp.float32) * (r + 1))
assert np.asarray(rsj).shape == (rows, 3)
assert np.allclose(np.asarray(rsj), np.mean(np.arange(1, s + 1)))

# --- broadcast_parameters: rank-divergent params converge to rank 0's
params = {"w": jnp.full((3, 3), float(r)), "b": jnp.full((3,), float(r))}
params = hvd.broadcast_parameters(params, root_rank=0)
assert np.allclose(np.asarray(params["w"]), 0.0)

# --- full DP training loop: DistributedOptimizer averages grads
rng = np.random.default_rng(7)  # same data everywhere; shard by rank
X = rng.normal(size=(64, 5)).astype(np.float32)
Y = (X @ np.arange(5).astype(np.float32))[:, None]
Xr, Yr = jnp.asarray(X[r::s]), jnp.asarray(Y[r::s])

w0 = {"w": jnp.asarray(rng.normal(size=(5, 1)).astype(np.float32))}
w0 = hvd.broadcast_parameters(w0, root_rank=0)
tx = hvd.DistributedOptimizer(optax.sgd(0.05), name="dp.grads")
opt_state = tx.init(w0)


def loss_fn(p, xb, yb):
    return jnp.mean((xb @ p["w"] - yb) ** 2)


@jax.jit
def step(p, o, xb, yb):
    loss, g = jax.value_and_grad(loss_fn)(p, xb, yb)
    updates, o = tx.update(g, o, p)
    return optax.apply_updates(p, updates), o, loss


p, o = w0, opt_state
first = last = None
for i in range(20):
    p, o, loss = step(p, o, Xr, Yr)
    if first is None:
        first = float(loss)
    last = float(loss)
assert last < first * 0.2, (first, last)

# All ranks must hold identical weights (grads were averaged identically).
gathered = hvd.allgather(jnp.reshape(p["w"], (1, -1)), name="final.w")
gw = np.asarray(gathered)
assert gw.shape[0] == s
assert np.allclose(gw, gw[0], atol=1e-6), gw

# fp16 compression path (gradients cross the wire as float16)
tx2 = hvd.DistributedOptimizer(optax.sgd(0.05), name="fp16.grads",
                               compression=hvd.Compression.fp16)
loss, g = jax.value_and_grad(loss_fn)(p, Xr, Yr)
updates, _ = tx2.update(g, tx2.init(p), p)
assert jax.tree.all(jax.tree.map(lambda u: bool(jnp.all(jnp.isfinite(u))), updates))
assert updates["w"].dtype == jnp.float32  # decompressed back

# metric averaging
m = hvd.metric_average(float(r), name="metric.r")
assert abs(m - np.mean(np.arange(s))) < 1e-9

hvd.shutdown()
print(f"rank {r}: JAX DP PASS", flush=True)
