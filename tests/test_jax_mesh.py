"""In-mesh (SPMD) collective + DP train-step tests on the 8-device virtual
CPU mesh (SURVEY.md §4: the 'fake pod')."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from horovod_tpu.ops import jax_ops  # noqa: E402
from horovod_tpu.parallel import create_mesh, make_train_step  # noqa: E402
from horovod_tpu.parallel.data_parallel import (  # noqa: E402
    replicate, shard_batch)


@pytest.fixture(scope="module")
def mesh():
    # The session may expose a real TPU platform too; the test pod is the
    # 8-device virtual CPU backend (conftest sets the XLA flag).
    cpus = jax.devices("cpu")
    assert len(cpus) == 8, cpus
    return create_mesh({"data": 8}, devices=cpus)


def _smap(mesh, fn, in_spec, out_spec):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                             out_specs=out_spec, check_vma=False))


def test_allreduce_mean_sum(mesh):
    x = jnp.arange(8.0)

    out = _smap(mesh, lambda a: jax_ops.allreduce(a, "data", jax_ops.Sum),
                P("data"), P("data"))(x)
    assert np.allclose(out, np.full(8, x.sum()))

    out = _smap(mesh, lambda a: jax_ops.allreduce(a, "data", jax_ops.Average),
                P("data"), P("data"))(x)
    assert np.allclose(out, np.full(8, x.mean()))


def test_allgather(mesh):
    x = jnp.arange(16.0).reshape(8, 2)
    out = _smap(mesh, lambda a: jax_ops.allgather(a, "data"),
                P("data"), P("data"))(x)
    # Each shard gathers the full array; with out_spec P('data') the global
    # result is 8 stacked copies of rows.
    assert out.shape == (64, 2)
    got = np.asarray(out).reshape(8, 8, 2)
    exp = np.broadcast_to(np.arange(16.0).reshape(8, 2), (8, 8, 2))
    assert np.allclose(got, exp)


def test_broadcast(mesh):
    x = jnp.arange(8.0)
    out = _smap(mesh, lambda a: jax_ops.broadcast(a, "data", root_index=3),
                P("data"), P("data"))(x)
    assert np.allclose(out, np.full(8, 3.0))


def test_alltoall(mesh):
    # 8 shards each with 8 rows -> transpose blocks.
    x = jnp.arange(64.0).reshape(64, 1)
    out = _smap(mesh, lambda a: jax_ops.alltoall(a, "data"),
                P("data"), P("data"))(x)
    assert out.shape == (64, 1)
    got = np.asarray(out).reshape(8, 8)
    exp = np.arange(64).reshape(8, 8).T
    assert np.allclose(got, exp)


def test_reducescatter(mesh):
    # Global (64, 4) -> per-shard (8, 4) -> scattered to (1, 4) per shard.
    x = jnp.ones((64, 4))
    out = _smap(mesh, lambda a: jax_ops.reducescatter(a, "data", jax_ops.Sum),
                P("data"), P("data"))(x)
    assert out.shape == (8, 4)
    assert np.allclose(out, 8.0)


def test_dp_train_step_matches_single_device(mesh):
    """The sharded step must be numerically identical to the single-device
    step on the full batch (allreduce-mean == full-batch gradient)."""

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(4, 1)).astype(np.float32)),
              "b": jnp.zeros((1,), jnp.float32)}
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)
    tx = optax.sgd(0.1)

    # Single-device reference.
    def ref_step(p, o, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    p1, o1, l1 = ref_step(params, tx.init(params), (x, y))

    # Sharded step.
    step = make_train_step(loss_fn, tx, mesh)
    p = replicate(params, mesh)
    o = replicate(tx.init(params), mesh)
    batch = shard_batch((x, y), mesh)
    p2, o2, l2 = step(p, o, batch)

    assert np.allclose(float(l1), float(l2), rtol=1e-5)
    assert np.allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5)
    assert np.allclose(np.asarray(p1["b"]), np.asarray(p2["b"]), rtol=1e-5)


@pytest.mark.parametrize("shape, data_axis", [
    ({"data": 8}, "data"), ({"data": 4, "model": 2}, "data"),
    ({"data": 4, "fsdp": 2}, ("data", "fsdp"))],
    ids=["data8", "data4_model2", "data4_fsdp2"])
def test_dp_train_step_compile_options_and_counter(shape, data_axis):
    """On the CPU's devices the step is compiled with no option (only a TPU
    mesh whose every device is a data shard gets the asynchronous-collective
    options, tests/test_tpu_compile.py) and trains to the reference's
    numbers; the counter reads no asynchronous all-reduce of n > 0 in its
    compiled text."""
    from horovod_tpu.parallel import data_parallel

    mesh = create_mesh(shape, devices=jax.devices("cpu"))
    axes = (data_axis,) if isinstance(data_axis, str) else data_axis
    assert data_parallel._overlap_options(mesh, axes) is None

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)

    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(4, 1)).astype(np.float32),
              "b": np.zeros((1,), np.float32)}
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)
    tx = optax.sgd(0.1)
    loss1, g = jax.value_and_grad(loss_fn)(params, (x, y))
    want = optax.apply_updates(params, tx.update(g, tx.init(params))[0])

    step = make_train_step(loss_fn, tx, mesh, data_axis=data_axis)
    assert type(step).__name__ == "PjitFunction"
    args = (replicate(params, mesh), replicate(tx.init(params), mesh),
            shard_batch((x, y), mesh, data_axis))
    text = step.lower(*args).compile().as_text()
    n, n_async = data_parallel.grad_collective_counts(text)
    assert n > 0 and n_async == 0
    got, _, loss2 = step(*args)
    assert np.allclose(float(loss1), float(loss2), rtol=1e-5)
    assert np.allclose(np.asarray(want["w"]), np.asarray(got["w"]),
                       rtol=1e-5)


_SYNC_TEXT = """HloModule jit_step, is_scheduled=true

%region_1.2 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(%a, %b)
}

ENTRY %main.3_spmd (p0: f32[8,4]) -> (f32[8,4], f32[]) {
  %p0 = f32[8,4]{1,0} parameter(0)
  %all-reduce.1 = (f32[8,4]{1,0}, f32[4]{0}) all-reduce(%p0, %x), to_apply=%region_1.2
  %all-reduce.2 = f32[] all-reduce(%loss), to_apply=%region_1.2
}
"""
# One collective in three phases (start, the matmul fusion it runs beside,
# done), one ``all-reduce-start``, and the loss's synchronous mean.
_ASYNC_TEXT = """HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8,4]) -> (f32[8,4], u32[]) {
  %all-reduce.5 = f32[8,4]{1,0} all-reduce(%param_0.1), to_apply=%region_1.2
}

%async_collective_fusion.8.clone (param_0.2: f32[8,4]) -> (f32[8,4], u32[]) {
  %convolution.3 = bf16[8,4]{1,0} convolution(%a, %b)
  %all-reduce.6 = f32[8,4]{1,0} all-reduce(%param_0.2), to_apply=%region_1.2
}

%fused_computation.9 (param_0.3: f32[8,4]) -> f32[8,4] {
  %all-reduce.7 = f32[8,4]{1,0} all-reduce(%param_0.3), to_apply=%region_1.2
}

ENTRY %main.3_spmd (p0: f32[8,4]) -> (f32[8,4], f32[]) {
  %async-collective-start.1 = (f32[8,4]{1,0}, u32[]) fusion(%g), kind=kCustom, calls=%fused_computation.7
  %get-tuple-element.4 = f32[8,4]{1,0} get-tuple-element(%async-collective-start.1), index=0
  %fusion.8 = (f32[8,4]{1,0}, u32[]) fusion(%get-tuple-element.4), kind=kOutput, calls=%async_collective_fusion.8.clone
  %async-collective-done.1 = f32[8,4]{1,0} fusion(%fusion.8), kind=kCustom, calls=%fused_computation.9
  %all-reduce-start.2 = f32[4]{0} all-reduce-start(%h), to_apply=%region_1.2
  %all-reduce-done.2 = f32[4]{0} all-reduce-done(%all-reduce-start.2)
  ROOT %all-reduce.3 = f32[] all-reduce(%loss), to_apply=%region_1.2
}
"""


@pytest.mark.parametrize("text, counts", [(_SYNC_TEXT, (2, 0)),
                                          (_ASYNC_TEXT, (3, 2))],
                         ids=["synchronous", "asynchronous"])
def test_grad_collective_counts_reads_compiled_text(text, counts):
    """A collective's three phases count once; what is under no
    ``async-collective-`` instruction is synchronous."""
    from horovod_tpu.parallel.data_parallel import grad_collective_counts

    assert grad_collective_counts(text) == counts


# One step of one chip as the profiler names it: a synchronous all-reduce
# (the parent's step), and an asynchronous one in its phases: the start, a
# matmul fusion and an update fusion that carry its steps, the done.
_TRACE_NAMES = [
    "%all-reduce.1 = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %p), "
    "to_apply=%region_1.2",
    "%async-collective-start.1 = (f32[8,4]{1,0}, u32[]) fusion(%g), "
    "kind=kCustom, calls=%fused_computation.7",
    "%fusion.8 = (f32[8,4]{1,0}, u32[]) fusion(%a), kind=kOutput, "
    "calls=%async_collective_fusion.8",
    "%fusion.9 = (f32[8,4]{1,0}, u32[]) fusion(%b), kind=kLoop, "
    "output_to_operand_aliasing={{0}: (0, {})}, "
    "calls=%async_collective_fusion.9",
    "%async-collective-done.1 = f32[8,4]{1,0} fusion(%fusion.9), "
    "kind=kCustom, calls=%fused_computation.9",
    "%fusion.10 = f32[8,4]{1,0} fusion(%c), kind=kLoop, "
    "calls=%fused_computation.10",
]


@pytest.mark.parametrize("events, want", [
    # the parent's step: nothing asynchronous to read
    ([(0, 0, 4_000_000), (5, 4_000_000, 1_000_000)],
     {"allreduce_ms": 4.0, "allreduce_exposed_ms": 4.0,
      "allreduce_async_ms": None, "allreduce_async_exposed_ms": 0.0,
      "optimizer_overlap_dev_ms": None}),
    # this PR's: start 0.1, matmul 2, update 1.5, done 0.5, a plain update 1
    ([(1, 0, 100_000), (2, 100_000, 2_000_000), (3, 2_100_000, 1_500_000),
      (4, 3_600_000, 500_000), (5, 4_100_000, 1_000_000)],
     {"allreduce_ms": None, "allreduce_exposed_ms": 0.0,
      "allreduce_async_ms": 4.1, "allreduce_async_exposed_ms": 0.6,
      "optimizer_overlap_dev_ms": 1.5})],
    ids=["synchronous", "asynchronous"])
def test_benchmark_readers_of_the_asynchronous_all_reduces(events, want):
    """``benchmark/layer_metrics``' files of PR 50 read an asynchronous
    collective by its instructions' names: in flight from start to done
    (with the fusions that carry it), exposed in the start and the done, and
    the update fusions among the carriers. The accepted readers see only the
    synchronous ones."""
    import importlib
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        n, s, d = zip(*events)
        trace = {"names": _TRACE_NAMES, "planes": [{
            "name": "/device:TPU:0", "lines": [{
                "name": "XLA Ops", "n": list(n), "s": list(s),
                "d": list(d)}]}]}
        ctx = {"trace": trace, "fields": {"trace_steps": 1}}
        for metric, value in want.items():
            with open(os.path.join(root, "benchmark", "layer_metrics",
                                   metric + ".json")) as f:
                src = json.load(f)
            reader = importlib.import_module(
                "benchmark.readers." + src["reader"])
            got = reader.read(ctx, src.get("params", {}))
            assert got == pytest.approx(value), metric
    finally:
        sys.path.remove(root)


def test_dp_train_step_unjitted(mesh):
    """``jit=False`` hands back the shard_map'ed step itself: no compile
    option is attached to it, its caller jits it."""
    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"]) ** 2)

    tx = optax.sgd(0.1)
    step = make_train_step(loss_fn, tx, mesh, jit=False)
    assert not hasattr(step, "lower")
    params = {"w": jnp.ones((4, 1), jnp.float32)}
    x = np.ones((16, 4), np.float32)
    p, o, loss = jax.jit(step)(replicate(params, mesh),
                               replicate(tx.init(params), mesh),
                               shard_batch(x, mesh))
    assert np.allclose(float(loss), 16.0)
    assert np.allclose(np.asarray(p["w"]), 1.0 - 0.1 * 8.0)


def test_train_step_loss_decreases(mesh):
    def loss_fn(params, batch):
        x, y = batch
        h = jnp.tanh(x @ params["w1"])
        pred = h @ params["w2"]
        return jnp.mean((pred - y) ** 2)

    rng = np.random.default_rng(1)
    params = {
        "w1": jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32) * 0.3),
        "w2": jnp.asarray(rng.normal(size=(16, 1)).astype(np.float32) * 0.3),
    }
    tx = optax.adam(1e-2)
    step = make_train_step(loss_fn, tx, mesh)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (x[:, :1] * 2.0).astype(np.float32)

    p = replicate(params, mesh)
    o = replicate(tx.init(params), mesh)
    batch = shard_batch((x, y), mesh)
    losses = []
    for _ in range(20):
        p, o, loss = step(p, o, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses


def test_dp_train_step_gradient_accumulation(mesh):
    """accum_steps (the compiled-path backward_passes_per_step, VERDICT r2
    weak #7): microbatched scan accumulation must produce the SAME params
    as the full-shard step for a mean-type loss, and reject indivisible
    batches at trace time."""

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.normal(size=(4, 1)).astype(np.float32))}
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.normal(size=(32, 1)).astype(np.float32)
    tx = optax.sgd(0.1)

    # donate=False: this test reuses the same replicated inputs across
    # step variants, and replicate() of an already-placed array can alias
    # the buffer a donated call would delete.
    full = make_train_step(loss_fn, tx, mesh, donate=False)
    accum = make_train_step(loss_fn, tx, mesh, accum_steps=4, donate=False)
    p0 = replicate(params, mesh)
    o0 = replicate(tx.init(params), mesh)
    batch = shard_batch((x, y), mesh)
    p1, _, l1 = full(p0, o0, batch)
    p2, _, l2 = accum(replicate(params, mesh),
                      replicate(tx.init(params), mesh), batch)
    assert np.allclose(float(l1), float(l2), rtol=1e-5)
    assert np.allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5)

    bad = make_train_step(loss_fn, tx, mesh, accum_steps=3, donate=False)
    with pytest.raises(ValueError, match="divisible"):
        bad(replicate(params, mesh), replicate(tx.init(params), mesh),
            batch)

    with pytest.raises(ValueError, match="accum_steps"):
        make_train_step(loss_fn, tx, mesh, accum_steps=0)


def test_adasum_device_plane_matches_vhdd_reference():
    """ops/jax_ops.adasum (device-plane Adasum, VERDICT r4 missing #5)
    must reproduce the host core's VHDD recursion (csrc/adasum.cc): at
    each doubling level, pair combines sa*a + sb*b with the dot products
    of the level's block aggregates. Checked against a numpy
    re-implementation of the recursion, plus the two analytic anchors:
    identical vectors pass through unchanged (sa=sb=1/2), mutually
    orthogonal vectors add exactly (sa=sb=1)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import jax_ops

    n, D = 8, 33
    cpus = jax.devices("cpu")
    assert len(cpus) >= n, cpus  # conftest forces 8 virtual CPU devices
    mesh = Mesh(np.asarray(cpus[:n]), ("data",))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=P("data", None),
                       out_specs=P("data", None), check_vma=False)
    def run(stacked):
        return jax_ops.adasum(stacked[0], "data")[None]

    def np_adasum(vs):
        vs = [v.astype(np.float64) for v in vs]
        m = len(vs)
        dist = 1
        while dist < m:
            nxt = list(vs)
            for i in range(m):
                a, b = vs[i], vs[i ^ dist]
                ab, aa, bb = a @ b, a @ a, b @ b
                sa = 1.0 - ab / (2 * aa) if aa > 0 else 1.0
                sb = 1.0 - ab / (2 * bb) if bb > 0 else 1.0
                nxt[i] = sa * a + sb * b
            vs = nxt
            dist <<= 1
        return vs[0]

    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((n, D)).astype(np.float32)
    x = jax.device_put(jnp.asarray(vecs),
                       NamedSharding(mesh, P("data", None)))
    out = np.asarray(run(x))
    want = np_adasum(list(vecs))
    # Every shard holds the same combined result.
    for r in range(n):
        assert np.allclose(out[r], want, atol=1e-4), (r, out[r][:4])

    # Identical vectors -> unchanged.
    same = np.broadcast_to(vecs[0], (n, D)).copy()
    out = np.asarray(run(jax.device_put(
        jnp.asarray(same), NamedSharding(mesh, P("data", None)))))
    assert np.allclose(out, same, atol=1e-5)

    # Orthogonal vectors -> exact sum.
    ortho = np.zeros((n, D), np.float32)
    for r in range(n):
        ortho[r, r] = float(r + 1)
    out = np.asarray(run(jax.device_put(
        jnp.asarray(ortho), NamedSharding(mesh, P("data", None)))))
    assert np.allclose(out, ortho.sum(0), atol=1e-5), out[0][:8]


def test_make_train_step_adasum_reduction():
    """make_train_step(grad_reduce='adasum'): the DP wrapper trains with
    the device-plane Adasum instead of pmean and the loss still falls."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from horovod_tpu.parallel.data_parallel import (make_train_step,
                                                    replicate, shard_batch)

    cpus = jax.devices("cpu")
    assert len(cpus) >= 8, cpus
    mesh = Mesh(np.asarray(cpus[:8]), ("data",))
    w_true = np.arange(1, 5, dtype=np.float32)

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    tx = optax.sgd(0.05)
    step = make_train_step(loss_fn, tx, mesh, grad_reduce="adasum")
    params = replicate({"w": jnp.zeros(4)}, mesh)
    opt_state = replicate(tx.init({"w": jnp.zeros(4)}), mesh)

    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    Y = X @ w_true
    batch = shard_batch({"x": jnp.asarray(X), "y": jnp.asarray(Y)}, mesh)
    losses = []
    for _ in range(40):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])
    assert np.isfinite(losses[-1])


def test_jitted_bridge_allreduce_lowers_with_no_environment(monkeypatch):
    """A jitted ``hvd_allreduce`` lowers to an ``io_callback`` on whatever
    backend JAX has, with no platform or override variable consulted
    (execution would need an initialized core, which single-process pytest
    does not have; chip_smoke.py runs the whole DistributedOptimizer step)."""
    from horovod_tpu.ops import jax_ops as jo

    for var in ("JAX_PLATFORMS", "HVD_INJIT_CALLBACKS"):
        monkeypatch.delenv(var, raising=False)
    lowered = jax.jit(lambda x: jo.hvd_allreduce(x, op=jo.Sum)).lower(
        jnp.ones(4))
    assert "callback" in lowered.as_text()
