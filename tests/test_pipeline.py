"""Pipeline parallelism (parallel/pipeline.py — beyond reference: the
reference has no PP or p2p send/recv at all). Correctness bar: the GPipe
schedule must match the sequential composition, forward AND gradients,
on the virtual CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


def test_pipeline_forward_matches_sequential():
    """parallel/pipeline.py (beyond reference — the reference has no PP
    or p2p at all): a 4-stage GPipe schedule over a 'pipe' mesh axis
    must reproduce running the same 4 layers sequentially on one
    device, for several microbatch counts (bubble masking correct at
    M == S and M > S)."""
    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               shard_stage_params)

    S, D = 4, 16
    cpus = jax.devices("cpu")
    assert len(cpus) >= S
    mesh = Mesh(np.asarray(cpus[:S]), ("pipe",))

    rng = np.random.default_rng(0)
    W = rng.normal(size=(S, D, D)).astype(np.float32) / np.sqrt(D)
    b = rng.normal(size=(S, D)).astype(np.float32) * 0.1
    x = rng.normal(size=(8, D)).astype(np.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def sequential(x):
        h = x
        for s in range(S):
            h = np.tanh(h @ W[s] + b[s])
        return h

    params = shard_stage_params({"w": W, "b": b}, mesh, "pipe")
    for M in (4, 8):
        out = np.asarray(pipeline_apply(stage_fn, params, jnp.asarray(x),
                                        mesh, "pipe", n_microbatches=M))
        assert np.allclose(out, sequential(x), atol=1e-5), (M, out[0][:4])


def test_pipeline_train_step_learns():
    """Gradients flow through the scan+ppermute schedule: jax.grad of a
    loss on pipeline outputs trains all four stages (loss falls 10x),
    and the per-stage grads match the sequential model's grads."""
    import optax

    from horovod_tpu.parallel.pipeline import (make_pipeline_train_step,
                                               pipeline_apply,
                                               shard_stage_params)

    S, D = 4, 8
    cpus = jax.devices("cpu")
    mesh = Mesh(np.asarray(cpus[:S]), ("pipe",))
    rng = np.random.default_rng(1)
    W = (rng.normal(size=(S, D, D)).astype(np.float32) / np.sqrt(D))
    x = rng.normal(size=(16, D)).astype(np.float32)
    y = np.roll(x, 1, axis=1) * 0.5  # a learnable linear-ish target

    def stage_fn(p, h):
        return h @ p["w"]

    def loss_fn(out, batch):
        return jnp.mean((out - batch["y"]) ** 2)

    # Grad parity vs the sequential composition, same loss.
    def seq_loss(Wflat):
        h = jnp.asarray(x)
        for s in range(S):
            h = h @ Wflat[s]
        return jnp.mean((h - jnp.asarray(y)) ** 2)

    params = shard_stage_params({"w": W}, mesh)
    def pipe_loss(p):
        out = pipeline_apply(stage_fn, p, jnp.asarray(x), mesh,
                             n_microbatches=4)
        return jnp.mean((out - jnp.asarray(y)) ** 2)

    g_pipe = jax.grad(pipe_loss)(params)["w"]
    g_seq = jax.grad(seq_loss)(jnp.asarray(W))
    assert np.allclose(np.asarray(g_pipe), np.asarray(g_seq),
                       atol=1e-5), np.abs(
        np.asarray(g_pipe) - np.asarray(g_seq)).max()

    # End-to-end training through make_pipeline_train_step.
    tx = optax.adam(3e-3)
    step = make_pipeline_train_step(stage_fn, loss_fn, tx, mesh,
                                    n_microbatches=4)
    opt_state = tx.init(params)
    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    losses = []
    for _ in range(200):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])


def test_pipeline_stage_count_mismatch_rejected():
    """A stage stack whose leading dim disagrees with the mesh axis must
    fail LOUDLY — shard_map would otherwise hand each device a slice of
    stages and silently compute the wrong (e.g. even-stages-only)
    composition."""
    import pytest

    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               shard_stage_params)

    cpus = jax.devices("cpu")
    mesh = Mesh(np.asarray(cpus[:4]), ("pipe",))
    W8 = np.zeros((8, 4, 4), np.float32)
    with pytest.raises(ValueError, match="stage"):
        shard_stage_params({"w": W8}, mesh)
    with pytest.raises(ValueError, match="stage"):
        pipeline_apply(lambda p, h: h, {"w": jnp.zeros((8, 4, 4))},
                       jnp.zeros((8, 4)), mesh, n_microbatches=4)


def test_pipelined_transformer_matches_forward():
    """The REAL model through the pipeline: 4 transformer blocks
    (models/transformer.py apply_block) as 4 pipeline stages must
    reproduce tfm.forward exactly — embedding and head handled outside,
    per-layer params stacked on the stage dim."""
    import dataclasses

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               shard_stage_params)

    # f32 compute: exact parity (bf16 would differ by rounding order
    # between the scanned pipeline and the unrolled forward).
    cfg = dataclasses.replace(tfm.tiny(), n_layers=4, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    cpus = jax.devices("cpu")
    mesh = Mesh(np.asarray(cpus[:4]), ("pipe",))

    B, S = 4, 16
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)

    want = np.asarray(tfm.forward(params, tokens, cfg))

    # Embed outside the pipeline (stage 0's input), blocks inside,
    # final-ln + head outside.
    dt = cfg.compute_dtype
    x = params["embed"].astype(dt)[tokens]
    x = x + params["pos_embed"].astype(dt)[:S][None]

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    stage_params = shard_stage_params(
        jax.tree.map(np.asarray, stacked), mesh)

    def stage_fn(layer, h):
        return tfm.apply_block(layer, h, cfg)

    h = pipeline_apply(stage_fn, stage_params, x, mesh, n_microbatches=4)
    h = tfm._layer_norm(h, params["final_ln"])
    got = np.asarray(jnp.einsum("bsd,vd->bsv", h,
                                params["embed"].astype(dt)))
    assert np.allclose(got, want, atol=2e-4), np.abs(got - want).max()


def test_pipeline_composes_with_data_parallel():
    """pp x dp on one 4x2 mesh: microbatch rows shard over 'data', each
    replica runs the pipeline schedule on its shard, outputs match the
    sequential composition on the full batch, and per-replica grads
    psum'd over 'data' equal the full-batch sequential grads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               shard_stage_params)

    S, D = 4, 8
    cpus = jax.devices("cpu")
    assert len(cpus) >= 8
    mesh = Mesh(np.asarray(cpus[:8]).reshape(4, 2), ("pipe", "data"))

    rng = np.random.default_rng(2)
    W = rng.normal(size=(S, D, D)).astype(np.float32) / np.sqrt(D)
    x = rng.normal(size=(16, D)).astype(np.float32)
    y = np.roll(x, 1, axis=1) * 0.5

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    params = shard_stage_params({"w": W}, mesh, "pipe")
    xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))

    out = np.asarray(pipeline_apply(stage_fn, params, xd, mesh,
                                    n_microbatches=4, batch_axis="data"))
    ref = x
    for s in range(S):
        ref = np.tanh(ref @ W[s])
    assert np.allclose(out, ref, atol=1e-5)

    # Gradient parity: mean loss over the FULL batch — per-shard mean
    # losses averaged over 'data' equal the full mean, so psum(grad)/2
    # must equal the sequential full-batch grad.
    def pipe_loss(p):
        o = pipeline_apply(stage_fn, p, xd, mesh, n_microbatches=4,
                           batch_axis="data")
        return jnp.mean((o - jnp.asarray(y)) ** 2)

    def seq_loss(Wf):
        h = jnp.asarray(x)
        for s in range(S):
            h = jnp.tanh(h @ Wf[s])
        return jnp.mean((h - jnp.asarray(y)) ** 2)

    g_pipe = np.asarray(jax.grad(pipe_loss)(params)["w"])
    g_seq = np.asarray(jax.grad(seq_loss)(jnp.asarray(W)))
    assert np.allclose(g_pipe, g_seq, atol=1e-5), np.abs(
        g_pipe - g_seq).max()


def test_pipeline_with_remat_stage():
    """jax.checkpoint around the stage function composes with the
    scan+ppermute schedule (the long-context recipe: rematerialized
    blocks inside pipeline stages) — gradients still match sequential."""
    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               shard_stage_params)

    S, D = 4, 8
    cpus = jax.devices("cpu")
    mesh = Mesh(np.asarray(cpus[:S]), ("pipe",))
    rng = np.random.default_rng(3)
    W = rng.normal(size=(S, D, D)).astype(np.float32) / np.sqrt(D)
    x = rng.normal(size=(8, D)).astype(np.float32)

    stage_fn = jax.checkpoint(lambda p, h: jnp.tanh(h @ p["w"]))
    params = shard_stage_params({"w": W}, mesh)

    def pipe_loss(p):
        out = pipeline_apply(stage_fn, p, jnp.asarray(x), mesh,
                             n_microbatches=4)
        return jnp.sum(out ** 2)

    def seq_loss(Wf):
        h = jnp.asarray(x)
        for s in range(S):
            h = jnp.tanh(h @ Wf[s])
        return jnp.sum(h ** 2)

    g_pipe = np.asarray(jax.grad(pipe_loss)(params)["w"])
    g_seq = np.asarray(jax.grad(seq_loss)(jnp.asarray(W)))
    assert np.allclose(g_pipe, g_seq, atol=1e-5), np.abs(
        g_pipe - g_seq).max()


# ---------------------------------------------------------------------------
# Schedule parity (ISSUE 13): every schedule is a different ORDER of the
# same math — loss and grads must match the single-device sequential
# reference, across stage counts and composed with data parallelism.
# ---------------------------------------------------------------------------


def _schedule_parity_setup(S, dp, n_slices):
    """Mesh + params + batch + the sequential reference for one case."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    D, B = 8, 16
    cpus = jax.devices("cpu")
    assert len(cpus) >= S * dp
    if dp > 1:
        mesh = Mesh(np.asarray(cpus[:S * dp]).reshape(S, dp),
                    ("pipe", "data"))
    else:
        mesh = Mesh(np.asarray(cpus[:S]), ("pipe",))

    rng = np.random.default_rng(7)
    W = (rng.normal(size=(n_slices, D, D)).astype(np.float32)
         / np.sqrt(D))
    x = rng.normal(size=(B, D)).astype(np.float32)
    y = np.roll(x, 1, axis=1) * 0.5

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    def loss_fn(out, batch):
        return jnp.mean((out - batch["y"]) ** 2)

    def seq_loss(Wf):
        h = jnp.asarray(x)
        for j in range(n_slices):
            h = jnp.tanh(h @ Wf[j])
        return jnp.mean((h - jnp.asarray(y)) ** 2)

    xs = jnp.asarray(x)
    if dp > 1:
        xs = jax.device_put(xs, NamedSharding(mesh, P("data")))
    batch = {"x": xs, "y": jnp.asarray(y)}
    return mesh, W, batch, stage_fn, loss_fn, seq_loss


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved", "zb"])
@pytest.mark.parametrize("S,dp", [(2, 1), (4, 1), (8, 1), (2, 2), (4, 2)])
def test_schedule_parity_vs_reference(schedule, S, dp):
    """Outputs AND gradients: each schedule x {2,4,8} stages (dp=1) and
    x {2,4} stages (dp=2) must allclose the single-device sequential
    composition — schedules change timing, not math."""
    from horovod_tpu.parallel.pipeline import (make_pipeline_value_and_grad,
                                               shard_stage_params)

    V = 2 if schedule == "interleaved" else None
    n_slices = S * (V or 1)
    mesh, W, batch, stage_fn, loss_fn, seq_loss = _schedule_parity_setup(
        S, dp, n_slices)

    params = shard_stage_params({"w": W}, mesh, "pipe",
                                virtual_stages=V or 1)
    vg = make_pipeline_value_and_grad(
        stage_fn, loss_fn, mesh, n_microbatches=S,
        batch_axis="data" if dp > 1 else None,
        schedule=schedule, virtual_stages=V)
    loss, grads = vg(params, batch)

    ref_loss, ref_grad = jax.value_and_grad(seq_loss)(jnp.asarray(W))
    assert np.isclose(float(loss), float(ref_loss), atol=1e-5), (
        schedule, S, dp, float(loss), float(ref_loss))
    g = np.asarray(grads["w"])
    assert g.shape == np.asarray(ref_grad).shape
    assert np.allclose(g, np.asarray(ref_grad), atol=1e-4), (
        schedule, S, dp, np.abs(g - np.asarray(ref_grad)).max())


def test_divisibility_error_suggests_nearest():
    """The divisibility error must hand the user the nearest valid
    n_microbatches instead of a bare modulo complaint."""
    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               shard_stage_params)

    cpus = jax.devices("cpu")
    mesh = Mesh(np.asarray(cpus[:4]), ("pipe",))
    W = np.zeros((4, 4, 4), np.float32)
    params = shard_stage_params({"w": W}, mesh)
    with pytest.raises(ValueError,
                       match="nearest valid n_microbatches is 4"):
        pipeline_apply(lambda p, h: h @ p["w"], params,
                       jnp.zeros((16, 4)), mesh, n_microbatches=5)


def test_stage_dim_error_mentions_virtual_slices():
    """With virtual_stages > 1 the stage-dim validator must explain the
    S*V expectation — '6 != 4' alone would send the user hunting."""
    from horovod_tpu.parallel.pipeline import shard_stage_params

    cpus = jax.devices("cpu")
    mesh = Mesh(np.asarray(cpus[:4]), ("pipe",))
    with pytest.raises(ValueError, match="virtual slices"):
        shard_stage_params({"w": np.zeros((6, 4, 4), np.float32)}, mesh,
                           virtual_stages=2)


def test_zb_single_stage_falls_back_and_stays_correct():
    """S=1 can't split the backward (nothing to overlap) — zb must fall
    back to the fused 1F1B path, count the fallback when metrics are on,
    and still produce the exact sequential loss/grads."""
    from horovod_tpu.observability import metrics
    from horovod_tpu.parallel.pipeline import (make_pipeline_value_and_grad,
                                               shard_stage_params)

    D, B = 8, 16
    cpus = jax.devices("cpu")
    mesh = Mesh(np.asarray(cpus[:1]), ("pipe",))
    rng = np.random.default_rng(9)
    W = (rng.normal(size=(1, D, D)).astype(np.float32) / np.sqrt(D))
    x = rng.normal(size=(B, D)).astype(np.float32)
    y = np.roll(x, 1, axis=1) * 0.5

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    def loss_fn(out, batch):
        return jnp.mean((out - batch["y"]) ** 2)

    was_enabled = metrics.enabled()
    metrics.enable()
    try:
        vg = make_pipeline_value_and_grad(stage_fn, loss_fn, mesh,
                                          n_microbatches=4, schedule="zb")
        snap = metrics.snapshot()["hvd_pipeline_zb_fallbacks_total"]
        reasons = {s["labels"]["reason"]: s["value"]
                   for s in snap["samples"]}
        assert reasons.get("single_stage", 0) >= 1, snap
    finally:
        if not was_enabled:
            metrics.disable()

    assert vg.schedule_label == "1f1b"
    params = shard_stage_params({"w": W}, mesh)
    loss, grads = vg(params, {"x": jnp.asarray(x), "y": jnp.asarray(y)})

    def seq_loss(Wf):
        h = jnp.tanh(jnp.asarray(x) @ Wf[0])
        return jnp.mean((h - jnp.asarray(y)) ** 2)

    ref_loss, ref_grad = jax.value_and_grad(seq_loss)(jnp.asarray(W))
    assert np.isclose(float(loss), float(ref_loss), atol=1e-5)
    assert np.allclose(np.asarray(grads["w"]), np.asarray(ref_grad),
                       atol=1e-4)
