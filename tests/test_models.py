"""Model zoo tests (virtual 8-device CPU mesh; see conftest.py).

Mirrors the reference's benchmark-model smoke coverage and adds what the
reference never had: sharded-training correctness for tp/sp/ep layouts.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import resnet, transformer as tfm


def test_resnet50_forward_shapes():
    model, variables = resnet.create_train_state(
        jax.random.PRNGKey(0), image_size=64, num_classes=10)
    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    logits = jax.jit(lambda v, x: model.apply(v, x, train=False))(
        variables, x)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()


def test_resnet50_s2d_stem():
    """The space-to-depth stem (models/resnet.py stem="s2d" — the
    MLPerf-closed equivalent-weights rearrangement used by the TPU
    benchmark) produces the same output geometry as the classic 7x7/2
    stem and trains with finite gradients."""
    model, variables = resnet.create_train_state(
        jax.random.PRNGKey(0), image_size=64, num_classes=10, stem="s2d")
    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    logits = jax.jit(lambda v, x: model.apply(v, x, train=False))(
        variables, x)
    assert logits.shape == (2, 10)
    # Stem kernel is 4x4x12 (2x2 space-to-depth of 3 channels).
    k = variables["params"]["conv_init"]["kernel"]
    assert k.shape[:3] == (4, 4, 12), k.shape

    def loss(params):
        out, _ = model.apply(
            {"params": params,
             "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return jnp.mean(out ** 2)

    grads = jax.jit(jax.grad(loss))(variables["params"])
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)


def test_transformer_forward_and_loss():
    cfg = tfm.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 17)),
        jnp.int32)
    loss = jax.jit(lambda p, b: tfm.loss_fn(p, b, cfg))(
        params, {"tokens": tokens})
    assert np.isfinite(float(loss))


def test_transformer_moe_matches_dense_expert():
    """With 1 expert, MoE must equal the dense FFN given identical weights."""
    cfg_d = tfm.tiny(n_experts=0)
    cfg_m = tfm.tiny(n_experts=1)
    p = tfm.init_params(jax.random.PRNGKey(0), cfg_d)
    pm = tfm.init_params(jax.random.PRNGKey(0), cfg_m)
    for ld, lm in zip(p["layers"], pm["layers"]):
        lm["w_in"] = ld["w_in"][None]
        lm["w_out"] = ld["w_out"][None]
    for k in ("embed", "pos_embed", "final_ln"):
        pm[k] = p[k]
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg_d.vocab_size, (2, 9)),
        jnp.int32)
    out_d = tfm.forward(p, tokens, cfg_d)
    out_m = tfm.forward(pm, tokens, cfg_m)
    np.testing.assert_allclose(np.asarray(out_d, np.float32),
                               np.asarray(out_m, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("axes", [
    {"data": 8},
    {"data": 2, "model": 4},
    {"data": 2, "seq": 2, "model": 2},
])
def test_transformer_sharded_matches_single_device(axes):
    """tp/sp/ep-sharded forward == single-device forward (same params)."""
    import dataclasses
    # 8 experts: divisible by the expert-carrying axis in every mesh below
    cfg = dataclasses.replace(tfm.tiny(n_experts=8), expert_axis="data")
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (8, 16)),
        jnp.int32)
    ref = tfm.forward(params, tokens, cfg)

    sizes = list(axes.values())
    mesh = Mesh(np.asarray(jax.devices()[:int(np.prod(sizes))])
                .reshape(sizes), tuple(axes.keys()))
    specs = tfm.filter_specs(tfm.param_specs(cfg), mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    sharded = jax.device_put(params, shardings)
    tok_sh = jax.device_put(
        tokens, NamedSharding(mesh, P("data" if "data" in axes else None,
                                      None)))
    out = jax.jit(lambda p, t: tfm.forward(p, t, cfg, mesh=mesh))(
        sharded, tok_sh)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(out, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_graft_entry_dryrun():
    import __graft_entry__ as g
    g.dryrun_multichip(8)


def test_transformer_ring_attention_matches_gather():
    """attn_impl='ring' (sequence-parallel K/V rotation) must equal the
    gather implementation on the same sharded mesh."""
    import dataclasses

    cfg = dataclasses.replace(tfm.tiny(), attn_impl="ring")
    cfg_g = tfm.tiny()
    params = tfm.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(6).integers(0, cfg.vocab_size, (4, 16)),
        jnp.int32)
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
                ("data", "seq", "model"))
    specs = tfm.filter_specs(tfm.param_specs(cfg), mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    sharded = jax.device_put(params, shardings)
    tok_sh = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
    out_ring = jax.jit(
        lambda p, t: tfm.forward(p, t, cfg, mesh=mesh))(sharded, tok_sh)
    out_gather = jax.jit(
        lambda p, t: tfm.forward(p, t, cfg_g, mesh=mesh))(sharded, tok_sh)
    np.testing.assert_allclose(np.asarray(out_ring, np.float32),
                               np.asarray(out_gather, np.float32),
                               rtol=3e-2, atol=3e-2)
