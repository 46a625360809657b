"""Pallas flash-attention numerics (interpret mode on CPU): forward and
gradients must match the naive XLA attention that models/transformer.py
uses, causal and non-causal, f32 and bf16 inputs."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas_attention import flash_attention


def _naive(q, k, v, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bshk,bthk->bhst",
                        q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhst,bthk->bshk", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_naive(causal):
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 256, 3, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, block=128, interpret=True)
    ref = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gradients_match_naive():
    rng = np.random.default_rng(1)
    B, S, H, D = 1, 256, 2, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block=128, interpret=True)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(_naive(q, k, v, True).astype(jnp.float32)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn, name in zip(g_flash, g_naive, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gn),
                                   atol=3e-4, rtol=3e-4,
                                   err_msg=f"d{name} mismatch")


def test_bf16_inputs_and_partial_block():
    rng = np.random.default_rng(2)
    B, S, H, D = 1, 128, 2, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
               for _ in range(3))
    out = flash_attention(q, k, v, causal=True, block=128, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _naive(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)
    # block > S clamps to S; non-divisible S rejected clearly.
    out2 = flash_attention(q, k, v, causal=True, block=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out2, np.float32),
                               np.asarray(out, np.float32), atol=1e-6)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q[:, :100], k[:, :100], v[:, :100], block=64,
                        interpret=True)


def _naive_lse(q, k, v, mode):
    """float32 attention and its log-sum-exp under the kernels' three
    masks; a row with no visible key (row 0 under "strict") gives o = 0
    and an lse the loss below leaves out."""
    S, D = q.shape[1], q.shape[-1]
    s = jnp.einsum("bshk,bthk->bhst", q, k) / np.sqrt(D)
    if mode != "none":
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool),
                               k=-1 if mode == "strict" else 0), s, -np.inf)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.where(jnp.isfinite(lse)[..., None], jnp.exp(s - lse[..., None]),
                  0.0)
    return (jnp.einsum("bhst,bthk->bshk", p, v),
            jnp.where(jnp.isfinite(lse), lse, -1e30))


def _lse_loss(attn, with_lse):
    """A loss over attn's (o, lse) whose cotangent on lse is no constant
    (ring attention's merge feeds one back), or over o alone."""
    def loss(q, k, v):
        o, lse = attn(q, k, v)
        out = jnp.sum(jnp.sin(o.astype(jnp.float32)))
        if with_lse:
            out += jnp.sum(jnp.where(lse > -1e29, jnp.cos(lse), 0.0))
        return out, o
    return loss


@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o+lse"])
@pytest.mark.parametrize("mode", ["diag", "strict", "none"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("block", [64, 256])
def test_forward_and_gradients_match_gather_and_naive(block, dtype, mode,
                                                      with_lse):
    """Operands in the arrays' dtype into every product, f32 everything
    else: forward and the three gradients of the one backward kernel agree
    with the float32 naive attention to what the inputs allow, in every
    mode, with and without a cotangent on lse; and in bf16 causal with the
    gather attention the S 512 cells run (bf16 logits, so the coarser of
    the two), which they beat."""
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops.pallas_attention import flash_attention_lse

    rng = np.random.default_rng(7)
    B, S, H, D = 1, 256, 2, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
               for _ in range(3))

    def run(attn):
        (_, o), g = jax.value_and_grad(
            _lse_loss(attn, with_lse), argnums=(0, 1, 2), has_aux=True)(
                q, k, v)
        return [np.asarray(a, np.float32) for a in (o,) + tuple(g)]

    flash = run(lambda q, k, v: flash_attention_lse(
        q, k, v, mode=mode, block=block, interpret=True))
    naive = run(lambda q, k, v: _naive_lse(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        mode))
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-4
    for f, n, name in zip(flash, naive, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(f, n, atol=tol, rtol=tol,
                                   err_msg=f"{name} against f32 naive")
    if dtype == jnp.bfloat16 and mode == "diag" and not with_lse:
        cfg = tfm.TransformerConfig(vocab_size=8, d_model=H * D, n_heads=H,
                                    n_layers=1, d_ff=8, max_seq_len=S,
                                    dtype="bfloat16")
        gather = run(lambda q, k, v: (tfm.causal_attend(q, k, v, cfg), None))
        for f, g, n, name in zip(flash, gather, naive,
                                 ("o", "dq", "dk", "dv")):
            np.testing.assert_allclose(f, g, atol=4e-2, rtol=4e-2,
                                       err_msg=f"{name} against gather")
            # and closer to the float32 answer than the gather path is
            assert np.abs(f - n).mean() <= np.abs(g - n).mean(), name


def _sub_jaxprs(params):
    for val in params.values():
        for x in (val if isinstance(val, (tuple, list)) else (val,)):
            x = getattr(x, "jaxpr", x)         # ClosedJaxpr -> Jaxpr
            if hasattr(x, "eqns"):
                yield x


def _eqns(jaxpr, name):
    """Every equation of primitive ``name`` in ``jaxpr``, nested ones too
    (not looking inside a match)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        else:
            for sub in _sub_jaxprs(eqn.params):
                yield from _eqns(sub, name)


@pytest.fixture(params=["one", "two"])
def backward(request, monkeypatch):
    """Both sides of the backward's rule on the shape
    (``_bwd_vmem_limit``): "one" kernel as every test shape gets it, "two"
    as a shape gets them whose dq would not fit the chip's VMEM, here by
    leaving the chip none."""
    if request.param == "two":
        _no_vmem(monkeypatch)
    return request.param


def _no_vmem(monkeypatch):
    from horovod_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_VMEM_DEFAULT", 0)
    monkeypatch.setattr(pa, "_VMEM_MOST", 0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("mode", ["diag", "strict", "none"])
def test_every_product_takes_input_dtype_and_gives_f32(dtype, mode, backward):
    """Forward and backward kernels: each dot_general's operands have the
    arrays' dtype (nothing is widened on its way to the MXU) and its
    result is float32 (nothing is accumulated narrower)."""
    from horovod_tpu.ops.pallas_attention import flash_attention_lse

    x = jnp.zeros((1, 256, 2, 64), dtype)

    def loss(q, k, v):
        o, lse = flash_attention_lse(q, k, v, mode=mode, block=128,
                                     interpret=True)
        return o.astype(jnp.float32).sum() + lse.sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    kernels = list(_eqns(jaxpr.jaxpr, "pallas_call"))
    dots = [[d for sub in _sub_jaxprs(kern.params)
             for d in _eqns(sub, "dot_general")] for kern in kernels]
    # fwd and the one backward kernel's five products, or fwd, dq, dk/dv
    assert sorted(len(d) for d in dots) == {"one": [2, 5],
                                            "two": [2, 3, 4]}[backward]
    for d in sum(dots, []):
        assert [a.aval.dtype for a in d.invars] == [dtype, dtype], d
        assert d.outvars[0].aval.dtype == jnp.float32, d
    # scratch (m, l and the accumulators: rank 2) and the lse / delta
    # blocks and the resident dq (rank 4) are float32 whatever the arrays
    for kern in kernels:
        for sub in _sub_jaxprs(kern.params):
            refs = [a.aval for a in sub.invars]
            assert len([r for r in refs if len(r.shape) == 2]) in (1, 2, 3)
            assert all(r.dtype == jnp.float32 for r in refs
                       if len(r.shape) in (2, 4)), refs


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_one_backward_kernel_gives_the_two_kernels_bits(dtype, monkeypatch):
    """Same products on the same operands, each dq block summed over its
    K blocks in the same order: the two sides of the rule agree bit for
    bit, with a cotangent on lse."""
    from horovod_tpu.ops import pallas_attention as pa

    rng = np.random.default_rng(46)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 32)), dtype)
               for _ in range(3))
    grad = jax.grad(lambda q, k, v: _lse_loss(
        lambda *a: pa.flash_attention_lse(*a, mode="diag", block=64,
                                          interpret=True), True)(q, k, v)[0],
        argnums=(0, 1, 2))
    one = grad(q, k, v)
    _no_vmem(monkeypatch)
    assert pa._bwd_vmem_limit(256, 32, dtype, 64) is None
    for a, b, name in zip(one, grad(q, k, v), ("dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), name)


def test_backward_rule_by_shape():
    """_bwd_vmem_limit at the shapes it was compiled and measured at: the
    default VMEM to [16, 4096, 128] bf16, a raised limit from there to
    S 65,536, the two kernels past it."""
    from horovod_tpu.ops.pallas_attention import (_VMEM_DEFAULT, _VMEM_MOST,
                                                  _bwd_vmem_limit)

    bf16, f32 = jnp.bfloat16, jnp.float32
    assert _bwd_vmem_limit(4096, 64, bf16, 1024) == 0
    assert _bwd_vmem_limit(4096, 128, bf16, 1024) == 0
    assert _bwd_vmem_limit(512, 64, bf16, 512) == 0
    assert _bwd_vmem_limit(4096, 64, bf16, 256) == 0
    for S, D, dtype in ((8192, 64, bf16), (4096, 64, f32), (16384, 64, bf16),
                        (65536, 64, bf16), (32768, 128, f32)):
        assert _VMEM_DEFAULT < _bwd_vmem_limit(S, D, dtype, 1024) \
            <= _VMEM_MOST, (S, D, dtype)
    # what it asks for grows with the resident dq: 2 x S x 128 lanes x 4 B
    assert _bwd_vmem_limit(32768, 64, bf16, 1024) \
        - _bwd_vmem_limit(16384, 64, bf16, 1024) == 2 * 16384 * 128 * 4
    assert _bwd_vmem_limit(131072, 64, bf16, 1024) is None
    assert _bwd_vmem_limit(65536, 256, bf16, 1024) is None


# sha256 (first 16 hex digits) over o, lse, dq, dk, dv of _f32_digest's
# call, taken at the commit before the kernels' operands and grid changed
# (64669e3). head_dim 32: a scale that is no power of two.
_F32_DIGESTS = {
    ("diag", 32): "67e552e11127834d", ("diag", 64): "4615fbbeac834dab",
    ("strict", 32): "5923e9dabd78b35b", ("strict", 64): "b24c57d2027f3384",
    ("none", 32): "a3b20c128ab91c5e", ("none", 64): "fd5fe36600207941",
}


@pytest.mark.parametrize("mode, D", sorted(_F32_DIGESTS))
def test_float32_inputs_give_the_same_bits_as_before(mode, D):
    """The kernels adapt to the input dtype: float32 arrays run the
    program they always ran, bit for bit, forward and gradients."""
    import hashlib

    from horovod_tpu.ops.pallas_attention import flash_attention_lse

    rng = np.random.default_rng(32)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, D)), jnp.float32)
               for _ in range(3))

    def f(q, k, v):
        o, lse = flash_attention_lse(q, k, v, mode=mode, block=64,
                                     interpret=True)
        loss = jnp.sum(jnp.sin(o)) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0))
        return loss, (o, lse)

    (_, (o, lse)), g = jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True)(q, k, v)
    h = hashlib.sha256()
    for a in (o, lse) + tuple(g):
        h.update(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes())
    assert h.hexdigest()[:16] == _F32_DIGESTS[mode, D]


def test_large_block_request_takes_the_big_tile():
    """_validate: 512 or more asks for "large" and gets 1024 where the
    sequence divides by it; a smaller request stands."""
    from horovod_tpu.ops.pallas_attention import _validate

    def used(S, block):
        x = jax.ShapeDtypeStruct((1, S, 2, 64), jnp.bfloat16)
        return _validate(x, x, x, block)

    assert used(4096, 512) == used(4096, 1024) == used(4096, 2048) == 1024
    assert used(1024, 512) == 1024
    assert used(1536, 512) == 512 and used(512, 512) == 512
    assert used(4096, 256) == 256 and used(4096, 128) == 128
    assert used(384, 256) == 192          # largest multiple-of-8 divisor


def test_transformer_flash_impl_matches_gather():
    """attn_impl='flash' in the transformer produces the same logits as the
    XLA 'gather' path — single device and on a dp x tp mesh (shard_map)."""
    import dataclasses

    from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401

    from horovod_tpu.models import transformer as tfm

    cfg_g = tfm.tiny()
    cfg_f = dataclasses.replace(cfg_g, attn_impl="flash")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg_g)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, cfg_g.vocab_size, (2, 32)),
                         jnp.int32)
    out_g = tfm.forward(params, tokens, cfg_g)
    out_f = tfm.forward(params, tokens, cfg_f)
    np.testing.assert_allclose(np.asarray(out_g, np.float32),
                               np.asarray(out_f, np.float32),
                               atol=2e-2, rtol=2e-2)

    devs = jax.devices()[:4]
    if len(devs) < 4:  # conftest forces 8 virtual CPU devices in CI
        pytest.skip("needs >=4 devices for the dp x tp shard_map branch")
    mesh = Mesh(np.asarray(devs).reshape(2, 2), ("data", "model"))
    out_m = jax.jit(lambda p, t: tfm.forward(p, t, cfg_f, mesh=mesh))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(out_m, np.float32),
                               np.asarray(out_f, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("n_blocks", [2, 3, 8])
def test_strict_mode_and_masked_rows(n_blocks):
    """mode="strict" (q > k, ring striped cross-shard mask): row 0 is
    fully masked and must return o = 0, lse = sentinel, and ZERO
    gradients — the -1e30 sentinel must not cancel in exp(s - m). At 2, 3
    and 8 blocks: the enumeration of live block pairs keeps every row's
    diagonal block and nothing past it."""
    from horovod_tpu.ops.pallas_attention import flash_attention_lse

    rng = np.random.default_rng(6)
    B, S, H, D = 1, 64 * n_blocks, 2, 32
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
               for _ in range(3))
    o, lse = flash_attention_lse(q, k, v, mode="strict", block=64,
                                 interpret=True)
    # reference: strict lower-triangular mask
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bshk,bthk->bhst", q, k) * scale
    mask = jnp.tril(jnp.ones((S, S), bool), k=-1)
    s = jnp.where(mask[None, None], s, -np.inf)
    w = jax.nn.softmax(s, -1)
    ref = jnp.einsum("bhst,bthk->bshk", jnp.where(jnp.isnan(w), 0, w), v)
    assert np.allclose(np.asarray(o[:, 0]), 0.0), o[:, 0]
    assert np.all(np.asarray(lse[:, :, 0]) < -1e29)
    np.testing.assert_allclose(np.asarray(o[:, 1:]),
                               np.asarray(ref[:, 1:]), atol=2e-5, rtol=2e-5)

    # gradients of a loss touching every row: row 0 contributes nothing.
    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention_lse(q, k, v, mode="strict", block=64,
                            interpret=True)[0] ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert np.allclose(np.asarray(g[0][:, 0]), 0.0), g[0][:, 0]
    assert np.all(np.isfinite(np.asarray(g[1]))) \
        and np.all(np.isfinite(np.asarray(g[2])))


def _assert_grads_close(g_full, g_chunk):
    # bf16 compute: chunked summation reassociates, so grads agree to bf16
    # rounding, not bitwise.
    full, chunk = jax.tree.leaves(g_full), jax.tree.leaves(g_chunk)
    assert len(full) == len(chunk)
    for a, b in zip(full, chunk):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-3, rtol=1e-2)


def _plain_loss(params, batch, cfg):
    """The plain reference the loss's rule is held to: one projection in the
    compute dtype, a float32 ``log_softmax``, ``take_along_axis`` and the
    compiler's own transpose (``transformer._nll`` until PR 52)."""
    from horovod_tpu.models import transformer as tfm

    tokens = batch["tokens"]
    hidden = tfm.forward(params, tokens[:, :-1], cfg, return_hidden=True)
    head = tfm.head_weights(params, cfg)
    logits = jnp.einsum("bsd,vd->bsv", hidden, head.astype(hidden.dtype))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.mean(picked)


@pytest.mark.parametrize("tokens, loss_chunk, rows, trips", [
    # a caller's chunk: 2 and 4 chunks of S 32, B 1 and 2
    ((1, 33), 16, None, 2), ((2, 33), 16, None, 2),
    ((1, 33), 8, None, 4), ((2, 33), 8, None, 4),
    # loss_chunk 0, the program picks (transformer._loss_positions):
    ((2, 33), 0, None, 1),      # rows under _LOSS_ROWS: one trip
    ((2, 33), 0, 16, 4),        # 4 trips of [2, 8]
    ((3, 41), 0, 32, 4),        # B.S = 120 no multiple of 32: 4 of [3, 10]
    ((2, 38), 0, 32, 1),        # S 37 is prime: the whole sequence
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
@pytest.mark.parametrize("tied", [True, False])
def test_chunked_loss_matches_full(monkeypatch, tied, tokens, loss_chunk,
                                   rows, trips):
    """Whatever chunk the caller or the program picks, ``loss_fn`` computes
    the plain cross-entropy without ever keeping the [B, S, vocab] float32
    tensor (value and every gradient leaf), whether the head is the
    embedding or a weight of its own."""
    import dataclasses

    from horovod_tpu.models import transformer as tfm

    if rows:
        monkeypatch.setattr(tfm, "_LOSS_ROWS", rows)
    cfg = dataclasses.replace(tfm.tiny(), tie_embeddings=tied,
                              loss_chunk=loss_chunk)
    B, S = tokens[0], tokens[1] - 1
    assert S // tfm._loss_positions(B, S, loss_chunk, False) == trips
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert ("head" in params) == (not tied)
    rng = np.random.default_rng(5)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, tokens), jnp.int32)}
    l_full, g_full = jax.value_and_grad(_plain_loss)(params, batch, cfg)
    l_chunk, g_chunk = jax.value_and_grad(tfm.loss_fn)(params, batch, cfg)
    np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-5)
    _assert_grads_close(g_full, g_chunk)
    # called without differentiation (its own rule, no gradient built)
    np.testing.assert_allclose(
        float(jax.jit(tfm.loss_fn, static_argnums=2)(params, batch, cfg)),
        float(jax.jit(_plain_loss, static_argnums=2)(params, batch, cfg)),
        rtol=1e-5)


def test_chunked_loss_matches_full_under_accumulation():
    """Through ``make_train_step`` with two microbatches a step: the fused
    rule's gradients are summed by the accumulation scan like any others."""
    import dataclasses

    import optax
    from jax.sharding import Mesh

    from horovod_tpu import parallel
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.tiny()
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    tx = optax.sgd(1.0)               # the update IS the gradient
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (8, 33)), jnp.int32)}
    out = {}
    for chunk in (0, 8):
        cfg_c = dataclasses.replace(cfg, loss_chunk=chunk)
        step = parallel.make_train_step(
            lambda p, b, c=cfg_c: tfm.loss_fn(p, b, c), tx, mesh,
            accum_steps=2, donate=False)
        new, _, loss = step(params, tx.init(params), batch)
        out[chunk] = (float(loss), jax.tree.map(jnp.subtract, params, new))
    np.testing.assert_allclose(out[0][0], out[8][0], rtol=1e-5)
    _assert_grads_close(out[0][1], out[8][1])


def test_flash_under_jit_and_vmapless_shapes():
    """The kernel composes with jit (the transformer uses it inside one)."""
    rng = np.random.default_rng(3)
    B, S, H, D = 2, 128, 2, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    f = jax.jit(functools.partial(flash_attention, causal=True,
                                  interpret=True))
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(_naive(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)
