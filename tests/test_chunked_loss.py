"""The program the chunked loss lowers to (``transformer._chunked_nll``).

Under ``cfg.loss_chunk`` the scan over chunks takes each chunk's gradients
in the trip that makes its logits: one loop, three products with the
vocabulary in them, no scatter into a ``[rows, vocab]`` cotangent. With
``loss_chunk`` 0 the loss is ``_nll``'s single projection, untouched (the
one-chip cells want the compiler's own backward there, PERF.md PR 41).
Values and gradients are compared in ``tests/test_pallas_attention.py::
test_chunked_loss_matches_full``; here only the lowered text is read.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models import transformer as tfm

VOCAB = 251     # no other size of the tiny model: a dimension of 251 is V
D = 64


def _cfg(loss_chunk, tied=True):
    return dataclasses.replace(tfm.tiny(), vocab_size=VOCAB,
                               loss_chunk=loss_chunk, tie_embeddings=tied)


def _shapes(cfg):
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return params, {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}


def _lowered(cfg, grad):
    fn = functools.partial(tfm.loss_fn, cfg=cfg)
    return jax.jit(jax.value_and_grad(fn) if grad else fn) \
        .lower(*_shapes(cfg)).as_text()


def _dims(tensor_type):
    return [int(x) for x in re.findall(r"(\d+)x", tensor_type)]


def _vocab_dots(text):
    """-> [(operand dims, operand dims, result dims)] of every
    ``dot_general`` of the module with the vocabulary among its sizes."""
    found = []
    for m in re.finditer(r"stablehlo\.dot_general .*: \(tensor<([^>]*)>, "
                         r"tensor<([^>]*)>\) -> tensor<([^>]*)>", text):
        dims = [_dims(g) for g in m.groups()]
        if any(VOCAB in d for d in dims):
            found.append(tuple(dims))
    return found


def _scatter_operands(text):
    """-> the dims of the operand of every ``scatter`` of the module."""
    return [_dims(m.group(1)) for m in re.finditer(
        r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<([^>]*)>', text, re.S)]


@pytest.mark.parametrize("tied", [True, False])
def test_chunked_backward_is_one_loop_of_three_products(tied):
    text = _lowered(_cfg(8, tied), grad=True)
    # the tiny model's layers are unrolled: the only loop is the loss's
    assert text.count("stablehlo.while") == 1
    dots = _vocab_dots(text)
    # logits, dh = dlogits . head, dhead += dlogits^T . h; nothing replayed
    assert sorted(d[2] for d in dots) == sorted(
        [[2, 8, VOCAB], [2, 8, D], [VOCAB, D]]), dots
    # the target is picked by comparison: no gather to transpose
    scatters = _scatter_operands(text)
    assert scatters and all(op[-1] != VOCAB for op in scatters), scatters


def test_chunked_loss_alone_builds_no_gradient():
    text = _lowered(_cfg(8), grad=False)
    assert text.count("stablehlo.while") == 1
    dots = _vocab_dots(text)
    assert [d[2] for d in dots] == [[2, 8, VOCAB]], dots
    assert f"tensor<{VOCAB}x{D}xf32>" not in text.split("stablehlo.while")[1]


def test_unchunked_loss_is_nlls_single_projection(monkeypatch):
    calls = []
    nll = tfm._nll

    def counted(*args):
        calls.append(1)
        return nll(*args)

    monkeypatch.setattr(tfm, "_nll", counted)
    for chunk, reached in ((0, 1), (8, 0)):
        del calls[:]
        cfg = _cfg(chunk)
        jaxpr = jax.make_jaxpr(lambda p, b: tfm.loss_fn(p, b, cfg))(
            *_shapes(cfg))
        assert len(calls) == reached
        assert ("custom_vjp_call" in str(jaxpr)) == (not reached)
    # and its backward is the compiler's: take_along_axis transposed, the
    # scatter into the [B, S, vocab] cotangent that the chunked rule avoids
    text = _lowered(_cfg(0), grad=True)
    assert "stablehlo.while" not in text
    assert any(op[-1] == VOCAB for op in _scatter_operands(text))


def test_backward_scaling_reads_under_loss():
    """The backward rule is a scaling of what the loop left; where the
    incoming cotangent is not 1 it is instructions, and they carry the
    call's scope path: ``loss_dev_ms`` keeps reading all of the loss."""
    from horovod_tpu.observability import scopes

    cfg = _cfg(8)
    text = jax.jit(jax.grad(lambda p, b: 3.0 * tfm.loss_fn(p, b, cfg))) \
        .lower(*_shapes(cfg)).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    assert any(p.endswith("/mul") and scopes.parse(p) == (None, "loss", True)
               for p in paths), sorted(p for p in paths if "loss" in p)
