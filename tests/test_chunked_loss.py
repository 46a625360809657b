"""The program the loss lowers to (``transformer._chunked_nll``).

``loss_fn`` has one path: a scan over chunks of positions that takes each
chunk's gradients in the trip that makes its logits: one loop, three products
with the vocabulary in them, no scatter into a ``[rows, vocab]`` cotangent.
``cfg.loss_chunk`` > 0 is the caller's chunk; with 0 the program picks it
from the shapes it sees (``transformer._loss_positions``: at most
``_LOSS_ROWS`` rows a trip; PERF.md PR 52). Values and gradients are
compared in ``tests/test_pallas_attention.py::
test_chunked_loss_matches_full``; here only the lowered text and the picker
are read.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.models import transformer as tfm

VOCAB = 251     # no other size of the tiny model: a dimension of 251 is V
D = 64


def _cfg(loss_chunk, tied=True, max_seq_len=64):
    return dataclasses.replace(tfm.tiny(), vocab_size=VOCAB,
                               loss_chunk=loss_chunk, tie_embeddings=tied,
                               max_seq_len=max_seq_len)


def _shapes(cfg, tokens=(2, 33)):
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return params, {"tokens": jax.ShapeDtypeStruct(tokens, jnp.int32)}


def _lowered(cfg, grad, tokens=(2, 33)):
    fn = functools.partial(tfm.loss_fn, cfg=cfg)
    return jax.jit(jax.value_and_grad(fn) if grad else fn) \
        .lower(*_shapes(cfg, tokens)).as_text()


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


def _assert_the_rule(text, B, C, trips):
    """One loop of ``trips`` over [B, C, d] chunks, the rule's three
    products, no scatter into a [rows, vocab] cotangent."""
    # the tiny model's layers are unrolled: the only loop is the loss's
    assert text.count("stablehlo.while") == 1
    dots = _vocab_dots(text)
    # logits, dh = dlogits . head, dhead += dlogits^T . h; nothing replayed
    assert sorted(d[2] for d in dots) == sorted(
        [[B, C, VOCAB], [B, C, D], [VOCAB, D]]), dots
    # the target is picked by comparison: no gather to transpose
    scatters = _scatter_operands(text)
    assert scatters and all(op[-1] != VOCAB for op in scatters), scatters
    assert re.search(rf"tensor<{trips}x{B}x{C}x{D}x\w+>", text)   # stacked


@pytest.mark.parametrize("tied", [True, False])
def test_chunked_backward_is_one_loop_of_three_products(tied):
    _assert_the_rule(_lowered(_cfg(8, tied), grad=True), 2, 8, 4)


def test_chunked_loss_alone_builds_no_gradient():
    text = _lowered(_cfg(8), grad=False)
    assert text.count("stablehlo.while") == 1
    dots = _vocab_dots(text)
    assert [d[2] for d in dots] == [[2, 8, VOCAB]], dots
    assert f"tensor<{VOCAB}x{D}xf32>" not in text.split("stablehlo.while")[1]


@pytest.mark.parametrize("tokens, trips", [((8, 513), 1), ((32, 513), 4)])
def test_program_picks_the_trips_from_the_shape(tokens, trips):
    """``loss_chunk`` 0: at the S 512 cells' [8, 512] the 4,096 rows are
    ONE trip of ``_LOSS_ROWS`` (a loop the compiler inlines, so that on one
    chip the embedding's update fuses into the weight-gradient product:
    PERF.md, PR 52); four times the batch is four trips of [32, 128]."""
    assert tfm._LOSS_ROWS == 4096
    B, C = tokens[0], 512 // trips
    text = _lowered(_cfg(0, max_seq_len=512), grad=True, tokens=tokens)
    _assert_the_rule(text, B, C, trips)
    assert text == _lowered(_cfg(C, max_seq_len=512), grad=True,
                            tokens=tokens)


def test_a_callers_chunk_is_taken_as_it_is():
    """The S 4096 cell's traffic file says ``loss_chunk`` 2048: two trips of
    [1, 2048] whatever ``_LOSS_ROWS`` is, the program its parent lowered
    (CHANGES.md, PR 52 has the digests); left to itself the program takes
    the 4,096 rows in one."""
    _assert_the_rule(_lowered(_cfg(2048, max_seq_len=4096), grad=True,
                              tokens=(1, 4097)), 1, 2048, 2)
    _assert_the_rule(_lowered(_cfg(0, max_seq_len=4096), grad=True,
                              tokens=(1, 4097)), 1, 4096, 1)


@pytest.mark.parametrize("B, S, cap, whole, rows, expect", [
    (8, 512, 0, False, 4096, 512),      # the S 512 cells: one trip
    (8, 512, 0, False, 2048, 256),      # two trips of [8, 256]
    (1, 4096, 0, False, 2048, 2048),    # the S 4096 cell's [1, 2048]
    (2, 32, 0, False, 4096, 32),        # rows already fit: one trip
    (3, 40, 0, False, 32, 10),          # B.S no multiple: 4 trips of 30 rows
    (2, 37, 0, False, 32, 37),          # S prime: one trip, not 37
    (64, 32, 0, False, 32, 32),         # B above the constant: one trip
    (2, 34, 0, False, 32, 34),          # best divisor 2 = 4 rows of 32: whole
    (8, 512, 0, True, 2048, 512),       # positions sharded over a mesh axis
    (8, 512, 128, True, 4096, 128),     # a caller's chunk is taken as it is
    (1, 4096, 2048, False, 4096, 2048),
    (2, 32, 64, False, 8, 32),          # a chunk over S: the whole sequence
])
def test_positions_a_trip(monkeypatch, B, S, cap, whole, rows, expect):
    monkeypatch.setattr(tfm, "_LOSS_ROWS", rows)
    assert tfm._loss_positions(B, S, cap, whole) == expect


def test_a_callers_chunk_must_divide_the_sequence():
    with pytest.raises(ValueError, match="must divide by loss_chunk 24"):
        tfm._loss_positions(2, 32, 24, False)


def test_positions_sharded_over_the_mesh_are_one_trip(monkeypatch):
    """``loss_fn(..., mesh=)`` with a ``seq`` axis that shards positions
    (ring attention): a loop over chunks of S would walk from device to
    device, so the whole sequence is one trip whatever ``_LOSS_ROWS`` says;
    a ``data`` axis alone leaves the program's pick as it is. The value is
    the meshless one."""
    monkeypatch.setattr(tfm, "_LOSS_ROWS", 16)
    cfg = dataclasses.replace(_cfg(0), attn_impl="ring")
    plain = dataclasses.replace(cfg, attn_impl="gather")
    tokens = (4, 33)
    seq = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))
    data = Mesh(np.array(jax.devices()[:2]), ("data",))
    for mesh, use, trips in ((seq, cfg, 1), (data, plain, 8)):
        text = jax.jit(jax.value_and_grad(
            lambda p, b, m=mesh, c=use: tfm.loss_fn(p, b, c, mesh=m))) \
            .lower(*_shapes(use, tokens)).as_text()
        assert re.search(rf"tensor<{trips}x4x{32 // trips}x{D}x\w+>", text)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(np.random.default_rng(7).integers(
        0, VOCAB, tokens), jnp.int32)}
    got = jax.jit(lambda p, b: tfm.loss_fn(p, b, cfg, mesh=seq))(params,
                                                                 batch)
    want = jax.jit(lambda p, b: tfm.loss_fn(p, b, plain))(params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-3)


def test_one_trip_is_a_loop_the_compiler_inlines():
    """A sequence whose rows fit one trip (every tiny test and example): the
    lowered text still holds the scan's ``while``, of one trip; the compiled
    program holds none, its three products stand alone."""
    cfg = _cfg(0)
    fn = jax.jit(jax.value_and_grad(functools.partial(tfm.loss_fn, cfg=cfg)))
    lowered = fn.lower(*_shapes(cfg))
    text = lowered.as_text()
    assert text.count("stablehlo.while") == 1
    assert re.search(rf"tensor<1x2x32x{D}x\w+>", text)
    assert not re.search(r" while\(", lowered.compile().as_text())


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
