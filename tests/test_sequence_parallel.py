"""Sequence/context/expert parallelism tests on the virtual 8-device mesh.

Correctness bar: sharded implementations must match a single-device
reference computed on the gathered arrays.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel import (
    make_moe_layer,
    make_ring_attention,
    make_ulysses_attention,
)


def _ref_attention(q, k, v, causal):
    q32, k32, v32 = (np.asarray(t, np.float32) for t in (q, k, v))
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = np.einsum("bqhd,bkhd->bhqk", q32, k32) * scale
    if causal:
        S = s.shape[-1]
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask[None, None], s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    w = np.exp(s)
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", w, v32)


def _qkv(B=2, S=32, H=4, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def seq_mesh():
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("data", "seq"))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(seq_mesh, causal):
    q, k, v = _qkv()
    fn = make_ring_attention(seq_mesh, axis="seq", causal=causal,
                             batch_axis="data")
    out = fn(q, k, v)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layout", ["contiguous", "striped"])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_flash_inner_matches_reference(seq_mesh, causal,
                                                      layout):
    """inner="flash" runs the fused pallas kernel per block pair and
    merges partials by log-sum-exp; must equal the dense reference for
    both layouts (striped exercises the kernel's "strict" mode)."""
    q, k, v = _qkv(S=64, D=16, seed=11)
    fn = make_ring_attention(seq_mesh, axis="seq", causal=causal,
                             batch_axis="data", layout=layout,
                             inner="flash")
    out = fn(q, k, v)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("layout", ["contiguous", "striped"])
def test_ring_attention_flash_inner_gradients(seq_mesh, layout):
    """Gradients flow through the kernel's custom vjp AND the lse-based
    partial merge (the lse cotangent path): must match the einsum ring.
    striped exercises the "strict" mode backward (masked-row hazard)."""
    q, k, v = _qkv(B=2, S=32, H=2, D=8, seed=12)
    fns = {inner: make_ring_attention(seq_mesh, axis="seq", causal=True,
                                      batch_axis="data", layout=layout,
                                      inner=inner)
           for inner in ("einsum", "flash")}

    grads = {}
    for inner, fn in fns.items():
        grads[inner] = jax.grad(
            lambda q, k, v, fn=fn: jnp.sum(fn(q, k, v) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    for ge, gf, name in zip(grads["einsum"], grads["flash"], "qkv"):
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gf),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_reference(seq_mesh, causal):
    q, k, v = _qkv()
    fn = make_ulysses_attention(seq_mesh, axis="seq", causal=causal,
                                batch_axis="data")
    out = fn(q, k, v)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_ring_matches_ulysses_long_seq(seq_mesh):
    """Cross-check the two SP schemes against each other at longer S."""
    q, k, v = _qkv(B=2, S=128, H=8, D=16, seed=3)
    ring = make_ring_attention(seq_mesh, axis="seq", batch_axis="data")
    uly = make_ulysses_attention(seq_mesh, axis="seq", batch_axis="data")
    np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                               np.asarray(uly(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_moe_dispatch_matches_dense():
    """alltoall dispatch/combine == dense one-hot routing when capacity is
    generous (no drops)."""
    rng = np.random.default_rng(7)
    E, D, F, T = 8, 16, 32, 64          # tokens per expert shard
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("expert",))
    w_in = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((8 * T, D)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((8 * T, E)), jnp.float32)

    layer = make_moe_layer(mesh, "expert", w_in, w_out,
                           capacity_factor=float(E))  # capacity = T: no drop
    out = layer(x, logits)

    # dense reference: every token through its argmax expert, gate-weighted
    probs = jax.nn.softmax(np.asarray(logits, np.float32), axis=-1)
    eidx = np.argmax(probs, -1)
    gate = probs[np.arange(len(eidx)), eidx]
    h = np.einsum("td,edf->tef", np.asarray(x), np.asarray(w_in))
    h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
    y = np.einsum("tef,efd->ted", h, np.asarray(w_out))
    ref = y[np.arange(len(eidx)), eidx] * gate[:, None]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_moe_drops_overflow():
    """With capacity 1 and all tokens routed to one expert, all but one
    token per shard-queue are dropped (output zeros)."""
    E, D, T = 8, 4, 16
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("expert",))
    w_in = jnp.zeros((E, D, D), jnp.float32) + jnp.eye(D)
    w_out = jnp.zeros((E, D, D), jnp.float32) + jnp.eye(D)
    x = jnp.ones((8 * T, D), jnp.float32)
    logits = jnp.zeros((8 * T, E), jnp.float32).at[:, 0].set(10.0)
    layer = make_moe_layer(mesh, "expert", w_in, w_out, capacity_factor=0.0)
    out = np.asarray(layer(x, logits))
    # capacity clamps to >=1: exactly one token per shard survives
    nonzero_rows = (np.abs(out).sum(-1) > 1e-6).sum()
    assert nonzero_rows == 8, nonzero_rows


def test_ragged_alltoall_uneven_splits():
    """ragged_alltoall (the ICI alltoallv — VERDICT r3 #7): every shard
    sends a DIFFERENT number of rows to each peer; receivers must see
    exactly the sent rows, tagged with correct counts, zero-padded."""
    import functools

    from jax import shard_map

    from horovod_tpu.ops.jax_ops import ragged_alltoall

    Pn, D, cap = 8, 4, 6
    mesh = Mesh(np.asarray(jax.devices()[:Pn]), ("x",))
    # shard i sends (i + j) % 4 rows to peer j; row values encode
    # (src, dst, slot) so the receiver can verify provenance exactly.
    counts = np.array([[(i + j) % 4 for j in range(Pn)]
                      for i in range(Pn)], np.int32)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(),
                       out_specs=(P("x", None, None, None), P("x", None)),
                       check_vma=False)
    def go():
        i = jax.lax.axis_index("x")
        my_counts = jnp.asarray(counts)[i]                       # [P]
        starts = jnp.cumsum(my_counts) - my_counts
        T = int(counts.sum(1).max())
        row = jnp.arange(T, dtype=jnp.int32)
        # destination of each row under the grouped layout
        dst = jnp.sum((row[:, None] >= (starts + my_counts)[None, :])
                      .astype(jnp.int32), axis=1)
        slot = row - starts[dst]
        x = (i * 10000 + dst * 100 + slot).astype(jnp.float32)[:, None] \
            * jnp.ones((1, D), jnp.float32)
        recv, rcounts = ragged_alltoall(x, my_counts, "x", cap)
        return recv[None], rcounts[None]

    recv, rcounts = go()
    recv, rcounts = np.asarray(recv), np.asarray(rcounts)
    for dst in range(Pn):
        for src in range(Pn):
            n = counts[src, dst]
            assert rcounts[dst, src] == n, (dst, src, rcounts[dst])
            for s in range(cap):
                expect = (src * 10000 + dst * 100 + s) if s < n else 0.0
                np.testing.assert_allclose(
                    recv[dst, src, s], expect,
                    err_msg=f"dst={dst} src={src} slot={s}")


def test_ragged_alltoall_overflow_truncates_cleanly():
    """Counts EXCEEDING ``capacity`` (ISSUE 19 satellite): the sender
    ships only the first ``capacity`` rows of an overflowing block,
    recv_counts clamp to ``capacity`` (never point past the drop), and
    the overflow must not corrupt adjacent (src, dst) slots — every
    non-overflowing block still arrives byte-exact, padding stays zero."""
    import functools

    from jax import shard_map

    from horovod_tpu.ops.jax_ops import ragged_alltoall

    Pn, D, cap = 8, 4, 2
    mesh = Mesh(np.asarray(jax.devices()[:Pn]), ("x",))
    # Counts 0..4 against cap=2: pairs with (i + 2j) % 5 > 2 overflow.
    counts = np.array([[(i + 2 * j) % 5 for j in range(Pn)]
                      for i in range(Pn)], np.int32)
    assert (counts > cap).any() and (counts <= cap).any()

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(),
                       out_specs=(P("x", None, None, None), P("x", None)),
                       check_vma=False)
    def go():
        i = jax.lax.axis_index("x")
        my_counts = jnp.asarray(counts)[i]                       # [P]
        starts = jnp.cumsum(my_counts) - my_counts
        T = int(counts.sum(1).max())
        row = jnp.arange(T, dtype=jnp.int32)
        dst = jnp.sum((row[:, None] >= (starts + my_counts)[None, :])
                      .astype(jnp.int32), axis=1)
        slot = row - starts[dst]
        x = (i * 10000 + dst * 100 + slot).astype(jnp.float32)[:, None] \
            * jnp.ones((1, D), jnp.float32)
        recv, rcounts = ragged_alltoall(x, my_counts, "x", cap)
        return recv[None], rcounts[None]

    recv, rcounts = go()
    recv, rcounts = np.asarray(recv), np.asarray(rcounts)
    for dst in range(Pn):
        for src in range(Pn):
            n = min(int(counts[src, dst]), cap)
            # clamp contract: counts never exceed the slots that exist
            assert rcounts[dst, src] == n, (dst, src, rcounts[dst])
            for s in range(cap):
                expect = (src * 10000 + dst * 100 + s) if s < n else 0.0
                np.testing.assert_allclose(
                    recv[dst, src, s], expect,
                    err_msg=f"dst={dst} src={src} slot={s}")


def _ragged_moe_fn(mesh, axis, **kw):
    """Jitted sharded ragged-MoE layer taking (x, logits, w_in, w_out) as
    traced arguments — usable both for forward parity and for
    differentiating w.r.t. the weights."""
    import functools

    from jax import shard_map

    from horovod_tpu.parallel import moe_dispatch_combine_ragged

    espec = P(axis, None, None)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), espec, espec),
        out_specs=P(axis, None), check_vma=False)
    def fn(x, logits, w_in_l, w_out_l):
        def expert_fn(buf):
            h = jnp.einsum("end,edf->enf", buf.astype(jnp.float32),
                           w_in_l.astype(jnp.float32))
            h = jax.nn.gelu(h)
            return jnp.einsum("enf,efd->end", h,
                              w_out_l.astype(jnp.float32)).astype(buf.dtype)

        out, _ = moe_dispatch_combine_ragged(x, logits, expert_fn, axis,
                                             **kw)
        return out

    return fn


def _ragged_moe_layer(mesh, axis, w_in, w_out, **kw):
    fn = _ragged_moe_fn(mesh, axis, **kw)
    return lambda x, logits: fn(x, logits, w_in, w_out)


def test_make_moe_layer_ragged_flag_matches_dense():
    """make_moe_layer(ragged=True) — the bench's entry point to the
    alltoallv wire format — agrees with the dense-slot layer when
    capacity is generous (same routing, same experts, different wire)."""
    rng = np.random.default_rng(13)
    E, D, F, T = 8, 16, 32, 64
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("expert",))
    w_in = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((8 * T, D)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((8 * T, E)), jnp.float32)

    dense = make_moe_layer(mesh, "expert", w_in, w_out,
                           capacity_factor=float(E))
    ragged = make_moe_layer(mesh, "expert", w_in, w_out,
                            capacity_factor=float(E), ragged=True)
    np.testing.assert_allclose(np.asarray(ragged(x, logits)),
                               np.asarray(dense(x, logits)),
                               rtol=2e-3, atol=2e-3)


def test_moe_ragged_matches_dense():
    """Ragged (wire-following) dispatch == dense one-hot routing when
    capacities are lossless — including under IMBALANCED routing."""
    rng = np.random.default_rng(11)
    E, D, F, T = 8, 16, 32, 64
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("expert",))
    w_in = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((8 * T, D)), jnp.float32)
    # skewed router: expert 0 drawn ~6x more often than the rest
    logits_np = rng.standard_normal((8 * T, E)).astype(np.float32)
    logits_np[:, 0] += 1.5
    logits = jnp.asarray(logits_np)

    layer = _ragged_moe_layer(mesh, "expert", w_in, w_out,
                              peer_capacity=T, expert_capacity=8 * T)
    out = layer(x, logits)

    probs = jax.nn.softmax(np.asarray(logits, np.float32), axis=-1)
    eidx = np.argmax(probs, -1)
    gate = probs[np.arange(len(eidx)), eidx]
    h = np.einsum("td,edf->tef", np.asarray(x), np.asarray(w_in))
    h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
    y = np.einsum("tef,efd->ted", h, np.asarray(w_out))
    ref = y[np.arange(len(eidx)), eidx] * gate[:, None]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_moe_ragged_gradients_match_dense():
    """Training flows through the ragged dispatch: grads of the sharded
    ragged MoE layer w.r.t. x and the expert weights == grads of the
    dense single-device reference (lossless capacities)."""
    rng = np.random.default_rng(13)
    E, D, F, T = 8, 8, 16, 32
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("expert",))
    w_in = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((8 * T, D)), jnp.float32)
    logits_np = rng.standard_normal((8 * T, E)).astype(np.float32)
    logits_np[:, 0] += 1.0  # imbalanced routing
    logits = jnp.asarray(logits_np)

    ragged = _ragged_moe_fn(mesh, "expert", peer_capacity=T,
                            expert_capacity=8 * T)

    def dense(x, logits, w_in, w_out):
        probs = jax.nn.softmax(logits, axis=-1)
        gate = jnp.max(probs, axis=-1)
        eidx = jnp.argmax(probs, axis=-1)
        h = jnp.einsum("td,edf->tef", x, w_in)
        h = jax.nn.gelu(h)
        y = jnp.einsum("tef,efd->ted", h, w_out)
        sel = jnp.take_along_axis(
            y, eidx[:, None, None].repeat(D, axis=2), axis=1)[:, 0]
        return sel * gate[:, None]

    w = jnp.asarray(rng.standard_normal((8 * T, D)), jnp.float32)

    def loss_ragged(x, w_in, w_out):
        return jnp.sum(ragged(x, logits, w_in, w_out) * w)

    def loss_dense(x, w_in, w_out):
        return jnp.sum(dense(x, logits, w_in, w_out) * w)

    gr = jax.grad(loss_ragged, (0, 1, 2))(x, w_in, w_out)
    gd = jax.grad(loss_dense, (0, 1, 2))(x, w_in, w_out)
    for a, b, n in zip(gr, gd, ("x", "w_in", "w_out")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{n} mismatch")


def test_moe_ragged_drops_overflow():
    """peer_capacity=1 with every token routed to shard 0's expert:
    exactly one token per source shard survives; dropped outputs are 0."""
    E, D, T = 8, 4, 16
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("expert",))
    eye = jnp.zeros((E, D, D), jnp.float32) + jnp.eye(D)
    x = jnp.ones((8 * T, D), jnp.float32)
    logits = jnp.zeros((8 * T, E), jnp.float32).at[:, 0].set(10.0)
    layer = _ragged_moe_layer(mesh, "expert", eye, eye,
                              peer_capacity=1, expert_capacity=16)
    out = np.asarray(layer(x, logits))
    nonzero_rows = (np.abs(out).sum(-1) > 1e-6).sum()
    assert nonzero_rows == 8, nonzero_rows


def test_ring_attention_gradients(seq_mesh):
    """Training must differentiate through the ring (scan + ppermute):
    grads of sharded ring attention == grads of the dense reference."""
    q, k, v = _qkv(B=2, S=16, H=2, D=4, seed=9)
    fn = make_ring_attention(seq_mesh, axis="seq", causal=True,
                             batch_axis="data")

    def loss_ring(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def loss_ref(q, k, v):
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        S = s.shape[-1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        return jnp.sum(out ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_striped_ring_attention_matches_reference(seq_mesh, causal):
    """layout='striped' (zig-zag): equal causal work per device; results
    must be identical to the dense reference on contiguous sequences
    (stripe/unstripe happen inside the wrapper)."""
    q, k, v = _qkv(seed=5)
    fn = make_ring_attention(seq_mesh, axis="seq", causal=causal,
                             batch_axis="data", layout="striped")
    out = fn(q, k, v)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_striped_ring_attention_gradients(seq_mesh):
    """Gradients must flow through stripe -> ring -> unstripe identically
    to the contiguous path."""
    q, k, v = _qkv(B=2, S=32, H=2, D=8, seed=9)
    contig = make_ring_attention(seq_mesh, axis="seq", causal=True,
                                 batch_axis="data")
    striped = make_ring_attention(seq_mesh, axis="seq", causal=True,
                                  batch_axis="data", layout="striped")

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) ** 2)

    g_c = jax.grad(loss(contig), argnums=(0, 1, 2))(q, k, v)
    g_s = jax.grad(loss(striped), argnums=(0, 1, 2))(q, k, v)
    for gc, gs in zip(g_c, g_s):
        np.testing.assert_allclose(np.asarray(gc), np.asarray(gs),
                                   rtol=2e-4, atol=2e-4)


def test_stripe_unstripe_roundtrip():
    from horovod_tpu.parallel import (
        stripe_sequence,
        unstripe_sequence,
    )

    x = jnp.arange(2 * 12 * 3, dtype=jnp.float32).reshape(2, 12, 3)
    y = stripe_sequence(x, 4)
    # shard 0 of 4 (rows 0:3 of striped order) holds positions {0, 4, 8}
    np.testing.assert_array_equal(np.asarray(y[:, :3]),
                                  np.asarray(x[:, [0, 4, 8]]))
    np.testing.assert_array_equal(np.asarray(unstripe_sequence(y, 4)),
                                  np.asarray(x))
