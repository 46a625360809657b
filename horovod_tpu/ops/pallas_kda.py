"""A window's gated delta-rule recurrence in its chunked form as ONE kernel
(Pallas TPU): ``kda_chunk_scan``.

``transformer._delta_blocks`` is the definition: per head, blocks of 64
positions; the decayed key-key and query-key products of a block, the inverse
of its unit triangular matrix, ``W`` and ``U``, and a chain over the blocks'
states. XLA computes that as a dozen float32 fusions over ``[32, 64, 4, 16,
16]``-shaped tiles with every intermediate through HBM, and copies the
operands between the layouts of the fusions: 40 ms + 4.9 ms of a 96 ms chunk
program at three layers of 2,048 positions and 64 heads of 128 (PERF.md, PR
48). Here a grid step is one block of positions of a group of heads: the four
``[64, 128]`` operands of a head are read where they lie (``[B, S, H * d]``
seen as ``[S, H * d]``: a head's 128 channels are 128 lanes, no
transposition), everything between them and the block's outputs stays in
VMEM, the head's ``[d, d]`` state is carried across the blocks in the output's
own VMEM block (it enters once and leaves once a head group), and ``o`` is
written in the ``[B, S, H * d]`` layout the gated norm reads.

The same mathematics at the same precision, float32 throughout and every
matrix product at ``Precision.HIGHEST``; only the order of float32 sums
differs from ``_delta_blocks``:

- the running sum ``G`` of a block's log decays is a product with a
  triangular matrix of ones (every product is a part of ``g`` times one);
- ``exp(-G_j)`` alone is never formed: inside a sub-block of 16 positions the
  difference ``G_i - G_j`` is taken first and the sum over the channels is a
  sum over the lanes, one column ``j`` (of every sub-block) at a time; across
  sub-blocks the decay is split at the later sub-block's first position,
  both exponents at most 0, and the sum is a matrix product;
- ``(I + A)^-1`` is exact substitution (:func:`_unit_lower_inverse`): the
  diagonal blocks of 16 rows a column at a time, then by halves as
  ``transformer._unit_lower_inverse``. Never the finite product ``(I - A)(I +
  A^2)(I + A^4)..``;
- ``beta`` scales the ROWS of ``[K exp G | V]`` where ``_delta_blocks`` scales
  the columns of the inverse: ``W`` and ``U`` are then one product over ``[64,
  2 d]``;
- ``[W ; q exp G]`` against the state is one product, then ``u = U - W S``,
  ``o``, and the state leaving.

A window that is no multiple of 64 is padded by the wrapper with dead
positions (``g`` and ``beta`` 0) behind the live ones, which change neither
the state nor a live output; a dead window leaves the state bit for bit.

The heads of a grid step are STACKED: their blocks' rows one under the other,
``[n 64, d]``, and the ``[64, 64]`` matrices of a block as the diagonal blocks
of one ``[n 64, n 64]`` matrix (zeros elsewhere, exactly), so that the running
sum, the inverse, ``W | U`` and ``qk u`` are one product each for all of them;
only the products with a head's own state are a head's. What the kernel waits
for is a small product's latency in a dependent chain and the inverse's 16
dependent columns, not the matrix unit's rate (read on a v5e, PERF.md, PR 49:
a float32 ``[64, 64] x [64, 64]`` at ``HIGHEST`` takes 0.051 us beside others
and 0.139 us behind another; ``[128, 128] x [128, 128]`` 0.129 and 0.181), and
two heads of 64 rows fill the unit's 128 x 128 once where each alone fills a
quarter. Readings at :data:`_HEADS_BLOCK`.

``interpret=True`` runs the same kernel on the CPU (tests/test_pallas_kda.py).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The instruction name in a compiled program and in the chip's trace
# (docs/observability.md; tests/test_tpu_compile.py counts one a layer).
KERNEL_NAME = "kda_chunk_scan"

_LANES = 128
_SUBLANES = 8          # rows of a float32 register
# ``transformer._DELTA_BLOCK`` and ``_DELTA_SUB``: the block of the chunked
# form (``benchmark/flops_linear`` counts the recurrence at it) and the
# sub-block inside which a decay is exponentiated as a difference.
BLOCK = 64
SUB = 16

# Heads a grid step takes, stacked. Read on a v5e (PERF.md, PR 49), one layer
# of 2,048 positions and 64 heads of 128, the kernel alone with the copies of
# its operands into the flat layout (1.2 ms; XLA's ``_delta_blocks`` 13.68
# ms): two heads stacked 5.12 ms, four 6.77 (a ``[256, 256]`` problem is
# three quarters zeros). With the sub-blocks' diagonal columns one sub-block
# at a time (128 small steps for 16 wide ones): two stacked 5.75 ms, two such
# pairs a step 5.69; one head at a time, a step of 1 / 2 / 4 heads one after
# the other, 7.05 / 6.66 / 6.90; and with the diagonal blocks of 16 inverted
# by halves too (six rounds of two products) 7.63 / 7.37 / 7.24.
_HEADS_BLOCK = 2

_HI = jax.lax.Precision.HIGHEST


def supported(a):
    """Whether the kernel tiles on a TPU for the mixer ``a``: a head's
    channels are whole registers' lanes (interpret mode takes any width)."""
    return a.head_dim % _LANES == 0


def _dot(x, y, dims=((1,), (0,))):
    return jax.lax.dot_general(x, y, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _rows(pieces):
    """The pieces one under the other (none of no rows: Mosaic has no empty
    vector)."""
    pieces = [p for p in pieces if p.shape[0]]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, 0)


def _decayed_dots(q, k, G):
    """``transformer._decayed_dots`` of one block of ``n`` stacked heads
    (``q, k, G [n C, d]``): -> (``kk``, ``qk`` ``[n C, n C]``), in a head's
    diagonal block ``P[i, j] = sum_c x[i, c] k[j, c] exp(G[i, c] - G[j, c])``
    for ``j <= i``, and 0 above the diagonal and between heads."""
    rows, d = k.shape
    subs, halves = rows // SUB, SUB // _SUBLANES
    # Against a head's earlier sub-blocks: split at the later sub-block's
    # first position, both exponents at most 0; a product a sub-block.
    across = []
    for at in range(0, rows, SUB):
        first = at // BLOCK * BLOCK        # its head's first row
        if at == first:
            across.append(jnp.zeros((2 * SUB, rows), jnp.float32))
            continue
        Gs, ks, qs = (t[at:at + SUB] for t in (G, k, q))
        ref = Gs[:1]
        y_ref = _rows([jnp.zeros((first, d), jnp.float32),
                       k[first:at] * jnp.exp(ref - G[first:at]),
                       jnp.zeros((rows - at, d), jnp.float32)])
        from_ref = jnp.exp(Gs - ref)
        across.append(_dot(jnp.concatenate([ks * from_ref, qs * from_ref], 0),
                           y_ref, ((1,), (1,))))            # [2 SUB, n C]
    # Inside a sub-block: the difference first, a column ``j`` at a time, of
    # every sub-block at once (``[sub-block, register, 8, lanes]``), over the
    # registers (8 rows) that reach the diagonal or lie below.
    G4, k4, q4 = (t.reshape(subs, halves, _SUBLANES, d) for t in (G, k, q))
    shape = (subs, halves, _SUBLANES, rows)
    at = _iota(shape, 1) * _SUBLANES + _iota(shape, 2)   # place in sub-block
    column = _iota(shape, 0) * SUB - _iota(shape, 3)     # -(lane - its first)
    inside = [jnp.zeros(shape, jnp.float32)] * 2
    for j in range(SUB):
        top, r = divmod(j, _SUBLANES)
        low = at[:, top:, :, :1] >= j
        weight = jnp.exp(jnp.where(
            low, G4[:, top:] - G4[:, top:top + 1, r:r + 1], 0.0))
        y = k4[:, top:top + 1, r:r + 1]
        for n, x in enumerate((k4, q4)):
            col = jnp.where(low, jnp.sum(x[:, top:] * y * weight, -1,
                                         keepdims=True), 0.0)
            if top:
                col = jnp.concatenate([jnp.zeros_like(col)] * top + [col], 1)
            inside[n] = jnp.where(column == -j, col, inside[n])
    return tuple(
        jnp.concatenate([a[half] for a in across], 0)
        + found.reshape(rows, rows)
        for half, found in zip((slice(0, SUB), slice(SUB, None)), inside))


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of ``A [n C, n C]``, strictly lower-triangular blocks
    of ``C`` rows on the diagonal and zeros elsewhere, by exact substitution.
    The diagonal blocks of :data:`SUB` rows a column at a time, all of them at
    once: with ``X (I + A) = I``, column ``j`` of ``X`` is ``e_j - sum_{i > j}
    X[:, i] A[i, j]`` from the last column back, held transposed (``XT [j,
    (block, r)]``) so that ``A[:, j]`` multiplies down the sublanes. Then by
    halves as ``transformer._unit_lower_inverse``: with ``D`` the inverses of
    the diagonal blocks of ``s`` rows (one block-diagonal matrix) and ``L``
    the part of ``A`` that joins two neighbours, ``D - (D L) D`` holds those
    of ``2 s`` rows: the same two products a round, over the whole matrix,
    whose other entries are exact zeros."""
    rows = A.shape[0]
    lane, below = _iota((SUB, rows), 1), _iota((SUB, rows), 0)
    XT = jnp.zeros((SUB, rows), jnp.float32)
    for j in reversed(range(SUB)):
        down = A[:SUB, j:j + 1]
        for at in range(SUB, rows, SUB):
            down = jnp.where(lane // SUB == at // SUB,
                             A[at:at + SUB, at + j:at + j + 1], down)
        column = (lane[:1] % SUB == j) - jnp.sum(down * XT, 0, keepdims=True)
        XT = jnp.where(below == j, column, XT)
    row, col = _iota((rows, rows), 0), _iota((rows, rows), 1)
    DT = jnp.concatenate([jnp.where(lane // SUB == at // SUB, XT, 0.0)
                          for at in range(0, rows, SUB)], 0)
    # Transposed by a product with the identity: every sum is one value
    # times one.
    D = _dot(DT, (row == col).astype(jnp.float32), ((0,), (0,)))
    s = SUB
    while s < BLOCK:
        # The bottom-left quarter of every diagonal block of 2 s rows.
        joins = ((row // (2 * s) == col // (2 * s))
                 & (row // s % 2 == 1) & (col // s % 2 == 0))
        D = D - _dot(_dot(D, jnp.where(joins, A, 0.0)), D)
        s *= 2
    return D


def _block(q, k, v, g, beta, states):
    """One block of ``n`` stacked heads: ``q, k, v, g [n C, d]``, ``beta [n C,
    1]``, the states entering ``n x [d, d]`` (value-major) -> (``o [n C, d]``,
    the states leaving)."""
    rows, d = q.shape
    C = BLOCK
    row, col = _iota((rows, rows), 0), _iota((rows, rows), 1)
    # The running sum, a head's block by itself.
    G = _dot(((row >= col) & (row // C == col // C)).astype(jnp.float32), g)
    kk, qk = _decayed_dots(q, k, G)
    T = _unit_lower_inverse(jnp.where(row > col, kk, 0.0) * beta)
    decayed = jnp.exp(G)
    WU = _dot(T, jnp.concatenate([k * decayed, v], 1) * beta)  # [n C, 2 d]
    reads = q * decayed
    heads = [slice(at, at + C) for at in range(0, rows, C)]
    held = [_dot(jnp.concatenate([WU[mine, :d], reads[mine]], 0), s,
                 ((1,), (1,))) for mine, s in zip(heads, states)]  # [2 C, d]
    u = WU[:, d:] - _rows([h[:C] for h in held])
    o = _rows([h[C:] for h in held]) + _dot(qk, u)
    leaving = []
    for mine, s in zip(heads, states):
        G_end = G[mine][C - 1:]
        leaving.append(s * jnp.exp(G_end) + _dot(
            u[mine], k[mine] * jnp.exp(G_end - G[mine]), ((0,), (0,))))
    return o, leaving


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, o_ref, out_ref, *,
            heads):
    d = s_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[...] = s_ref[...]

    betas = beta_ref[0]                                          # [C, H]
    head = _iota(betas.shape, 1)
    lanes = [slice(h * d, (h + 1) * d) for h in range(heads)]
    # A head's column of ``beta``: one lane of H, by a masked sum.
    beta = _rows([jnp.sum(jnp.where(head == pl.program_id(1) * heads + h,
                                    betas, 0.0), -1, keepdims=True)
                  for h in range(heads)])
    o, leaving = _block(
        *(_rows([ref[0, :, mine] for mine in lanes])
          for ref in (q_ref, k_ref, v_ref, g_ref)),
        beta, [out_ref[0, h] for h in range(heads)])
    for h, mine in enumerate(lanes):
        o_ref[0, :, mine] = o[h * BLOCK:(h + 1) * BLOCK]
        out_ref[0, h] = leaving[h]


@functools.partial(jax.jit, static_argnames=("heads_block", "interpret"))
def kda_chunk_scan(q, k, v, g, beta, state, *, heads_block=None,
                   interpret=False):
    """``transformer._delta_blocks`` at its block of 64 for a window of more
    than one position: ``q, k, v, g [B, S, H, d]``, ``beta [B, S, H]``,
    ``state [B, H, d, d]`` entering (value-major) -> (``o [B, S, H, d]``
    float32, the state leaving, float32).

    Jitted so that a program calling it once a layer traces and lowers the
    kernel once."""
    f32 = jnp.float32
    B, S, H, d = q.shape
    if state.shape != (B, H, d, d):
        raise ValueError(f"state {state.shape} is not [{B}, {H}, {d}, {d}]")
    hb = min(int(heads_block or _HEADS_BLOCK), H)
    while H % hb:
        hb -= 1
    nb = -(-S // BLOCK)
    behind = [(0, 0), (0, nb * BLOCK - S), (0, 0)]    # dead positions

    def flat(t):                    # [B, S, H, d] -> [B, nb C, H d]: no copy
        return jnp.pad(t.astype(f32).reshape(B, S, H * d), behind)

    seq = pl.BlockSpec((1, BLOCK, hb * d), lambda b, h, n: (b, n, h),
                       memory_space=pltpu.VMEM)
    rows = pl.BlockSpec((1, hb, d, d), lambda b, h, n: (b, h, 0, 0),
                        memory_space=pltpu.VMEM)
    steps = B * H * nb
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        grid=(B, H // hb, nb),
        in_specs=[seq, seq, seq, seq,
                  pl.BlockSpec((1, BLOCK, H), lambda b, h, n: (b, n, 0),
                               memory_space=pltpu.VMEM),
                  rows],
        out_specs=[seq, rows],
        out_shape=[jax.ShapeDtypeStruct((B, nb * BLOCK, H * d), f32),
                   jax.ShapeDtypeStruct((B, H, d, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=steps * BLOCK * (5 * BLOCK * d + 6 * d * d),
            transcendentals=steps * BLOCK * d * (SUB + 6),
            bytes_accessed=4 * (5 * B * nb * BLOCK * H * d
                                + 2 * B * H * d * d)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(flat(q), flat(k), flat(v), flat(g),
      jnp.pad(beta.astype(f32), behind), state.astype(f32))
    return o[:, :S].reshape(B, S, H, d), state
