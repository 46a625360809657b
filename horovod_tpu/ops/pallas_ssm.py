"""The state-space layers' recurrence as Pallas TPU kernels: a decode step's
state update and read-out in ONE pass over the state, in place
(``ssm_decode_update``, below), and a window's chunked form in one kernel a
layer (``ssm_chunk_scan``, further down, with its own notes).

``transformer._ssd_step`` is the definition: per slot and head, ``S = keep S
+ dx (x) B`` and ``y = sum_n S C`` over a state ``S [P, N]`` in float32, with
``keep = exp(step rate)``, ``dx = step x`` and a group's heads sharing ``B``
and ``C``. XLA compiles that to two fusions over the state (the update: one
read, one write) and a third that reads the new state again for ``y``: three
passes over 0.54 GB a layer at 128 slots of ``[128, 64, 128]`` (PERF.md, PR
43). Here a grid step loads a block of a slot's heads once, updates it, takes
``y`` from the registers it just filled and stores the block to the rows it
came from: the layer's state array ``[state_rows, H, P, N]`` is addressed
where it lies (batch row ``b`` is row ``b + 1``; row 0, the trash row, is in
no block) and aliased in and out, so nothing of the state is sliced or copied.

The same formula on the same operand values as ``_ssd_step``, all float32;
only the order of the sum over ``N`` differs. A slot that ``begins`` its
sequence (a flag a slot, scalar-prefetched) enters on zeros whatever its rows
hold. A dead slot (``step`` 0: ``keep`` 1, ``dx`` 0) comes out bit for bit.

Layout. A state row ``S[h, p, :]`` is ``N`` lanes; eight consecutive ``p``
fill a float32 register ``[8, N]``. A group's ``hpg = H / G`` heads times
``P / 8`` such registers are its ``M`` registers (128 at the shapes served:
16 heads of 64). What the update needs a register is one ``dx`` a sublane,
and what the read-out gives a register is one sum a sublane: both travel
PACKED, a group's values as one ``[8, M]`` tile with register ``i = (p // 8)
hpg + h`` in lane ``i`` and ``p % 8`` on the sublanes (:func:`_pack`), so
they cost ``1 / N`` of the state's bytes and no padding; ``keep`` is a scalar
a head (scalar-prefetched beside the flags).

What bounds the kernel is the copies in and out (it streams 643 GB/s on a
v5e, where a kernel that only copies the blocks reads 617 to 635; PERF.md, PR
43) as long as the lanes are crossed at most once a register: a lane
broadcast AND a lane rotation a register took 2.4 times the copies' time, and
a lane sum a register more. So ``dx`` leaves its lane by a broadcast, and the
sums over the lanes are the matrix unit's: a group's ``M`` registers of ``S C``
times a
matrix of ones (float32 operands at the highest precision: every product is
a part of ``S C`` times one, exact, and the parts add up in float32), which
leaves each row's sum in every lane; lane ``i`` of register ``i`` is kept.

``interpret=True`` runs the same kernel on the CPU (tests/test_pallas_ssm.py).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The instruction name in a compiled program and in the chip's trace
# (docs/observability.md; tests/test_tpu_compile.py counts one a layer).
KERNEL_NAME = "ssm_decode_update"

_SUBLANES = 8          # rows of a float32 register
_LANES = 128

# Groups a grid step takes: every head of a slot (8 groups of 16 heads of
# [64, 128] float32 = 4 MB in and 4 MB out a step). Read on a v5e (PERF.md,
# PR 43), five layers of 128 slots: 1 / 2 / 4 / 8 groups a step take 9.46 /
# 8.59 / 8.34 / 8.35 ms (XLA's three passes 12.12).
_GROUPS_BLOCK = 8


def _packs(hpg, rows):
    """PACKS a group is taken as: 1 where its ``hpg * rows`` registers are the
    128 lanes that hold their packed values, else how many times 128 they are
    (each pack whole heads: 128 / rows of them), or 0 where they do not
    divide so."""
    m = hpg * rows
    if m == _LANES:
        return 1
    if m % _LANES or _LANES % rows:
        return 0
    return m // _LANES


def supported(a):
    """Whether the kernel tiles on a TPU for the mixer ``a``: a state row is
    one register's lanes, a head whole registers, and a group's registers
    as many as the lanes that hold their packed values, or a whole multiple
    of them (the shapes compiled and measured: 16 heads of 64 a group, and 64
    heads of 64 in one group taken as four packs of 16 that share ``B`` and
    ``C``; interpret mode takes any)."""
    return (a.state_size == _LANES and a.head_dim % _SUBLANES == 0
            and a.n_heads % a.n_groups == 0
            and _packs(a.n_heads // a.n_groups,
                       a.head_dim // _SUBLANES) > 0)


def _pack(v, hpg):
    """``[B, H, P]`` -> ``[B, G, 8, M]``: register ``i = (p // 8) hpg + h``
    of a group in lane ``i``, ``p % 8`` on the sublanes."""
    B, H, P = v.shape
    v = v.reshape(B, H // hpg, hpg, P // _SUBLANES, _SUBLANES)
    return v.transpose(0, 1, 4, 3, 2).reshape(B, H // hpg, _SUBLANES, -1)


def _unpack(v, hpg):
    """:func:`_pack` undone: ``[B, G, 8, M]`` -> ``[B, H, P]``."""
    B, G, _, m = v.shape
    v = v.reshape(B, G, _SUBLANES, m // hpg, hpg)
    return v.transpose(0, 1, 4, 3, 2).reshape(B, G * hpg, -1)


def _kernel(begins_ref, keep_ref, dx_ref, b_ref, c_ref, s_ref, y_ref, o_ref,
            sc_s, *, groups, hpg, rows):
    n = s_ref.shape[-1]
    m = hpg * rows
    shape = (_SUBLANES, n)
    slot = pl.program_id(0)
    head0 = (slot * pl.num_programs(1) + pl.program_id(1)) * groups * hpg
    # A slot that begins its sequence enters on zeros, whatever its rows hold.
    fresh = jnp.full(shape, begins_ref[slot], jnp.int32) != 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, m), 1)
    ones = jnp.ones((n, m), jnp.float32)

    def group(g, _):
        b = jnp.broadcast_to(b_ref[0, g], shape)
        c = jnp.broadcast_to(c_ref[0, g], shape)
        dx = dx_ref[0, g]                                   # [8, M]
        for h in range(hpg):
            keep = keep_ref[head0 + g * hpg + h]
            for j in range(rows):
                i = j * hpg + h
                at = (0, g * hpg + h, pl.ds(j * _SUBLANES, _SUBLANES))
                s = jnp.where(fresh, 0.0, s_ref[at])
                s = s * keep + dx[:, i:i + 1] * b
                o_ref[at] = s
                sc_s[pl.ds(i * _SUBLANES, _SUBLANES), :] = s * c
        sums = jnp.dot(sc_s[...], ones, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)  # [8 M, M]
        y = jnp.zeros((_SUBLANES, m), jnp.float32)
        for i in range(m):
            y = jnp.where(lane == i,
                          sums[i * _SUBLANES:(i + 1) * _SUBLANES], y)
        y_ref[0, g] = y

    jax.lax.fori_loop(0, groups, group, None)


@functools.partial(jax.jit, static_argnames=("groups_block", "interpret"))
def ssm_decode_update(x, step, rate, b_in, c_out, state, begins, *,
                      groups_block=None, interpret=False):
    """``transformer._ssd_step`` over the layer's own state array: ``x [B,
    1, H, P]``, ``step [B, 1, H]``, ``rate [H]``, ``b_in, c_out [B, 1, G,
    N]`` as there; ``state [rows, H, P, N]`` float32 with batch row ``b`` in
    row ``b + 1`` (``rows >= B + 1``); ``begins [B]`` marks the slots that
    enter on zeros -> (``y [B, 1, H, P]`` float32, the state array with rows
    ``1 .. B`` updated, the others as they were).

    The state goes in and out aliased: under a ``jit`` that donates it the
    update is in place. Jitted so that a program calling it once a layer
    traces and lowers the kernel once."""
    f32 = jnp.float32
    B, _, H, P = x.shape
    G, N = b_in.shape[2:]
    if state.shape[1:] != (H, P, N) or state.shape[0] <= B \
            or state.dtype != f32:
        raise ValueError(f"state {state.shape} {state.dtype} does not hold "
                         f"{B} slots of float32 [{H}, {P}, {N}] behind a "
                         f"trash row")
    hpg, rows = H // G, P // _SUBLANES
    # A group wider than the 128 lanes of its packed values is taken as that
    # many groups of consecutive heads, each handed the group's B and C.
    packs = max(_packs(hpg, rows), 1)
    if packs > 1:
        b_in, c_out = (jnp.repeat(v, packs, axis=2) for v in (b_in, c_out))
        G, hpg = G * packs, hpg // packs
    m = hpg * rows
    gb = min(int(groups_block or _GROUPS_BLOCK), G)
    while G % gb:
        gb -= 1
    # The operand values of _ssd_step, by its own expressions.
    step = step[:, 0].astype(f32)                                   # [B, H]
    dx = x[:, 0].astype(f32) * step[..., None]                      # [B, H, P]
    keep = jnp.exp(step * rate.astype(f32))
    lanes = lambda v: v[:, 0].astype(f32).reshape(B, G, 1, N)  # noqa: E731
    block = gb * hpg * P * N * 4

    def at(shape, row=0):
        return pl.BlockSpec(shape, lambda b, g, *_: (b + row, g, 0, 0),
                            memory_space=pltpu.VMEM)

    state_spec = at((1, gb * hpg, P, N), row=1)
    y, state = pl.pallas_call(
        functools.partial(_kernel, groups=gb, hpg=hpg, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G // gb),
            in_specs=[at((1, gb, _SUBLANES, m)), at((1, gb, 1, N)),
                      at((1, gb, 1, N)), state_spec],
            out_specs=[at((1, gb, _SUBLANES, m)), state_spec],
            scratch_shapes=[pltpu.VMEM((m * _SUBLANES, N), f32)]),
        out_shape=[jax.ShapeDtypeStruct((B, G, _SUBLANES, m), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={5: 1},      # the state, behind the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * block + (24 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=6 * B * H * P * N, transcendentals=0,
            bytes_accessed=2 * B * H * P * N * 4),
        name=KERNEL_NAME,
        interpret=interpret,
    )(begins.astype(jnp.int32), keep.reshape(-1), _pack(dx, hpg),
      lanes(b_in), lanes(c_out), state)
    return _unpack(y, hpg).reshape(B, 1, H, P), state


# ---- a window of more than one position: the chunked form in one kernel ----
#
# ``transformer._ssd_blocks`` is the definition (Mamba-2's state-space
# duality): inside a block position q takes ``exp(cs_q - cs_s) (C_q . B_s)
# step_s x_s`` of every s <= q, with ``cs`` the running sum of ``step rate``;
# the state entering gives it ``exp(cs_q) (C_q . S)``; and the block leaves
# ``S exp(cs_end) + sum_s exp(cs_end - cs_s) step_s x_s (x) B_s``. XLA computes
# that as a dozen fusions over ``[blocks, heads, block, block]`` float32
# tensors in HBM (33.5 MB each a layer at 64 heads and a block of 256) and
# four einsums at ``HIGHEST``: 4.9 ms of a 28.3 ms chunk program at 36 layers
# of 512 positions (PERF.md, PR 58). ``ssm_chunk_scan`` is one kernel a layer:
#
# - A grid over (sequence, pack of heads of one group, block of ``a.block``
#   positions), the blocks in order. The pack's state ``[heads P, N]`` enters
#   at the window's first block, is kept in VMEM TRANSPOSED (``(h, p)`` on the
#   lanes: both products with it are then plain ones) and is written out once,
#   at the last. (Taking the layer's whole array aliased in and out, the row
#   scalar-prefetched as the decode kernel has it, spared the program two
#   fusions over 2 MB a layer and read SLOWER: a chunk 26.6 ms for 25.3.)
# - A block is walked in tiles of 128 positions, each tile the chunked form's
#   block (which changes no value: inside a tile a decay is the exponential of
#   a difference, between tiles it goes through the state). Per tile: the
#   running sum as a product with a triangle of ones and its rows by a
#   product with the identity (exact: every sum is parts of one value times
#   one); ``C B^T`` once for the pack; per head the decay tile under the
#   causal mask times ``C B^T`` times ``step_s``, against ``x``; the
#   state's read-out for all heads in one product; the tile's effect on the
#   state in one more.
# - Operands stay where their lanes lie: the heads one lane tile holds (two of
#   64 channels) are taken together, a head's column of per-position weights
#   broadcast over the tile and kept on the head's own lanes. (Head by head on
#   64-lane slices the same kernel took 3.79 ms for this one's 3.40.)
# - Precision is ``_ssd_blocks``': state, decay, step and every sum float32,
#   every product as accurate as ``HIGHEST`` (:func:`_dot`).
#
# Read on a v5e (PERF.md, PR 58; 36 layers of 512 positions of 64 heads of 64,
# each call on its own operands, chained through the state, with the
# harness's own 1.0 ms): ``_ssd_blocks`` 4.8 to 5.0 ms, the kernel 3.40; with
# the in-block product at ``HIGHEST`` over two float32 operands (six passes)
# 4.25; packs of 8 / 16 / 32 heads 3.76 / 3.40 / 3.37; a tile of 256 positions
# 5.6 for 4.5 (an earlier form). What a grid step waits for, by knocking
# pieces out: a kernel that only copies ``x`` to ``y`` and carries the state
# 1.70 (the floor: 16 MB a layer); the rest of the skeleton 0.26; the
# products with the state, their splits into parts and the running sums 0.82;
# the heads' decay tiles and in-block products 0.62.

# The instruction name of the chunk program's kernel.
CHUNK_KERNEL_NAME = "ssm_chunk_scan"

# Positions the kernel multiplies as one tile: a lane tile.
_TILE = 128
# Heads a grid step takes (whole heads of one group: they share B and C).
_HEADS_PACK = 16

_HI = jax.lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _heads_pack(hpg, most=None):
    """Heads of a group that a grid step takes: the most that divide it, up
    to ``most``."""
    hb = min(int(most or _HEADS_PACK), hpg)
    while hpg % hb:
        hb -= 1
    return hb


def chunk_supported(a, q_len):
    """Whether :func:`ssm_chunk_scan` tiles on a TPU for a window of ``q_len``
    positions of the mixer ``a``, from shapes alone: what :func:`supported`
    asks (a state row one register's lanes, whole registers a head, heads
    that pack), a pack's channels whole lane tiles, blocks of whole tiles,
    and a window of whole blocks. (Interpret mode takes any.)"""
    hb = _heads_pack(a.n_heads // a.n_groups)
    return (supported(a) and (hb * a.head_dim) % _LANES == 0
            and a.block % _TILE == 0 and q_len % a.block == 0)


def _parts(v):
    """A float32 value as the three bfloat16 values that sum to it exactly
    (8 + 8 + 8 bits of its 24), smallest first; a bfloat16 value is its one
    part."""
    if v.dtype == jnp.bfloat16:
        return [v]
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = v.astype(bf16)
    rest = v - hi.astype(f32)
    mid = rest.astype(bf16)
    return [(rest - mid.astype(f32)).astype(bf16), mid, hi]


def _dot(x, y, dims=_NN):
    """``x . y`` as accurate as ``Precision.HIGHEST`` makes a float32
    product, in float32: two float32 operands at ``HIGHEST`` (six passes of
    the matrix unit); where an operand IS a bfloat16 value its middle and low
    parts are zero and the passes that multiply them are left out, so a
    float32 operand against it is its three parts, one pass each, and two
    bfloat16 operands are one pass. The same products summed in float32.
    (The three parts behind one another in ONE product, so that the other
    operand is loaded once, read 7 % slower on a v5e: PERF.md, PR 58.)"""
    if x.dtype == y.dtype == jnp.float32:
        return jax.lax.dot_general(x, y, (dims, ((), ())), precision=_HI,
                                   preferred_element_type=jnp.float32)
    total = None
    for a in _parts(x):
        for b in _parts(y):
            part = jax.lax.dot_general(a, b, (dims, ((), ())),
                                       preferred_element_type=jnp.float32)
            total = part if total is None else total + part
    return total


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _chunk_kernel(x_ref, step_ref, rate_ref, b_ref, c_ref, s_ref, y_ref,
                  out_ref, sc_s, sc_dx, *, heads, tile):
    f32, bf16 = jnp.float32, jnp.bfloat16
    hb, T = heads, tile
    Q, W = x_ref.shape[1:]
    P, N = s_ref.shape[2:]
    block, last = pl.program_id(2), pl.num_programs(2) - 1

    # The pack's state, carried across the window's blocks TRANSPOSED: a
    # state row ``S[h, p, :]`` down the sublanes, ``(h, p)`` on the lanes, so
    # that both products with it are plain ones.
    @pl.when(block == 0)
    def _():
        sc_s[...] = s_ref[0].reshape(W, N).T

    # Matrices of ones and zeros, bfloat16 values exactly: the running sum
    # (lower triangle), the transposition (identity), a head's value to its
    # P lanes.
    lower = _iota((T, T), 0) >= _iota((T, T), 1)
    ones_below = lower.astype(bf16)
    identity = (_iota((T, T), 0) == _iota((T, T), 1)).astype(bf16)
    to_lanes = (_iota((hb, W), 1) // P == _iota((hb, W), 0)).astype(bf16)
    rate = rate_ref[0]                                          # [1, hb]
    # The heads a lane tile holds are taken together (two of 64 channels),
    # every operand where its lanes lie: a head's column of weights is
    # broadcast over the tile and kept on the head's own lanes.
    per = min(hb, max(1, _LANES // P))
    slab = per * P
    head_of = _iota((T, slab), 1) // P

    def spread(v, first):          # [T, hb] -> a column over each head's lanes
        out = v[:, first:first + 1]
        for k in range(1, per):
            out = jnp.where(head_of == k, v[:, first + k:first + k + 1], out)
        return out

    for t in range(Q // T):
        at = pl.ds(t * T, T)
        step = step_ref[0, 0, at, :]                            # [T, hb]
        cs = _dot(ones_below, step * rate)                      # [T, hb]
        end = cs[T - 1:]                                        # [1, hb]
        # A position's weight in the state at the tile's end, and what the
        # state entering is worth at a position.
        grows, carries = step * jnp.exp(end - cs), jnp.exp(cs)
        # The running sum and the step as rows, [2 hb, T]: transposed
        # exactly (every sum is a part of one value times one).
        across = _dot(jnp.concatenate([cs, step], 1), identity, _TN)
        x, b, c = x_ref[0, at, :], b_ref[0, at, :], c_ref[0, at, :]
        state = sc_s[...]                                       # [N, W]
        carried = _dot(c, state)                                # [T, W]
        cb = _dot(c, b, _NT)                            # [T, T], a group's
        for first in range(0, hb, per):
            lanes = slice(first * P, first * P + slab)
            xs = x[:, lanes]                                    # [T, slab]
            y = carried[:, lanes] * spread(carries, first)
            for k in range(per):
                h = first + k
                # q takes exp(cs_q - cs_s) (C_q . B_s) step_s of s <= q.
                seg = cs[:, h:h + 1] - across[h:h + 1, :]
                mix = cb * jnp.exp(jnp.where(lower, seg, -jnp.inf))
                mix = mix * across[hb + h:hb + h + 1, :]
                y = y + _dot(mix, xs if per == 1
                             else jnp.where(head_of == k, xs, 0))
            y_ref[0, at, lanes] = y
            sc_dx[:, lanes] = xs.astype(f32) * spread(grows, first)
        # The tile's own contribution to the state at its end, and its decay.
        grown = _dot(b.T, sc_dx[...])                           # [N, W]
        kept = jnp.exp(_dot(jnp.broadcast_to(end, (_SUBLANES, hb)),
                            to_lanes)[:1])                      # [1, W]
        sc_s[...] = state * kept + grown

    @pl.when(block == last)
    def _():
        out_ref[0] = sc_s[...].T.reshape(hb, P, N)


@functools.partial(jax.jit, static_argnames=("block", "heads_pack", "tile",
                                             "interpret"))
def ssm_chunk_scan(x, step, rate, b_in, c_out, state, *, block,
                   heads_pack=None, tile=None, interpret=False):
    """``transformer._ssd_blocks`` for a window of more than one position:
    ``x [B, S, H, P]``, ``step [B, S, H]``, ``rate [H]``, ``b_in, c_out [B,
    S, G, N]``, ``state [B, H, P, N]`` entering, ``block`` the mixer's ->
    (``y [B, S, H, P]`` float32, the state leaving, float32).

    Jitted so that a program calling it once a layer traces and lowers the
    kernel once."""
    f32 = jnp.float32
    B, S, H, P = x.shape
    G, N = b_in.shape[2:]
    if state.shape != (B, H, P, N):
        raise ValueError(f"state {state.shape} is not [{B}, {H}, {P}, {N}]")
    hpg = H // G
    hb = _heads_pack(hpg, heads_pack)
    packs = H // hb
    Q = min(int(block), -(-S // _SUBLANES) * _SUBLANES)
    nb = -(-S // Q)
    T = int(tile or (_TILE if Q % _TILE == 0 else Q))
    if Q % T:
        raise ValueError(f"a block of {Q} positions is no whole tiles of {T}")
    behind = [(0, 0), (0, nb * Q - S), (0, 0)]        # dead positions

    def flat(v):                      # [B, S, .., ..] -> [B, nb Q, ..]
        return jnp.pad(v.reshape(B, S, -1), behind)

    steps = jnp.pad(step.astype(f32), behind).reshape(
        B, nb * Q, packs, hb).transpose(0, 2, 1, 3)        # [B, packs, S, hb]
    seq = pl.BlockSpec((1, Q, hb * P), lambda b, p, n: (b, n, p),
                       memory_space=pltpu.VMEM)
    maps = pl.BlockSpec((1, Q, N), lambda b, p, n: (b, n, p * hb // hpg),
                        memory_space=pltpu.VMEM)
    rows = pl.BlockSpec((1, hb, P, N), lambda b, p, n: (b, p, 0, 0),
                        memory_space=pltpu.VMEM)
    tiles = B * packs * nb * (Q // T)
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, tile=T),
        grid=(B, packs, nb),
        in_specs=[seq,
                  pl.BlockSpec((1, 1, Q, hb), lambda b, p, n: (b, p, n, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, 1, hb), lambda b, p, n: (p, 0, 0),
                               memory_space=pltpu.VMEM),
                  maps, maps, rows],
        out_specs=[seq, rows],
        out_shape=[jax.ShapeDtypeStruct((B, nb * Q, H * P), f32),
                   jax.ShapeDtypeStruct((B, H, P, N), f32)],
        scratch_shapes=[pltpu.VMEM((N, hb * P), f32),
                        pltpu.VMEM((T, hb * P), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * tiles * T * hb * P * (T + 2 * N) + 2 * tiles * T * T * N,
            transcendentals=tiles * T * hb * (T + 2 * P),
            bytes_accessed=B * nb * Q * H * P * (x.dtype.itemsize + 4)
            + 2 * B * H * P * N * 4),
        name=CHUNK_KERNEL_NAME,
        interpret=interpret,
    )(flat(x), steps, rate.astype(f32).reshape(packs, 1, hb),
      flat(b_in), flat(c_out), state.astype(f32))
    return y[:, :S].reshape(B, S, H, P), state
