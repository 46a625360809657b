"""A decode step's state update and read-out of a state-space layer in ONE
pass over the state, in place (Pallas TPU).

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
