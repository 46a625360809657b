"""The state-space kernels (``ops/pallas_ssm.py``) in interpret mode on the
CPU, each against the function it stands in for: the decode step's
``ssm_decode_update`` against ``transformer._ssd_step`` and the chunk
program's ``ssm_chunk_scan`` against ``transformer._ssd_blocks``. The same
formula on the same operand values in float32, so they agree to float32
rounding (the kernels sum in another order); what a kernel must leave alone it
leaves bit for bit. That the chip's compiler takes the kernels at the served
shapes, once a layer and with no copy of the state, is
``tests/test_tpu_compile.py``'s; times are the chip's (PERF.md, PR 43 and
PR 58).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.serving import engine

# (slots, heads, head_dim, groups, state_size): the shapes the cell
# `nemotron-serve-reason-over` runs (two of its 128 slots), the tiny model of
# tests/test_nemotron_h.py, and one group of heads that are no power of two.
CELL = (2, 128, 64, 8, 128)
TINY = (3, 8, 8, 2, 16)
ODD = (2, 6, 16, 2, 32)
# `granite-serve-agent-share-over`: 64 heads of 64 in ONE group, which the
# kernel takes as four packs of 16 heads that share B and C.
ONE_GROUP = (2, 64, 64, 1, 128)


def _operands(shape, dtype=jnp.float32, spare_rows=0, seed=0):
    B, H, P, G, N = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, 1, H, P)).astype(dtype)
    step = jax.nn.softplus(jax.random.normal(ks[1], (B, 1, H)))
    rate = -jnp.exp(jax.random.normal(ks[2], (H,)))
    b_in = jax.random.normal(ks[3], (B, 1, G, N)).astype(dtype)
    c_out = jax.random.normal(ks[4], (B, 1, G, N)).astype(dtype)
    state = jax.random.normal(ks[5], (B + 1 + spare_rows, H, P, N))
    return x, step, rate, b_in, c_out, state


def _plain(x, step, rate, b_in, c_out, state, begins):
    """What the engine's plain tier computes: the rows behind the trash row,
    zeroed where the slot begins, through ``_ssd_step``."""
    B = x.shape[0]
    rows = jnp.where(begins[:, None, None, None], 0, state[1:B + 1])
    return tfm._ssd_step(x, step, rate, b_in, c_out, rows)


def _close(got, want, tol=2e-6):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [CELL, TINY, ODD, ONE_GROUP],
                         ids=["cell", "tiny", "odd", "one-group"])
def test_against_the_one_token_update(shape, dtype):
    """``y`` and the state leaving, for operands in the compute dtype the
    mixer hands over (the cell's is bfloat16; the state is float32 always)."""
    x, step, rate, b_in, c_out, state = _operands(shape, dtype)
    begins = jnp.zeros(shape[0], bool)
    y, out = pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out, state,
                                          begins, interpret=True)
    want_y, want = _plain(x, step, rate, b_in, c_out, state, begins)
    assert y.shape == want_y.shape and y.dtype == jnp.float32
    assert out.shape == state.shape and out.dtype == jnp.float32
    _close(y, want_y)
    _close(out[1:], want)


@pytest.mark.parametrize("shape", [CELL, TINY], ids=["cell", "tiny"])
def test_a_dead_slot_and_the_trash_row_bit_for_bit(shape):
    """``step`` 0 (what ``state_space_mix`` gives a slot with no live
    position) keeps the slot's rows to the bit, and no block holds row 0."""
    x, step, rate, b_in, c_out, state = _operands(shape)
    step = step.at[1].set(0.0)
    begins = jnp.zeros(shape[0], bool)
    _, out = pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out, state,
                                          begins, interpret=True)
    assert np.array_equal(out[0], state[0])
    assert np.array_equal(out[2], state[2])
    assert not np.array_equal(out[1], state[1])


@pytest.mark.parametrize("shape", [CELL, TINY], ids=["cell", "tiny"])
def test_a_slot_that_begins_enters_on_zeros(shape):
    """Whatever its rows held: here numbers no product with zero survives."""
    x, step, rate, b_in, c_out, state = _operands(shape)
    state = state.at[2].set(jnp.nan).at[2, 0].set(jnp.inf)
    begins = jnp.arange(shape[0]) == 1
    y, out = pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out, state,
                                          begins, interpret=True)
    want_y, want = _plain(x, step, rate, b_in, c_out, state, begins)
    assert np.isfinite(np.asarray(y)).all()
    _close(y, want_y)
    _close(out[1:], want)
    # ... and only where the flag says: the slot before it went on from its rows.
    fresh = tfm._ssd_step(x[:1], step[:1], rate, b_in[:1], c_out[:1],
                          jnp.zeros_like(state[1:2]))[1]
    assert np.abs(np.asarray(out[1] - fresh[0])).max() > 1e-2


def test_rows_one_onward_of_the_array_and_no_other():
    """The array may hold more rows than the program has slots: batch row
    ``b`` is row ``b + 1``, and the rows behind the last slot stay."""
    x, step, rate, b_in, c_out, state = _operands(TINY, spare_rows=2)
    begins = jnp.zeros(3, bool)
    _, out = pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out, state,
                                          begins, interpret=True)
    want = _plain(x, step, rate, b_in, c_out, state, begins)[1]
    _close(out[1:4], want)
    assert np.array_equal(out[0], state[0])
    assert np.array_equal(out[4:], state[4:])


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_groups_a_grid_step_takes_change_nothing(groups):
    x, step, rate, b_in, c_out, state = _operands(CELL)
    begins = jnp.arange(2) == 0
    whole = pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out, state,
                                         begins, interpret=True)
    split = pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out, state,
                                         begins, groups_block=groups,
                                         interpret=True)
    for got, want in zip(split, whole):      # the same block by block; the
        _close(got, want)                    # CPU fuses one trip otherwise


def test_pack_and_unpack_are_each_other():
    v = jnp.arange(2 * 32 * 16, dtype=jnp.float32).reshape(2, 32, 16)
    packed = pallas_ssm._pack(v, 8)
    assert packed.shape == (2, 4, 8, 16)
    # register i = (p // 8) * hpg + h in lane i, p % 8 on the sublanes
    assert packed[1, 2, 3, 1 * 8 + 5] == v[1, 2 * 8 + 5, 1 * 8 + 3]
    assert np.array_equal(pallas_ssm._unpack(packed, 8), v)


def test_who_takes_the_kernel():
    served = tfm.StateSpaceMixer(n_heads=128, head_dim=64, n_groups=8,
                                 state_size=128)
    small = tfm.StateSpaceMixer(n_heads=8, head_dim=8, n_groups=2,
                                state_size=16)
    assert pallas_ssm.supported(served)
    assert not pallas_ssm.supported(small)
    # 64 heads of 64 in one group: four packs of 16 heads (512 registers);
    # 24 heads of 64 are one and a half packs.
    assert pallas_ssm.supported(tfm.StateSpaceMixer(
        n_heads=64, head_dim=64, n_groups=1, state_size=128))
    assert pallas_ssm._packs(64, 8) == 4 and pallas_ssm._packs(16, 8) == 1
    assert not pallas_ssm.supported(tfm.StateSpaceMixer(
        n_heads=24, head_dim=64, n_groups=1, state_size=128))
    cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, state_space={"M": served},
        layer_attn=("M",), layer_parts=("mixer",), norm="rmsnorm")
    # The CPU of these tests, and any mesh, keep `_ssd_step`.
    assert not engine.state_kernels(cfg, None, None)
    assert not engine.state_kernels(tfm.tiny(), None, None)


def test_a_state_that_is_not_the_layers_array_is_refused():
    x, step, rate, b_in, c_out, state = _operands(TINY)
    begins = jnp.zeros(3, bool)
    with pytest.raises(ValueError, match="trash row"):       # no row 0
        pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out, state[1:],
                                     begins, interpret=True)
    with pytest.raises(ValueError, match="float32"):
        pallas_ssm.ssm_decode_update(x, step, rate, b_in, c_out,
                                     state.astype(jnp.bfloat16), begins,
                                     interpret=True)


# ---- the chunk program's kernel: ssm_chunk_scan against _ssd_blocks --------

# (heads, head_dim, groups, state_size, block): the two served mixers,
# Granite's (64 heads in ONE group, blocks of 256) and Nemotron's (8 groups of
# 16 heads, blocks of 128), whole and cut to a size at which every case runs
# in a second.
GRANITE = (64, 64, 1, 128, 256)
NEMOTRON = (128, 64, 8, 128, 128)
GRANITE_CUT = (16, 16, 1, 32, 32)
NEMOTRON_CUT = (16, 8, 4, 16, 16)
CUTS = pytest.mark.parametrize("mixer", [GRANITE_CUT, NEMOTRON_CUT],
                               ids=["granite-cut", "nemotron-cut"])


def _window(mixer, S, dtype=jnp.bfloat16, live=None, seed=0, slots=1,
            steps=(1e-3, 1e-1)):
    """A window's operands as ``state_space_mix`` hands them over: ``x``, ``B``
    and ``C`` in the compute dtype, the step float32 and 0 behind the ``live``
    positions, a state that is not zero. Steps and rates as ``init_params``
    draws them: a step log-uniform over ``steps``, a rate uniform over (1,
    16)."""
    H, P, G, N, _ = mixer
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (slots, S, H, P)).astype(dtype)
    step = jnp.exp(jax.random.uniform(ks[1], (slots, S, H), jnp.float32,
                                      *np.log(steps)))
    if live is not None:
        step = jnp.where(jnp.arange(S)[None, :, None] < live, step, 0.0)
    rate = -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)
    b_in = jax.random.normal(ks[3], (slots, S, G, N)).astype(dtype)
    c_out = jax.random.normal(ks[4], (slots, S, G, N)).astype(dtype)
    state = jax.random.normal(ks[5], (slots, H, P, N))
    return x, step, rate, b_in, c_out, state


def _scan(ops, mixer, **how):
    return pallas_ssm.ssm_chunk_scan(*ops, block=mixer[4], interpret=True,
                                     **how)


def _recurrence(x, step, rate, b_in, c_out, state):
    """The recurrence token by token in float64: what both forms round."""
    x, step, rate, b_in, c_out, state = (
        np.asarray(v, np.float64) for v in (x, step, rate, b_in, c_out, state))
    hpg = x.shape[2] // b_in.shape[2]
    ys = []
    for t in range(x.shape[1]):
        keep = np.exp(step[:, t] * rate)[..., None, None]
        grown = (step[:, t, :, None] * x[:, t])[..., None] \
            * np.repeat(b_in[:, t], hpg, 1)[:, :, None]
        state = state * keep + grown
        ys.append((state * np.repeat(c_out[:, t], hpg, 1)[:, :, None]).sum(-1))
    return np.stack(ys, 1), state


def _near(got, want):
    """Twice the decode kernel's tolerance: the kernel and ``_ssd_blocks``
    each stand up to 2e-6 (of the largest value) from the recurrence taken
    token by token in float64, a block's sums being ``block`` products deep
    (read at these shapes: 0.5 to 2.2e-6 the kernel, 0.3 to 4.0e-6 the
    blocked form)."""
    _close(got, want, tol=4e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [2, 4])
@CUTS
def test_a_window_of_whole_blocks_against_the_blocked_form(mixer, blocks,
                                                           dtype):
    """``y`` and the state leaving, a non-zero state entering; float32
    operands keep all six passes of every product, bfloat16 ones are their
    own single part."""
    ops = _window(mixer, blocks * mixer[4], dtype, slots=2)
    y, state = _scan(ops, mixer)
    want_y, want = tfm._ssd_blocks(*ops, mixer[4])
    assert y.shape == want_y.shape and y.dtype == jnp.float32
    assert state.shape == want.shape and state.dtype == jnp.float32
    _near(y, want_y)
    _near(state, want)


@pytest.mark.parametrize("mixer", [GRANITE, NEMOTRON],
                         ids=["granite", "nemotron"])
def test_the_served_widths_against_the_recurrence_in_float64(mixer):
    """The cell's case, two blocks of bfloat16 operands at the published
    widths. The blocked form's own state is 4e-6 from the float64 recurrence
    there (a block of 256), so both are held to that."""
    ops = _window(mixer, 2 * mixer[4], slots=2)
    y, state = _scan(ops, mixer)
    want_y, want = _recurrence(*ops)
    _near(y, want_y)
    _near(state, want)
    plain_y, plain = tfm._ssd_blocks(*ops, mixer[4])
    _near(plain_y, want_y)
    _close(plain, want, tol=8e-6)


@CUTS
@pytest.mark.parametrize("live", ["inside the first block",
                                  "inside the last block", "none"])
def test_dead_positions_behind_the_live_ones(mixer, live):
    """Positions whose step is 0 leave the state BIT FOR BIT, whatever their
    other operands hold, and the live positions' ``y`` too; a window with no
    live position hands its state back as it entered."""
    block = mixer[4]
    S = 3 * block
    live = {"inside the first block": block // 2 + 3,
            "inside the last block": 2 * block + 5, "none": 0}[live]
    ops = _window(mixer, S, live=live)
    y, state = _scan(ops, mixer)
    if live == 0:
        assert np.array_equal(state, ops[5])
        return
    want_y, want = tfm._ssd_blocks(*ops, block)
    _near(y[:, :live], want_y[:, :live])
    _near(state, want)
    # Other values behind the live positions: nothing read moves by a bit.
    dead = jnp.arange(S)[None, :, None, None] >= live
    other = _window(mixer, S, live=live, seed=1)
    dirty = tuple(jnp.where(dead, o, v) if v.ndim == 4 and v.shape[1] == S
                  else v for v, o in zip(ops, other))
    assert not np.array_equal(dirty[0], ops[0])
    y2, state2 = _scan(dirty, mixer)
    assert np.array_equal(state2, state)
    assert np.array_equal(y2[:, :live], y[:, :live])
    # ... and a window cut behind the live positions' block leaves the same.
    whole = -(-live // block) * block
    y3, state3 = _scan(tuple(v[:, :whole] if v.ndim > 1 and v.shape[1] == S
                             else v for v in ops), mixer)
    assert np.array_equal(state3, state)
    assert np.array_equal(y3[:, :live], y[:, :live])


@CUTS
@pytest.mark.parametrize("positions", [1, 5, 8, "block - 3"])
def test_a_window_shorter_than_a_block(mixer, positions):
    block = mixer[4]
    S = block - 3 if positions == "block - 3" else positions
    ops = _window(mixer, S)
    y, state = _scan(ops, mixer)
    want_y, want = tfm._ssd_blocks(*ops, block)
    assert y.shape == want_y.shape
    _near(y, want_y)
    _near(state, want)


@CUTS
def test_steps_that_forget_within_a_few_positions(mixer):
    """Steps of 0.3 to 3 at rates up to 16: the running sum of a block
    reaches -1000 and beyond, and ``exp`` of it 0. Nothing overflows (a decay
    is the exponential of a DIFFERENCE at most 0), and the two forms stay as
    near as the sum's own rounding lets them: a decay between neighbours is
    the difference of two sums of a thousand, half a unit of 6e-5 each."""
    ops = _window(mixer, 4 * mixer[4], steps=(0.3, 3.0), slots=2)
    y, state = _scan(ops, mixer)
    want_y, want = tfm._ssd_blocks(*ops, mixer[4])
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(state)).all()
    _close(y, want_y, tol=2e-4)
    _close(state, want, tol=2e-4)


@CUTS
def test_a_state_that_enters_on_zeros(mixer):
    ops = _window(mixer, 2 * mixer[4])
    ops = ops[:5] + (jnp.zeros_like(ops[5]),)
    y, state = _scan(ops, mixer)
    want_y, want = tfm._ssd_blocks(*ops, mixer[4])
    _near(y, want_y)
    _near(state, want)


@pytest.mark.parametrize("how", [dict(heads_pack=2), dict(heads_pack=4),
                                 dict(tile=8), dict(tile=16)],
                         ids=lambda how: "-".join(map(str, *how.items())))
@CUTS
def test_the_heads_and_the_tile_a_grid_step_takes_change_nothing(mixer, how):
    ops = _window(mixer, 2 * mixer[4], slots=2)
    for got, want in zip(_scan(ops, mixer, **how), _scan(ops, mixer)):
        _near(got, want)


@CUTS
def test_a_chunk_and_then_decode_steps(mixer):
    """The chunk kernel's state entered into the decode kernel's array, three
    steps on: what ``_ssd_blocks`` followed by ``_ssd_step`` gives."""
    H, P, G, N, block = mixer
    S, steps = 2 * block, 3
    ops = _window(mixer, S + steps, slots=2)
    x, step, rate, b_in, c_out, state = ops
    first = tuple(v[:, :S] if v.ndim > 1 and v.shape[1] == S + steps else v
                  for v in ops)
    _, got = _scan(first, mixer)
    _, want = tfm._ssd_blocks(*first, block)
    rows = jnp.concatenate([jnp.full((1, H, P, N), jnp.nan), got], 0)
    begins = jnp.zeros(2, bool)
    for t in range(S, S + steps):
        now = (x[:, t:t + 1], step[:, t:t + 1], rate, b_in[:, t:t + 1],
               c_out[:, t:t + 1])
        y, rows = pallas_ssm.ssm_decode_update(*now, rows, begins,
                                               interpret=True)
        want_y, want = tfm._ssd_step(*now, want)
        _near(y, want_y)
        _near(rows[1:], want)


def test_who_takes_the_chunk_kernel():
    """From shapes alone: what the decode kernel asks, blocks of whole lane
    tiles, and a window of whole blocks (the cell's 512 positions; not its
    page-wide tail of 16, nor a speculative window)."""
    granite = tfm.StateSpaceMixer(n_heads=64, head_dim=64, n_groups=1,
                                  state_size=128, block=256)
    nemotron = tfm.StateSpaceMixer(n_heads=128, head_dim=64, n_groups=8,
                                   state_size=128, block=128)
    for a in (granite, nemotron):
        assert pallas_ssm.chunk_supported(a, 512)
        assert pallas_ssm.chunk_supported(a, a.block)
        assert not pallas_ssm.chunk_supported(a, 16)
        assert not pallas_ssm.chunk_supported(a, a.block + 128 // 2)
    assert not pallas_ssm.chunk_supported(granite, 128)
    assert pallas_ssm._heads_pack(64) == 16 == pallas_ssm._heads_pack(16)
    assert not pallas_ssm.chunk_supported(tfm.StateSpaceMixer(
        n_heads=8, head_dim=8, n_groups=2, state_size=16, block=16), 32)
    assert not pallas_ssm.chunk_supported(tfm.StateSpaceMixer(
        n_heads=128, head_dim=64, n_groups=8, state_size=128, block=64), 512)
    cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, state_space={"M": nemotron},
        layer_attn=("M",), layer_parts=("mixer",), norm="rmsnorm")
    # The CPU of these tests, and any mesh, keep `_ssd_blocks`.
    assert not engine.state_kernels(cfg, None, None, 512)
    assert not engine._kernels(cfg, None, None, 512)["state"]


def test_a_state_of_another_shape_is_refused():
    ops = _window(NEMOTRON_CUT, 16)
    with pytest.raises(ValueError, match="state"):
        pallas_ssm.ssm_chunk_scan(*ops[:5], ops[5][:, :-1], block=16,
                                  interpret=True)
