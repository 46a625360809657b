"""The decode step's state-space kernel (``ops/pallas_ssm.py``:
``ssm_decode_update``) in interpret mode on the CPU, against the function it
stands in for, ``transformer._ssd_step``: the same formula on the same operand
values in float32, so they agree to float32 rounding (the kernel sums over the
state's last axis in another order); what the update must leave alone it leaves
bit for bit. That the chip's compiler takes the kernel at the served shapes,
once a layer and with no copy of the state, is ``tests/test_tpu_compile.py``'s;
times are the chip's (PERF.md, PR 43).
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
