"""The chunked delta rule's kernel (``ops/pallas_kda.py``: ``kda_chunk_scan``)
in interpret mode on the CPU, against the function it stands in for,
``transformer._delta_blocks``, AND against the recurrence itself position by
position in float64 (``tests/test_solar_open2.py``'s, with that file's
operands and tolerances): the same mathematics in float32 with the sums in
another order. That the chip's compiler takes the kernel at the served shapes,
once a layer, is ``tests/test_tpu_compile.py``'s; times are the chip's
(PERF.md, PR 49).
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_kda

from .test_solar_open2 import _operands, _recurrence


def _scan(*ops, **kw):
    return pallas_kda.kda_chunk_scan(*ops, interpret=True, **kw)


def _worst(got, want):
    return np.abs(np.asarray(got, np.float64)
                  - np.asarray(want, np.float64)).max()


@pytest.mark.parametrize("window,live", [(7, 7), (64, 64), (130, 130),
                                         (130, 101), (40, 33)])
def test_against_the_chunked_form_and_the_recurrence(window, live):
    """Windows of less than a sub-block, of one block and of three (padded by
    the wrapper to whole blocks), entering on a non-zero state, with dead
    positions behind the live ones: the live outputs and the state leaving."""
    ops = _operands(window, live)
    o, s = _scan(*ops)
    assert o.shape == ops[0].shape and o.dtype == jnp.float32
    assert s.shape == ops[5].shape and s.dtype == jnp.float32
    assert np.isfinite(np.asarray(o)).all()
    for want_o, want_s in (tfm._delta_blocks(*ops, 64), _recurrence(*ops)):
        assert _worst(o[:, :live], want_o[:, :live]) < 2e-6
        assert _worst(s, want_s) < 1e-5


def test_keys_that_share_a_direction():
    """Keys behind a SiLU (``k_i . k_j`` a few tenths for every pair): the
    inverse is exact substitution here too."""
    q, k, v, g, beta, state = _operands(130, 130, decay=0.05, seed=9)
    k = jax.nn.silu(3.0 * k + 0.5)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    assert float(jnp.einsum("bshd,bthd->bhst", k, k).mean()) > 0.3
    o, s = _scan(q, k, v, g, beta, state)
    for want_o, want_s in (tfm._delta_blocks(q, k, v, g, beta, state, 64),
                           _recurrence(q, k, v, g, beta, state)):
        assert _worst(o, want_o) < 1e-5
        assert _worst(s, want_s) < 1e-4


def test_decays_of_twenty_a_position_do_not_overflow():
    """``exp(-G)`` of the running sum overflows float32 after five such
    positions; the kernel exponentiates differences only."""
    ops = _operands(130, 130, decay=20.0)
    o, s = _scan(*ops)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    for want_o, want_s in (tfm._delta_blocks(*ops, 64), _recurrence(*ops)):
        assert _worst(o, want_o) < 2e-5
        assert _worst(s, want_s) < 1e-4


def test_a_dead_window_leaves_the_state_bit_for_bit():
    q, k, v, g, beta, state = _operands(70, 0)
    assert not np.asarray(g).any() and not np.asarray(beta).any()
    _, s = _scan(q, k, v, g, beta, state)
    assert np.array_equal(np.asarray(s), np.asarray(state))


@pytest.mark.parametrize("heads_block", [1, 2, 3, 4])
def test_head_groups_and_a_batch_of_two(heads_block):
    """Four heads in groups of one, of two (3 does not divide: two) and of
    four, a group's heads stacked into one block-diagonal problem, and a
    batch of two: every (batch row, head) carries its own state over its own
    blocks, and nothing crosses from a head to its neighbour in the stack."""
    ops = _operands(130, 120, seed=2, B=2, H=4)
    o, s = _scan(*ops, heads_block=heads_block)
    want_o, want_s = tfm._delta_blocks(*ops, 64)
    assert _worst(o[:, :120], want_o[:, :120]) < 2e-6
    assert _worst(s, want_s) < 1e-5


def test_a_head_of_128_lanes():
    """The served head width, one block of two stacked heads: the tiles the
    chip's compiler sees."""
    ops = _operands(64, 64, seed=4, B=1, H=2, d=128)
    o, s = _scan(*ops)
    want_o, want_s = _recurrence(*ops)
    assert _worst(o, want_o) < 2e-6
    assert _worst(s, want_s) < 1e-5


@pytest.mark.parametrize("head_dim,tiles", [(128, True), (256, True),
                                            (16, False), (64, False),
                                            (192, False)])
def test_supported_is_whole_registers_of_lanes(head_dim, tiles):
    assert pallas_kda.supported(types.SimpleNamespace(head_dim=head_dim)) \
        is tiles


def test_a_state_of_another_shape_is_refused():
    q, k, v, g, beta, state = _operands(7, 7)
    with pytest.raises(ValueError, match="state"):
        _scan(q, k, v, g, beta, state[:, :2])
