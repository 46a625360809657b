"""The plain reference of ``phi-4-mini-flash-reasoning``
(``benchmark/reference/phi4_flash.py``) at a tiny size on the CPU: it is the
program's forward pass to rounding (float32 both), every control of the
configuration file moves its logits, its view of the program's parameters
copies and upcasts nothing, and the rows it is asked for are the rows of the
whole pass."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import phi4_flash as reference
from benchmark.runners import serve_sambay
from horovod_tpu.models import transformer as tfm

HERE = os.path.dirname(os.path.abspath(__file__))
FILE = json.load(open(os.path.join(
    os.path.dirname(HERE), "configs", "phi-4-mini-flash-reasoning.json")))
KINDS = ["mamba", "window"] * 3 + ["mamba", "full"] + ["gmu", "cross"] * 2


@pytest.fixture(scope="module")
def tiny():
    config = json.loads(json.dumps(FILE))
    config.update(hidden_size=32, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=8, intermediate_size=48,
                  sliding_window=6, vocab_size=96, max_position_embeddings=256,
                  num_hidden_layers=len(KINDS), layer_kinds=KINDS,
                  memory_from=6, kv_from=7)
    config["model"].update(dtype="float32", param_dtype="float32")
    config["assumed"]["mamba"].update(d_inner=64, d_state=4, dt_rank=2)
    config["assumed"]["serve"]["chunk"] = 8
    cfg = serve_sambay.model_config(config)
    params = serve_sambay.make_params(cfg, jax.random.PRNGKey(0))
    hp = reference.hyper(config)
    ref = jax.jit(lambda p, t, kn: reference.logits(
        reference.from_horovod_tpu(p), t, hp, kn=kn))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 96, (1, 40)))
    return config, cfg, params, hp, ref, tokens


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def test_the_reference_is_the_programs_forward_pass(tiny):
    _, cfg, params, hp, ref, tokens = tiny
    want = ref(params, tokens, reference.knobs(hp))
    assert _rel(tfm.forward(params, tokens, cfg), want) < 2e-5
    rows = (3, 38, 39)
    some = reference.logits(reference.from_horovod_tpu(params), tokens, hp,
                            rows=rows)
    assert some.shape == (1, 3, 96)
    assert np.allclose(some[0], want[0, list(rows)], atol=1e-5)


def test_the_configuration_lists_every_fault():
    planted = FILE["controls"]["planted_faults"]["reference_faults"]
    assert set(planted) == set(reference.FAULTS)
    with pytest.raises(ValueError, match="no planted fault"):
        reference.knobs(reference.hyper(FILE), "something_else")


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_control_moves_the_logits(tiny, fault):
    _, _, params, hp, ref, tokens = tiny
    want = ref(params, tokens, reference.knobs(hp))
    assert _rel(ref(params, tokens, reference.knobs(hp, fault)), want) > 0.02


def test_weights_rounded_to_8_bits_move_the_logits(tiny):
    _, _, params, hp, ref, tokens = tiny
    low = reference.logits(reference.rounded_to_int8(
        reference.from_horovod_tpu(params)), tokens, hp)
    assert _rel(low, ref(params, tokens, reference.knobs(hp))) > 0.02


def test_the_view_of_the_parameters_copies_no_matrix_in_float32(tiny):
    """Slices, reshapes and transposes of what is stored: the dtype of every
    leaf is the parameters' own (a bfloat16 model is never upcast whole)."""
    _, cfg, _, _, _, _ = tiny
    import dataclasses
    half = dataclasses.replace(cfg, param_dtype="bfloat16")
    shapes = jax.eval_shape(lambda: reference.from_horovod_tpu(
        tfm.init_params(jax.random.PRNGKey(0), half)))
    assert {x.dtype for x in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    a_log = shapes["layers"][0]["mixer"]["A_log"]
    assert a_log.shape == (64, 4)              # [channel, state], as published
