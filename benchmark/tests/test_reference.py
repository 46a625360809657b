import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gpt2
from horovod_tpu.models import transformer as tfm


def _tiny(dtype):
    return tfm.TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                                 n_layers=2, d_ff=256, max_seq_len=64,
                                 attn_impl="gather", dtype=dtype)


def test_reference_agrees_with_the_program_in_float32():
    """Same mathematics: in float32 the two agree to rounding."""
    cfg = _tiny("float32")
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (3, 33), 0, 256)
    w = gpt2.from_horovod_tpu(params)
    want = gpt2.logits(w, tokens[:, :-1], cfg.n_heads)
    got = tfm.forward(params, tokens[:, :-1], cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        <= 2e-5 * np.abs(np.asarray(want)).max()
    assert abs(float(gpt2.loss(w, tokens, cfg.n_heads))
               - float(tfm.loss_fn(params, {"tokens": tokens}, cfg))) < 2e-5
    last = gpt2.logits(w, tokens[:, :-1], cfg.n_heads, last=5)
    assert np.allclose(np.asarray(last), np.asarray(want[:, -5:]), atol=1e-5)


def test_bf16_program_is_within_the_loss_tolerance_and_bf16_accumulation_is_not():
    """The train cells' tolerance (configs/*.json) passes the bf16 program
    and fails a program whose products are also ACCUMULATED in bf16."""
    cfg = _tiny("bfloat16")
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 33), 0, 256)
    want = float(gpt2.loss(gpt2.from_horovod_tpu(params), tokens,
                           cfg.n_heads))
    got = float(tfm.loss_fn(params, {"tokens": tokens}, cfg))
    assert abs(got - want) / want < 1e-3
