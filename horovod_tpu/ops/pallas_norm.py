"""Fused BatchNorm reductions as Pallas TPU kernels.

Profiling the ResNet-50 train step (PERF.md round 4) showed the convs at
~100% of their MXU roofline while HALF the step went to XLA's
`convert_reduce_fusion` ops — the BN statistics reductions (forward
mean/var, backward sum(dy)/sum(dy*xhat)) streaming activations from HBM
well below pin rate. This module provides the one-pass paired reduction

    paired_reduce(a, b) -> (sum(a), sum(a*b))    per channel, f32 acc

that serves BOTH directions: stats = paired_reduce(x, x) gives
(sum, sumsq); the backward pair = paired_reduce(dy, x) gives
(sum(dy), sum(dy*x)), from which sum(dy*xhat) = inv*(sum(dy*x) -
mu*sum(dy)). `batch_norm_train` wires them into a custom_vjp whose
elementwise legs (apply, dx) stay in XLA where they fuse with the
surrounding relu/residual ops.

No counterpart exists in the reference (its BN lives in framework
libraries backed by cuDNN); this is the "pallas kernels for the hot ops"
half of the TPU-native design applied to the normalization pipeline.

`interpret=True` runs on CPU for the numerics tests.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_rows(R, C):
    """Largest divisor of R (multiple of 8 preferred) with a ~0.5 MB
    per-input block (2 inputs x double buffering + scratch must fit the
    16 MB scoped VMEM budget with headroom). The grid must cover R
    exactly: a block larger than R would give a zero-size grid and the
    flush step would never run."""
    target = max(1, (1 << 19) // max(C, 1))
    best = 0
    b = 8
    while b <= min(R, target):
        if R % b == 0:
            best = b
        b += 8
    if best:
        return best
    # No multiple-of-8 divisor fits (tiny or odd R): largest divisor <=
    # target, down to 1.
    for d in range(min(R, target), 0, -1):
        if R % d == 0:
            return d
    return 1


def _paired_kernel(a_ref, b_ref, s_ref, p_ref, acc_s, acc_p):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)
        acc_p[:] = jnp.zeros_like(acc_p)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_s[:] = acc_s[:] + jnp.sum(a, axis=0, keepdims=True)
    acc_p[:] = acc_p[:] + jnp.sum(a * b, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _flush():
        s_ref[...] = acc_s[:]
        p_ref[...] = acc_p[:]


def paired_reduce(a, b, *, interpret=False):
    """(sum_r a[r, c], sum_r a[r, c] * b[r, c]) over all leading dims.

    a, b: same shape [..., C]; accumulation is float32 regardless of the
    input dtype (one HBM pass over both operands).
    """
    C = a.shape[-1]
    a2 = a.reshape(-1, C)
    b2 = b.reshape(-1, C)
    R = a2.shape[0]
    br = _block_rows(R, C)
    s, p = pl.pallas_call(
        _paired_kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((br, C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec((1, C), lambda i: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, C), lambda i: (0, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((1, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32),
                        pltpu.VMEM((1, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a2, b2)
    return s[0], p[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def batch_norm_train(x, gamma, beta, eps, interpret):
    """Training-mode batch norm over all leading dims of x [..., C].

    Returns (y, mean, var) — mean/var are the batch statistics (f32) for
    the caller's running-average update. gamma/beta: [C] float32.
    """
    y, mean, var, _ = _bn_fwd_impl(x, gamma, beta, eps, interpret)
    return y, mean, var


def _bn_fwd_impl(x, gamma, beta, eps, interpret):
    R = x.size // x.shape[-1]
    s, q = paired_reduce(x, x, interpret=interpret)
    mean = s / R
    var = jnp.maximum(q / R - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    a = (gamma * inv).astype(x.dtype)
    b = (beta - gamma * inv * mean).astype(x.dtype)
    y = x * a + b  # XLA fuses this (and the consumer relu) elementwise
    return y, mean, var, inv


def _bn_fwd(x, gamma, beta, eps, interpret):
    y, mean, var, inv = _bn_fwd_impl(x, gamma, beta, eps, interpret)
    return (y, mean, var), (x, gamma, mean, inv)


def _bn_bwd(eps, interpret, res, cts):
    x, gamma, mean, inv = res
    dy, _dmean, _dvar = cts  # stats cotangents: stop-grad semantics (the
    # running-average update must not backprop — same as flax BatchNorm)
    R = x.size // x.shape[-1]
    sdy, sdyx = paired_reduce(dy, x, interpret=interpret)
    # sum(dy * xhat) with xhat = (x - mean) * inv
    sdyxh = inv * (sdyx - mean * sdy)
    dgamma = sdyxh
    dbeta = sdy
    c1 = (gamma * inv).astype(x.dtype)
    m_dy = (sdy / R).astype(jnp.float32)
    m_dyxh = (sdyxh / R).astype(jnp.float32)
    # dx = gamma*inv * (dy - mean(dy) - xhat * mean(dy*xhat))
    xhat = (x.astype(jnp.float32) - mean) * inv
    dx = c1 * (dy.astype(jnp.float32) - m_dy - xhat * m_dyxh).astype(x.dtype)
    return dx, dgamma, dbeta


batch_norm_train.defvjp(_bn_fwd, _bn_bwd)
