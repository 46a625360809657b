"""Fused causal attention as a Pallas TPU kernel (FlashAttention-style).

The hot op of the transformer bench. The XLA path in
``models/transformer.py::_attention`` materializes the [B, H, S, S] logits
tensor in HBM — at S=4k that is 13+ GB of rematerialized temps and the
step no longer fits a v5e chip; this kernel streams K/V blocks through
VMEM (online softmax forward, FlashAttention-2 recomputation backward)
with float32 accumulators in scratch, so memory is O(S·D) and 16k+
sequences train on one chip.

Structure: every kernel runs on a grid ``(B*H, blocks, blocks)`` whose
innermost dimension streams the contraction blocks (K blocks for the
forward/dq kernels, Q blocks for the dk/dv kernel); accumulators live in
VMEM scratch, initialized on the first inner step and flushed to the
output refs on the last. Causal skipping is predicated (``@pl.when``), so
masked-out block pairs cost a prefetch but no MXU time. ``block_q ==
block_k`` keeps the causal frontier exactly one diagonal block.

No counterpart exists in the reference (its attention lives in user
scripts / framework libraries); this is the "pallas kernels for the hot
ops" half of the TPU-native design. Layouts follow the models/ convention
``[B, S, H, D]``. LSE/delta ride a ``[B*H, nq, 1, block]`` layout so the
row sits on the 128-lane dim (a ``[S, 1]`` layout pads the unit dim to
128 lanes — 4 MB per array at S=8k).

``interpret=True`` runs the same kernels on CPU (used by the numerics
tests, which check fwd + grads against the naive XLA attention).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _vspec(block, index_map=None):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _diag_keep(diag, mode, bq, bk):
    """Visibility mask for the diagonal block; off-diagonal active blocks
    are fully visible (block_q == block_k). mode: "diag" = q >= k
    (ordinary causal); "strict" = q > k (the half-open masks ring
    attention's striped layout needs for cross-shard blocks).

    Callers must BOTH mask s with it AND zero p with it after the exp:
    the -1e30 sentinel is finite, so on a fully-masked row
    exp(s - max(s)) = exp(0) = 1 would silently un-mask everything."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = (qpos > kpos) if mode == "strict" else (qpos >= kpos)
    return jnp.logical_not(diag) | keep


def _active(mode, qi, kj):
    """Block-level causal frontier: with any causal mode, key blocks past
    the diagonal contribute nothing."""
    if mode == "none":
        return jnp.bool_(True)
    return kj <= qi


# ---------------------------------------------------------------------------
# Forward: grid (B*H, nq, nk) — K/V blocks stream through the inner dim.

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                sm_scale, mode):
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(_active(mode, qi, kj))
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale        # [bq, D]
        k = k_ref[0].astype(jnp.float32)                   # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if mode != "none":
            keep = _diag_keep(kj == qi, mode, *s.shape)
            s = jnp.where(keep, s, _NEG_INF)
        m_prev, l_prev = m_s[:], l_s[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mode != "none":
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_s[:] = m_new
        l_s[:] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _flush():
        # Fully-masked rows (row 0 under mode="strict") have l == 0: emit
        # o = 0 and lse = -inf-ish instead of NaN so downstream online
        # merges (ring attention) treat them as "no contribution".
        l_safe = jnp.where(l_s[:] > 0, l_s[:], 1.0)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l_s[:] > 0, m_s[:] + jnp.log(l_safe), _NEG_INF)
        lse_ref[0, 0, 0] = lse[:, 0]


# ---------------------------------------------------------------------------
# Backward (FlashAttention-2): recompute P per block pair.

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_s, *, sm_scale, mode):
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    @pl.when(_active(mode, qi, kj))
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        delta = delta_ref[0, 0, 0][:, None]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)
        if mode != "none":
            # explicit zero, not just s = -1e30: a fully-masked row's
            # sentinel lse would cancel the sentinel s in the exp.
            p = jnp.where(_diag_keep(kj == qi, mode, *s.shape), p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _flush():
        dq_ref[0] = (dq_s[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *, sm_scale, mode):
    # Grid (B*H, nk, nq): Q blocks stream through the inner dim.
    kj, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(_active(mode, qi, kj))
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        delta = delta_ref[0, 0, 0][:, None]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)                                  # [bq, bk]
        if mode != "none":
            p = jnp.where(_diag_keep(kj == qi, mode, *s.shape), p, 0.0)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing over folded [B*H, S, D] arrays.

def _fold(x):
    # [B, S, H, D] -> [B*H, S, D]
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unfold(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _call_fwd(q, k, v, sm_scale, mode, block, interpret):
    BH, S, D = q.shape
    n = S // block
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, mode=mode)
    flops = 4 * BH * S * S * D // (1 if mode == "none" else 2)
    return pl.pallas_call(
        kernel,
        grid=(BH, n, n),
        in_specs=[
            _vspec((1, block, D), lambda bh, i, j: (bh, i, 0)),
            _vspec((1, block, D), lambda bh, i, j: (bh, j, 0)),
            _vspec((1, block, D), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            _vspec((1, block, D), lambda bh, i, j: (bh, i, 0)),
            _vspec((1, 1, 1, block), lambda bh, i, j: (bh, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, n, 1, block), jnp.float32),
        ],
        scratch_shapes=[_scratch((block, 1)), _scratch((block, 1)),
                        _scratch((block, D))],
        compiler_params=_compiler_params(),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=BH * S * S,
            bytes_accessed=3 * BH * S * D * q.dtype.itemsize),
        interpret=interpret,
    )(q, k, v)


def _call_bwd(q, k, v, do, lse, delta, sm_scale, mode, block, interpret):
    BH, S, D = q.shape
    n = S // block

    def q_blk(sel):
        return _vspec((1, block, D), lambda bh, i, j: (bh, sel(i, j), 0))

    def lse_blk(sel):
        return _vspec((1, 1, 1, block),
                      lambda bh, i, j: (bh, sel(i, j), 0, 0))

    i_of = lambda i, j: i  # noqa: E731
    j_of = lambda i, j: j  # noqa: E731

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, mode=mode),
        grid=(BH, n, n),
        in_specs=[q_blk(i_of), q_blk(j_of), q_blk(j_of), q_blk(i_of),
                  lse_blk(i_of), lse_blk(i_of)],
        out_specs=q_blk(i_of),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[_scratch((block, D))],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # Grid (BH, nk, nq): the kernel reads K/V at the middle index and
    # streams Q/dO/lse/delta along the inner one.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, mode=mode),
        grid=(BH, n, n),
        in_specs=[q_blk(j_of), q_blk(i_of), q_blk(i_of), q_blk(j_of),
                  lse_blk(j_of), lse_blk(j_of)],
        out_specs=[q_blk(i_of), q_blk(i_of)],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), v.dtype)],
        scratch_shapes=[_scratch((block, D)), _scratch((block, D))],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, mode, sm_scale, block, interpret):
    """Returns (o [BH,S,D], lse [BH,nq,1,block]). lse is a real output
    with its own cotangent: ring attention merges per-shard partials by
    lse, so gradients flow through it."""
    o, lse = _call_fwd(q, k, v, sm_scale, mode, block, interpret)
    return o, lse


def _flash_fwd(q, k, v, mode, sm_scale, block, interpret):
    o, lse = _call_fwd(q, k, v, sm_scale, mode, block, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(mode, sm_scale, block, interpret, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    BH, S, _ = q.shape
    # delta_i = rowsum(dO_i * O_i) — the FA2 softmax-jacobian correction —
    # packed to the same [BH, nq, 1, block] layout as lse. A cotangent on
    # lse adds p * dlse to dS (d lse / d s_j = p_j), which folds into the
    # same kernel as delta -> delta - dlse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    delta = delta.reshape(BH, S // block, 1, block)
    delta = delta - dlse.astype(jnp.float32)
    return _call_bwd(q, k, v, do, lse, delta, sm_scale, mode, block,
                     interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _validate(q, k, v, block):
    B, S, H, D = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q/k/v shapes must match, got {q.shape} "
                         f"{k.shape} {v.shape}")
    block = min(block, S)
    if S % block != 0 or block % 8 != 0:
        # Largest multiple-of-8 divisor of S that fits: callers shouldn't
        # have to tune the perf knob just to run S=384 (and Mosaic's
        # sublane tiling would reject a non-multiple-of-8 block later with
        # an opaque compile error).
        block = next((b for b in range(block - (block % 8 or 8), 7, -8)
                      if S % b == 0), 0)
        if not block:
            raise ValueError(
                f"seq len {S} must be divisible by some multiple-of-8 "
                f"block size")
    return block


def flash_attention_lse(q, k, v, *, mode="diag", sm_scale=None, block=256,
                        interpret=False):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ``[B, H, S]`` (float32, ``-1e30`` on fully-masked rows) —
    the statistic ring attention needs to merge per-shard partial
    attentions. mode: "diag" (causal, q >= k), "strict" (q > k), "none"
    (full attention). Differentiable in (q, k, v) including through lse.
    """
    if mode not in ("none", "diag", "strict"):
        raise ValueError(f"unknown mode: {mode!r}")
    B, S, H, D = q.shape
    block = _validate(q, k, v, block)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    o, lse = _flash(_fold(q), _fold(k), _fold(v), mode, float(sm_scale),
                    int(block), bool(interpret))
    return _unfold(o, B, H), lse.reshape(B, H, S)


def flash_attention(q, k, v, *, causal=True, sm_scale=None, block=256,
                    interpret=False):
    """Fused multi-head attention. q, k, v: ``[B, S, H, D]`` (same S for q
    and k/v). Returns ``[B, S, H, D]`` in the input dtype; softmax and
    accumulation run in float32 on-chip.

    ``block`` is both the query and key block size (S must divide by it);
    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU).
    """
    o, _ = flash_attention_lse(q, k, v,
                               mode="diag" if causal else "none",
                               sm_scale=sm_scale, block=block,
                               interpret=interpret)
    return o
