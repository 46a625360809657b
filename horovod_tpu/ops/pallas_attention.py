"""Fused causal attention as a Pallas TPU kernel (FlashAttention-style).

The hot op of the transformer bench. The XLA path in
``models/transformer.py::_attention`` materializes the [B, H, S, S] logits
tensor in HBM — at S=4k that is 13+ GB of rematerialized temps and the
step no longer fits a v5e chip; this kernel streams K/V blocks through
VMEM (online softmax forward, FlashAttention-2 recomputation backward)
with float32 accumulators in scratch, so memory is O(S·D) and 16k+
sequences train on one chip.

Structure: two kernels, the forward and ONE backward, each on a grid
``(B*H, live block pairs)``: the pairs a causal mode keeps (``blocks *
(blocks + 1) / 2`` of them; all in mode ``"none"``) are enumerated on the
host into two int32 tables that ride in SMEM as scalar prefetch, and the
index maps and the kernels read their block coordinates from them. A
masked-out pair is therefore never a grid step and never a copy. The inner
coordinate streams the contraction blocks (K blocks under a Q block in the
forward, Q blocks under a K block in the backward); accumulators live in
VMEM scratch, initialized on a row's first pair and flushed to the output
refs on its last. ``block_q == block_k`` keeps the causal frontier exactly
one diagonal block.

The backward computes a pair's scores, ``p``, ``dO.vT`` and ``ds`` once and
feeds all three gradients from them (five products a pair): dk and dv into
the K block's scratch, dq into the head's WHOLE dq, a float32
``[n, block, D]`` output block whose index moves only with the head, so it
stays in VMEM across the head's pairs, takes ``ds.k`` at row block ``qi``
in ascending K order, and is written back once (scaled and cast outside,
fused by XLA into the transpose back to ``[B, S, H, D]``). That block is
what bounds the shape: ``_bwd_vmem_limit`` asks for more than the
compiler's default VMEM from S 8192 (bf16) on, and past the chip's VMEM
(S over 65,536 at head_dim 64) two kernels run instead, a dq kernel and
the same kernel without its dq, each computing ``p`` and ``ds`` (seven
products a pair): the same bits, a third slower.

Precision: every product takes its operands in the dtype the arrays have
and accumulates in float32; scores, softmax statistics, lse, delta and the
accumulators are float32, and ``p`` / ``ds`` are cast to the input dtype
only as MXU operands. That is what the chip did before it was written
down: Mosaic's default float32 product is ONE bfloat16 pass, so widening
bf16 blocks bought nothing (same bits, same time; PERF.md, PR 32). What
bounds the kernels is not the MXU but a grid step's fixed cost and the
per-row softmax bookkeeping, both of which a larger tile amortizes; see
``_validate``.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _dot(a, b, contract):
    """a . b over ``contract`` = (dims of a, dims of b): operands as they
    are, float32 result."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))    # [m, c] . [n, c] -> [m, n]
_NN = ((1,), (0,))    # [m, c] . [c, n] -> [m, n]
_TN = ((0,), (0,))    # [c, m] . [c, n] -> [m, n]


def _diag_keep(diag, mode, bq, bk):
    """Visibility mask for the diagonal block; off-diagonal live blocks
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


def _q_operand(q_ref, sm_scale):
    """-> (q as the MXU takes it, the factor its products still owe).
    float32 inputs fold ``sm_scale`` into q, which is the program these
    kernels always were; narrower inputs go to the MXU as they are, and
    the scale is applied in float32 (to the scores, and to dk)."""
    q = q_ref[0]
    if q.dtype == jnp.float32:
        return q * sm_scale, None
    return q, sm_scale


def _scaled(x, factor):
    return x if factor is None else x * factor


# ---------------------------------------------------------------------------
# Forward: grid (B*H, pairs), K/V blocks streaming under each Q block.

def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, sm_scale, mode, n):
    t = pl.program_id(1)
    qi, kj = qi_ref[t], kj_ref[t]

    @pl.when(kj == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q, owed = _q_operand(q_ref, sm_scale)                  # [bq, D]
    v = v_ref[0]                                           # [bk, D]
    s = _scaled(_dot(q, k_ref[0], _NT), owed)              # [bq, bk] f32
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
    acc_s[:] = acc_s[:] * alpha + _dot(p.astype(v.dtype), v, _NN)

    @pl.when(kj == (n - 1 if mode == "none" else qi))
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

def _bwd_kernel(kj_ref, qi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, *outs, sm_scale, mode, n):
    # Q blocks (with dO, lse, delta) stream under each K/V block, and each
    # pair's p and ds feed all three gradients. dq_ref is the head's whole
    # dq, [1, n, block, D] float32: it stays in VMEM from the head's first
    # pair to its last, and a Q block receives its K blocks in ascending
    # order, as the dq kernel's scratch does. Called without it (the
    # shapes of the two kernels), this is the dk/dv kernel.
    *dq_ref, dk_ref, dv_ref, dk_s, dv_s = outs
    dq_ref = dq_ref[0] if dq_ref else None
    t = pl.program_id(1)
    kj, qi = kj_ref[t], qi_ref[t]

    if dq_ref is not None:
        @pl.when(t == 0)
        def _init_dq():
            dq_ref[:] = jnp.zeros_like(dq_ref)

    @pl.when(qi == (0 if mode == "none" else kj))
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    q, owed = _q_operand(q_ref, sm_scale)
    k = k_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0, 0][:, None]
    delta = delta_ref[0, 0, 0][:, None]
    s = _scaled(_dot(q, k, _NT), owed)                     # [bq, bk]
    p = jnp.exp(s - lse)
    if mode != "none":
        # explicit zero, not just s = -1e30: a fully-masked row's
        # sentinel lse would cancel the sentinel s in the exp.
        p = jnp.where(_diag_keep(kj == qi, mode, *s.shape), p, 0.0)
    dv_s[:] = dv_s[:] + _dot(p.astype(do.dtype), do, _TN)
    dp = _dot(do, v_ref[0], _NT)
    ds = (p * (dp - delta)).astype(q.dtype)
    dk_s[:] = dk_s[:] + _dot(ds, q, _TN)
    if dq_ref is not None:
        dq_ref[0, qi] = dq_ref[0, qi] + _dot(ds, k, _NN)

    @pl.when(qi == n - 1)
    def _flush():
        dk_ref[0] = _scaled(dk_s[:], owed).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_s, *, sm_scale, mode, n):
    # dq alone, K/V blocks streaming under each Q block and p and ds
    # computed again: for the shapes whose dq cannot stay in VMEM
    # (``_bwd_vmem_limit``), beside _bwd_kernel without a dq.
    t = pl.program_id(1)
    qi, kj = qi_ref[t], kj_ref[t]

    @pl.when(kj == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    q, owed = _q_operand(q_ref, sm_scale)
    k = k_ref[0]
    lse = lse_ref[0, 0, 0][:, None]
    delta = delta_ref[0, 0, 0][:, None]
    s = _scaled(_dot(q, k, _NT), owed)
    p = jnp.exp(s - lse)
    if mode != "none":
        p = jnp.where(_diag_keep(kj == qi, mode, *s.shape), p, 0.0)
    dp = _dot(do_ref[0], v_ref[0], _NT)
    ds = p * (dp - delta)
    dq_s[:] = dq_s[:] + _dot(ds.astype(k.dtype), k, _NN)

    @pl.when(kj == (n - 1 if mode == "none" else qi))
    def _flush():
        dq_ref[0] = (dq_s[:] * sm_scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing over folded [B*H, S, D] arrays.

def _fold(x):
    # [B, S, H, D] -> [B*H, S, D]
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unfold(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _live_pairs(n, mode, q_under_k=False):
    """The block pairs a kernel visits, in grid order, as two int32 tables
    (outer, inner), inner fastest. A causal mode keeps the K blocks
    ``0..outer`` under a Q block, or with ``q_under_k`` the Q blocks
    ``outer..n-1`` under a K block."""
    outer, inner = np.divmod(np.arange(n * n, dtype=np.int32), n)
    if mode != "none":
        live = inner >= outer if q_under_k else inner <= outer
        outer, inner = outer[live], inner[live]
    return jnp.asarray(outer), jnp.asarray(inner)


def _spec(block_shape, table):
    """The block of a ``[BH, S, D]`` operand (``(1, block, D)``) or of an
    lse-shaped ``[BH, n, 1, block]`` one (``(1, 1, 1, block)``) that table
    0 (outer) or 1 (inner) names for this grid step."""
    tail = (0,) * (len(block_shape) - 2)
    return pl.BlockSpec(block_shape,
                        lambda bh, t, *tables: (bh, tables[table][t]) + tail,
                        memory_space=pltpu.VMEM)


def _call(kernel, pairs, in_specs, out_specs, out_shape, scratch, interpret,
          operands, vmem_limit_bytes=None, **kw):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(operands[0].shape[0], pairs[0].shape[0]),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret, **kw)(*pairs, *operands)


def _call_fwd(q, k, v, sm_scale, mode, block, interpret):
    BH, S, D = q.shape
    n = S // block
    outer, inner = _spec((1, block, D), 0), _spec((1, block, D), 1)
    flops = 4 * BH * S * S * D // (1 if mode == "none" else 2)
    return _call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, mode=mode, n=n),
        _live_pairs(n, mode),
        [outer, inner, inner], [outer, _spec((1, 1, 1, block), 0)],
        [jax.ShapeDtypeStruct((BH, S, D), q.dtype),
         jax.ShapeDtypeStruct((BH, n, 1, block), jnp.float32)],
        [_scratch((block, 1)), _scratch((block, 1)), _scratch((block, D))],
        interpret, (q, k, v),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=BH * S * S,
            bytes_accessed=3 * BH * S * D * q.dtype.itemsize))


def _call_bwd(q, k, v, do, lse, delta, sm_scale, mode, block, interpret):
    BH, S, D = q.shape
    n = S // block
    outer, inner = _spec((1, block, D), 0), _spec((1, block, D), 1)
    row_o, row_i = _spec((1, 1, 1, block), 0), _spec((1, 1, 1, block), 1)
    operands = (q, k, v, do, lse, delta)
    kw = dict(sm_scale=sm_scale, mode=mode, n=n)
    vmem_limit = _bwd_vmem_limit(S, D, q.dtype, block)
    fused = vmem_limit is not None

    # K, V at the outer (K) block; Q, dO, lse, delta stream; the resident
    # dq is the head's whichever the pair: its block moves only with bh.
    resident = pl.BlockSpec((1, n, block, D),
                            lambda bh, t, *tables: (bh, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    *dq, dk, dv = _call(
        functools.partial(_bwd_kernel, **kw),
        _live_pairs(n, mode, q_under_k=True),
        [inner, outer, outer, inner, row_i, row_i],
        fused * [resident] + [outer, outer],
        fused * [jax.ShapeDtypeStruct((BH, n, block, D), jnp.float32)]
        + [jax.ShapeDtypeStruct((BH, S, D), k.dtype),
           jax.ShapeDtypeStruct((BH, S, D), v.dtype)],
        [_scratch((block, D)), _scratch((block, D))], interpret, operands,
        vmem_limit_bytes=vmem_limit or None)
    if fused:
        # one elementwise pass that XLA fuses into _unfold's transpose
        return (dq[0].reshape(BH, S, D) * sm_scale).astype(q.dtype), dk, dv

    # Q, dO, lse, delta at the outer (Q) block; K, V stream.
    dq, = _call(
        functools.partial(_bwd_dq_kernel, **kw), _live_pairs(n, mode),
        [outer, inner, inner, outer, row_o, row_o], [outer],
        [jax.ShapeDtypeStruct((BH, S, D), q.dtype)],
        [_scratch((block, D))], interpret, operands)
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


# What a kernel's time is made of on the chip is a grid step's fixed cost
# and the forward's per-row softmax bookkeeping, both per block pair, far
# more than the MXU: at [16, 4096, 64] bf16 on a v5e the three kernels take
# 154.2 / 72.6 / 58.5 ms a 24-layer step at blocks of 256 / 512 / 1024, the
# same at head_dim 128, and 1024 is the better of the last two at every S
# from 1024 to 8192 (PERF.md, PR 32). So a request for a large tile (512
# or more) gets the largest whose float32 score tiles (4 MB each, several
# live) fit the kernel's VMEM, wherever the sequence divides by it; a
# smaller request is a caller testing or debugging the block loop, and
# stands.
_BIG_BLOCK = 1024


# The one backward kernel keeps a head's dq, float32 [S, D] with D padded to
# the 128 lanes, in VMEM as an output block, which Pallas buffers twice:
# 4 MiB at [16, 4096, 64], 16 at S 16384, where the compiler's default of
# 16 MiB refuses the kernel ("scoped allocation 19.50M" compiled for a
# described v5e; 24.66M once Mosaic may take room for the score tiles). A
# raised limit costs nothing (on a v5e S 8192 takes 4.616 ms a layer at the
# default and 4.612 at 20 MiB) and keeps the one kernel ahead of the two:
# 16.85 against 23.24 ms at [16, 16384, 64] (PERF.md, PR 46). So the kernel
# asks for what it needs wherever that is over the default, up to
# _VMEM_MOST of the chip's 128 MiB ([16, 65536, 64] compiles at 96 MiB,
# [16, 131072, 64] does not at 126); past it the two kernels run, which
# keep nothing a head long.
_VMEM_DEFAULT = 16 << 20
_VMEM_MOST = 100 << 20


def _bwd_vmem_limit(S, D, dtype, block):
    """How the backward runs at this shape: the one kernel's
    ``vmem_limit_bytes`` (0: the compiler's default is enough), or None
    where a head's dq cannot stay in VMEM and the two kernels run."""
    lanes = -(-D // 128) * 128
    dq = 2 * S * lanes * 4
    # q, k, v, dO in and dk, dv out, twice each; two float32 accumulators
    blocks = block * lanes * (12 * jnp.dtype(dtype).itemsize + 2 * 4)
    need = dq + blocks + (8 << 20)          # room for the score tiles
    if need <= _VMEM_DEFAULT:
        return 0
    return need if need <= _VMEM_MOST else None


def _validate(q, k, v, block):
    B, S, H, D = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q/k/v shapes must match, got {q.shape} "
                         f"{k.shape} {v.shape}")
    if block >= _BIG_BLOCK // 2 and S % _BIG_BLOCK == 0:
        block = _BIG_BLOCK
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

    ``block`` asks for the query and key block size: clipped to S, shrunk
    to a divisor of S, and from 512 up taken as "large" (``_validate``);
    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU).
    """
    o, _ = flash_attention_lse(q, k, v,
                               mode="diag" if causal else "none",
                               sm_scale=sm_scale, block=block,
                               interpret=interpret)
    return o
