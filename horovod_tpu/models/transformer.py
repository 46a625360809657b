"""Decoder Transformer (optionally MoE) in pure JAX with explicit shardings.

This is the parallelism flagship: one model that exercises every axis the
framework supports on a `jax.sharding.Mesh`:

- **dp**   — batch dim sharded over the ``data`` axis (the reference's whole
  product: `DistributedOptimizer` ring-allreduce, SURVEY.md §2.4).
- **tp**   — Megatron-style column/row-parallel matmuls over the ``model``
  axis; XLA inserts the psum after row-parallel projections.
- **sp**   — activations sequence-sharded over the ``seq`` axis between
  blocks; attention gathers K/V (Ulysses-style alltoall is available in
  :mod:`horovod_tpu.parallel`).
- **ep**   — MoE expert dim sharded over the ``expert`` axis (reference
  exposes only the `hvd.alltoall` primitive for this — BASELINE.json names
  the MoE dispatch pattern as a graded config).

One block, :func:`block`, of which every supported model is an instance,
chosen by `TransformerConfig` alone: LayerNorm or RMSNorm, a learned
position table or RoPE, an RMSNorm on the projected Q and K or none, a
GELU or a gated-SiLU feed-forward, dense or ``n_experts`` routed experts
(``top_k`` a token, dropless), a tied or an untied output head. GPT-2 is
the defaults; OLMoE-1B-7B is :func:`olmoe_1b_7b`. `forward`, `loss_fn`,
`apply_block` and every serving program of ``serving/engine.py`` run that
one function and differ only in the attention they hand it.

Written as an explicit parameter pytree + a mirrored PartitionSpec pytree
(`param_specs`) instead of framework metadata, so the sharding story is
auditable in one screen. Activations in ``dtype`` (bfloat16), parameters
made and held in ``param_dtype`` (float32, or bfloat16 for a model whose
published weights are): a parameter already in the compute dtype is used
as it is, never cast.

Reference parity anchors: `examples/pytorch` BERT fine-tune (model scale),
`horovod/common/ops/*_operations.cc` `*Alltoall` (the EP primitive).
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 1024
    n_heads: int = 16
    n_layers: int = 24
    d_ff: int = 4096
    max_seq_len: int = 2048
    n_experts: int = 0          # 0 = dense FFN; >0 = MoE every layer
    top_k: int = 1              # experts a token is routed to (dropless)
    d_expert: int = 0           # width of one expert; 0 = d_ff
    # Divide a token's top-k router weights by their sum (HF's
    # ``norm_topk_prob``); False keeps the softmax's own values.
    norm_topk: bool = False
    norm: str = "layernorm"     # | "rmsnorm" (scale only, no mean, no bias)
    norm_eps: float = 1e-5
    pos: str = "learned"        # | "rope" (rotate-half, per head)
    rope_theta: float = 10000.0
    qk_norm: bool = False       # RMSNorm over the whole projected Q and K
    ffn: str = "gelu"           # | "swiglu": silu(x Wg) * (x Wu), then Wd
    tie_embeddings: bool = True  # False: a separate output head "head"
    # "gather" (K/V all-gather, XLA logits) | "ring" (seq-sharded K/V over
    # ICI) | "flash" (fused pallas kernel, ops/pallas_attention.py) |
    # "auto" (resolve per seq-len/mesh at trace time — see resolve_attn)
    attn_impl: str = "auto"
    # Q/K block size of the flash kernel (perf knob; clipped to the seq
    # len and auto-shrunk to a divisor by the kernel).
    attn_block: int = 512
    # >0: the loss computes vocab logits + log-softmax in sequence chunks of
    # this many positions (rematerialized), so the [S, vocab] float32 tensor
    # never exists — at S=8k x 30k vocab that tensor plus its backward temps
    # is gigabytes and caps single-chip sequence length before attention
    # does. 0 = single full-sequence projection.
    loss_chunk: int = 0
    # Rematerialize each transformer block in the backward pass
    # (jax.checkpoint): activation memory drops from O(n_layers * S * d *
    # intermediates) to O(n_layers * S * d), buying the last 2-4x of
    # single-chip sequence length for ~1/3 more compute.
    remat: bool = False
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # mesh axis names (any may be absent from the actual mesh; specs using a
    # missing name are invalid, so axes not in the mesh must be None'd via
    # `filter_specs`)
    data_axis: str = "data"
    model_axis: str = "model"
    seq_axis: str = "seq"
    expert_axis: str = "expert"

    def __post_init__(self):
        if self.attn_impl not in ("auto", "gather", "ring", "flash"):
            raise ValueError(
                f"attn_impl must be 'auto', 'gather', 'ring' or 'flash', "
                f"got {self.attn_impl!r}")
        for field, allowed in (("norm", ("layernorm", "rmsnorm")),
                               ("pos", ("learned", "rope")),
                               ("ffn", ("gelu", "swiglu"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"{field} must be one of {allowed}, got "
                                 f"{getattr(self, field)!r}")
        if self.n_experts and not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} must lie in 1.."
                             f"n_experts {self.n_experts}")

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def ffn_width(self):
        """Width of the feed-forward: of one expert where there are any."""
        return self.d_expert if self.n_experts and self.d_expert \
            else self.d_ff

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def bert_large() -> TransformerConfig:
    """BERT-large scale (340M): the reference's second graded config."""
    return TransformerConfig(vocab_size=30522, d_model=1024, n_heads=16,
                             n_layers=24, d_ff=4096, max_seq_len=512)


def tiny(n_experts: int = 0) -> TransformerConfig:
    """Tiny config for tests and the multi-chip dry run."""
    return TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                             n_layers=2, d_ff=128, max_seq_len=64,
                             n_experts=n_experts)


def olmoe_1b_7b(**overrides) -> TransformerConfig:
    """OLMoE-1B-7B (arXiv:2409.02060; ``allenai/OLMoE-1B-7B-0125-Instruct``
    ``config.json``): RMSNorm, RoPE, RMSNorm on the projected Q and K, 64
    gated-SiLU experts of width 1024, 8 a token with the softmax's own
    weights, an untied head, bfloat16 weights. ``overrides`` change fields
    (tests shrink every size and keep the block)."""
    fields = dict(vocab_size=50304, d_model=2048, n_heads=16, n_layers=16,
                  d_ff=1024, d_expert=1024, max_seq_len=4096, n_experts=64,
                  top_k=8, norm="rmsnorm", pos="rope", qk_norm=True,
                  ffn="swiglu", tie_embeddings=False,
                  param_dtype="bfloat16")
    fields.update(overrides)
    return TransformerConfig(**fields)


# ---------------------------------------------------------------------------
# Params

def _dense_init(key, shape, fan_in, dtype=jnp.float32):
    return (jax.random.normal(key, shape, dtype)
            / math.sqrt(fan_in)).astype(dtype)


def _norm_params(cfg, shape):
    """A norm's parameters: a scale, and for LayerNorm a bias. Float32 at
    ``param_dtype`` float32 as they always were; a bf16 model holds them
    in bf16 like its published weights."""
    pdt = jnp.dtype(cfg.param_dtype)
    p = {"scale": jnp.ones(shape, pdt)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(shape, pdt)
    return p


def init_params(key, cfg: TransformerConfig):
    """The parameter pytree, every array made from ``key`` directly in
    ``cfg.param_dtype``. Called outside ``jit`` each array is one small
    device program, so no float32 copy of a bf16 model ever exists (the
    largest temporary is one tensor's random bits)."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    D, F, H, dh = cfg.d_model, cfg.ffn_width, cfg.n_heads, cfg.head_dim
    pdt = jnp.dtype(cfg.param_dtype)
    params = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, D), pdt) * 0.02,
        "final_ln": _norm_params(cfg, (D,)),
        "layers": [],
    }
    if cfg.pos == "learned":
        params["pos_embed"] = jax.random.normal(
            keys[1], (cfg.max_seq_len, D), pdt) * 0.02
    if not cfg.tie_embeddings:
        params["head"] = _dense_init(jax.random.fold_in(keys[1], 1),
                                     (cfg.vocab_size, D), D, pdt)
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 8)
        layer = {
            "ln1": _norm_params(cfg, (D,)),
            "ln2": _norm_params(cfg, (D,)),
            # column-parallel fused QKV [D, 3, H, dh]; row-parallel out
            "wqkv": _dense_init(k[0], (D, 3, H, dh), D, pdt),
            "wo": _dense_init(k[1], (H, dh, D), D, pdt),
        }
        if cfg.qk_norm:
            layer["q_norm"] = {"scale": jnp.ones((H, dh), pdt)}
            layer["k_norm"] = {"scale": jnp.ones((H, dh), pdt)}
        lead = (cfg.n_experts,) if cfg.n_experts > 0 else ()
        if cfg.n_experts > 0:
            layer["router"] = _dense_init(k[2], (D, cfg.n_experts), D, pdt)
        layer["w_in"] = _dense_init(k[3], lead + (D, F), D, pdt)
        layer["w_out"] = _dense_init(k[4], lead + (F, D), F, pdt)
        if cfg.ffn == "swiglu":
            layer["w_gate"] = _dense_init(k[5], lead + (D, F), D, pdt)
        params["layers"].append(layer)
    return params


def param_specs(cfg: TransformerConfig):
    """PartitionSpec pytree mirroring `init_params` output.

    tp: QKV/FFN-in column-parallel (shard output dim on `model`), out
    projections row-parallel (shard input dim on `model`). ep: expert dim on
    `expert`. Embeddings vocab-sharded on `model` (XLA all-gathers for the
    tiny lookup, keeps the big table distributed).
    """
    m, e = cfg.model_axis, cfg.expert_axis
    norm = {"scale": P(), "bias": P()} if cfg.norm == "layernorm" \
        else {"scale": P()}
    layer = {
        "ln1": dict(norm),
        "ln2": dict(norm),
        "wqkv": P(None, None, m, None),   # heads sharded over model axis
        "wo": P(m, None, None),           # row-parallel
    }
    if cfg.qk_norm:
        layer["q_norm"] = {"scale": P(m, None)}
        layer["k_norm"] = {"scale": P(m, None)}
    if cfg.n_experts > 0:
        layer["router"] = P()
        layer["w_in"] = P(e, None, m)
        layer["w_out"] = P(e, m, None)
    else:
        layer["w_in"] = P(None, m)
        layer["w_out"] = P(m, None)
    if cfg.ffn == "swiglu":
        layer["w_gate"] = layer["w_in"]
    specs = {
        "embed": P(m, None),
        "final_ln": dict(norm),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }
    if cfg.pos == "learned":
        specs["pos_embed"] = P()
    if not cfg.tie_embeddings:
        specs["head"] = P(m, None)
    return specs


def filter_specs(specs, mesh):
    """Drop axis names not present in `mesh` from every spec (so one model
    definition serves any mesh shape — dp-only, dp×tp, dp×tp×sp×ep...)."""
    names = set(mesh.axis_names)

    def fix(spec):
        if not isinstance(spec, P):
            return spec
        return P(*[(a if (a in names) else None) for a in spec])

    return jax.tree.map(fix, specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Forward

# The named scopes below (embed, layer_norm, attention, mlp, loss; and grad,
# grad_reduce, optimizer in parallel/data_parallel.make_train_step) are
# metadata only: they reach every HLO operation's op_name, so XProf's and
# TensorBoard's op views group a step by them (docs/observability.md). They
# change no instruction and no program name.

def _layer_norm(x, p, eps=1e-5):
    with jax.named_scope("layer_norm"):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _rms_norm(x, p, eps, axes=(-1,)):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over ``axes``, in float32."""
    with jax.named_scope("rms_norm"):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axes, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def _norm(x, p, cfg):
    if cfg.norm == "rmsnorm":
        return _rms_norm(x, p, cfg.norm_eps)
    return _layer_norm(x, p, cfg.norm_eps)


def _rope(x, positions, cfg):
    """Rotary positions on ``x [B, S, H, dh]`` at ``positions [B, S]``: each
    head's first and second half paired (rotate-half), angle
    ``pos * theta^(-2i/dh)``; computed in float32."""
    half = cfg.head_dim // 2
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)                 # [B, S, 1, dh/2]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _qkv(h, layer, cfg, positions=None):
    """The block's Q, K, V ``[B, S, H, dh]`` from the normed input, as the
    attention and the KV cache take them: projected, RMS-normed over the
    whole width (``qk_norm``), rotated to ``positions [B, S]`` (``rope``;
    None = 0..S-1)."""
    qkv = jnp.einsum("bsd,dchk->cbshk", h,
                     layer["wqkv"].astype(cfg.compute_dtype))
    q, k, v = qkv[0], qkv[1], qkv[2]
    if cfg.qk_norm:
        q = _rms_norm(q, layer["q_norm"], cfg.norm_eps, axes=(-2, -1))
        k = _rms_norm(k, layer["k_norm"], cfg.norm_eps, axes=(-2, -1))
    if cfg.pos == "rope":
        if positions is None:
            positions = jnp.arange(h.shape[1])[None]
        q, k = _rope(q, positions, cfg), _rope(k, positions, cfg)
    return q, k, v


def _attend_ring(q, k, v, cfg, mesh):
    """Ring-attention path: K/V stay sequence-sharded and rotate on ICI
    (horovod_tpu.parallel.ring_attention) instead of being gathered. TP
    composes: each head group on the model axis runs its own ring."""
    from ..parallel.ring_attention import make_ring_attention

    names = set(mesh.axis_names)
    d = cfg.data_axis if cfg.data_axis in names else None
    s = cfg.seq_axis if cfg.seq_axis in names else None
    m = cfg.model_axis if cfg.model_axis in names else None
    S = q.shape[1]
    seq_size = mesh.shape[s] if s else 1
    head_size = mesh.shape[m] if m else 1
    if S % seq_size != 0:
        raise ValueError(
            f"attn_impl='ring' needs seq len {S} divisible by the "
            f"'{s}' axis size {seq_size}")
    if cfg.n_heads % head_size != 0:
        raise ValueError(
            f"attn_impl='ring' needs n_heads {cfg.n_heads} divisible by "
            f"the '{m}' axis size {head_size}")
    fn = make_ring_attention(mesh, axis=s, causal=True, batch_axis=d,
                             head_axis=m, jit=False)
    return fn(q, k, v)


def _attend_flash(q, k, v, cfg, mesh):
    """Fused pallas flash-attention path (ops/pallas_attention.py): the
    [B,H,S,S] logits tensor never exists in HBM. Composes with dp (batch
    over `data`) and tp (heads over `model`) via shard_map; a
    sequence-sharded mesh needs attn_impl='ring' instead. On non-TPU
    backends the kernel runs in the Pallas interpreter (numerics identical,
    speed irrelevant — that path exists for CPU tests)."""
    from ..ops.pallas_attention import flash_attention

    interpret = jax.default_backend() != "tpu"  # kernel is TPU-targeted
    attn = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block=cfg.attn_block, interpret=interpret)
    if mesh is None:
        return attn(q, k, v)
    names = set(mesh.axis_names)
    s_ax = cfg.seq_axis if cfg.seq_axis in names else None
    if s_ax and mesh.shape[s_ax] > 1:
        raise ValueError("attn_impl='flash' does not compose with a "
                         "sequence-sharded mesh; use 'ring'")
    d = cfg.data_axis if cfg.data_axis in names else None
    m = cfg.model_axis if cfg.model_axis in names else None
    if m and cfg.n_heads % mesh.shape[m] != 0:
        raise ValueError(
            f"attn_impl='flash' needs n_heads {cfg.n_heads} divisible "
            f"by the '{m}' axis size {mesh.shape[m]}")
    spec = P(d, None, m, None)
    return jax.shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def causal_attend(q, k, v, cfg, mask=None):
    """Causal multi-head attention with materialised scores, ``q [B, S, H,
    dh]`` against ``k, v [B, T, H, dh]`` -> ``[B, S, H, dh]``: the gather
    tier of :func:`resolve_attn`, and the one product the serving programs
    run over their gathered pages. ``mask`` broadcasts against the scores
    ``[B, H, S, T]``; None = the last ``S`` rows of the causal triangle."""
    dt = cfg.compute_dtype
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum("bshk,bthk->bhst", q, k) * scale
    if mask is None:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))[-s:, :]
    logits = jnp.where(mask, logits, jnp.finfo(dt).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(dt)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def _attend_gather(q, k, v, cfg, full_spec=None):
    """With a spec given, activations arrive seq-sharded and K/V are
    materialised full-sequence (XLA all-gather over the seq axis: the sp
    boundary); without, ordinary single-device attention."""
    return causal_attend(q, _constrain(k, full_spec),
                         _constrain(v, full_spec), cfg)


def _activation(h, layer, x, cfg, eq):
    """The feed-forward's hidden activation from the up projection ``h``:
    GELU of it, or SiLU of the gate projection times it."""
    if cfg.ffn == "swiglu":
        gate = jnp.einsum(eq, x, layer["w_gate"].astype(cfg.compute_dtype))
        return jax.nn.silu(gate) * h
    return jax.nn.gelu(h)


def _ffn(x, layer, cfg):
    dt = cfg.compute_dtype
    h = jnp.einsum("bsd,df->bsf", x, layer["w_in"].astype(dt))
    h = _activation(h, layer, x, cfg, "bsd,df->bsf")
    return jnp.einsum("bsf,fd->bsd", h, layer["w_out"].astype(dt))


def _route(x, layer, cfg):
    """Router of a MoE layer: ``x [.., D]`` -> the ``top_k`` largest
    softmax weights ``[.., k]`` (float32; as they are, or divided by their
    sum under ``norm_topk``) and their experts ``[.., k]``. Product and
    softmax in float32: the choice is discontinuous, so it is made at the
    precision of the reference."""
    gates = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                       layer["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    w, top = jax.lax.top_k(jax.nn.softmax(gates, -1), cfg.top_k)
    if cfg.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, top


def _moe_dense(x, w, top, layer, cfg):
    """Dense dispatch: every expert for every token, combined through the
    routing weights as a ``[b, s, E]`` mask — compilable under any mesh,
    exact. Expert weights are ep-sharded; XLA turns the einsum over the
    expert dim into compute local to each expert shard plus a psum. Costs
    ``n_experts / top_k`` times the routed work; the bandwidth-optimal
    alltoall dispatch is in horovod_tpu.parallel.expert_parallel."""
    dt = cfg.compute_dtype
    combine = jnp.sum(jax.nn.one_hot(top, cfg.n_experts, dtype=jnp.float32)
                      * w[..., None], -2).astype(dt)             # [b,s,E]
    h = jnp.einsum("bsd,edf->bsef", x, layer["w_in"].astype(dt))
    h = _activation(h, layer, x, cfg, "bsd,edf->bsef")
    y = jnp.einsum("bsef,efd->bsed", h, layer["w_out"].astype(dt))
    return jnp.einsum("bsed,bse->bsd", y, combine)


def _moe_grouped(x, w, top, layer, cfg):
    """Grouped dispatch: the ``tokens x top_k`` routed (token, expert) pairs
    sorted by expert, one ``jax.lax.ragged_dot`` per projection over the
    sorted rows, the results unsorted and summed per token under the
    routing weights. Work and (for few tokens) weight bytes follow the
    pairs and the experts they touch; nothing is dropped and no capacity
    exists. On a TPU each product is one ``ragged-dot`` instruction of the
    compiled program (a Mosaic kernel XLA brings): the name a trace reads
    (docs/observability.md)."""
    dt = cfg.compute_dtype
    B, S, D = x.shape
    k, E = cfg.top_k, cfg.n_experts
    experts = top.reshape(-1)                                     # [T*k]
    order = jnp.argsort(experts, stable=True)
    rows = x.reshape(-1, D)[order // k]                           # [T*k, D]
    sizes = jnp.bincount(experts, length=E).astype(jnp.int32)
    with jax.named_scope("experts"):
        h = jax.lax.ragged_dot(rows, layer["w_in"].astype(dt), sizes)
        if cfg.ffn == "swiglu":
            gate = jax.lax.ragged_dot(rows, layer["w_gate"].astype(dt),
                                      sizes)
            h = jax.nn.silu(gate) * h
        else:
            h = jax.nn.gelu(h)
        y = jax.lax.ragged_dot(h, layer["w_out"].astype(dt), sizes)
    y = y[jnp.argsort(order)].reshape(B, S, k, D)
    return jnp.einsum("bskd,bsk->bsd", y, w.astype(dt))


def _moe_ffn(x, layer, cfg, mesh=None, valid=None):
    """Top-k routed MoE, dropless: -> (output ``[b, s, D]``, routing).
    On one device (``mesh`` None) the experts take the grouped form
    (:func:`_moe_grouped`), prefill and decode alike; under a mesh the dense
    form (:func:`_moe_dense`), which XLA shards over the ``expert`` and
    ``model`` axes and which is right, at ``n_experts / top_k`` times the
    work: a grouped product under an expert-sharded mesh is not written.

    The routing is ``{"top": [b, s, k] experts, "counts": [E]}``, the
    (token, expert) pairs each expert received from the rows ``valid [b,
    s]`` marks (all by default): what ``serve_stats()["moe"]`` counts."""
    w, top = _route(x, layer, cfg)
    if mesh is None:
        y = _moe_grouped(x, w, top, layer, cfg)
    else:
        y = _moe_dense(x, w, top, layer, cfg)
    hit = jax.nn.one_hot(top, cfg.n_experts, dtype=jnp.int32)   # [b,s,k,E]
    if valid is not None:
        hit = hit * valid[..., None, None]
    return y, {"top": top, "counts": hit.sum((0, 1, 2))}


# The measured flash-vs-gather crossover expressed as LIVE score
# elements rather than a bare query length: causal self-attention at the
# measured S=1024 v5e crossover materializes S*S/2 = 524288 live logits,
# and that footprint — not the query length — is what the fused kernel
# eliminates. Keying on it makes the same calibration cover asymmetric
# shapes (chunked prefill: q=512 against an 8k KV cache is 4M live
# elements — flash territory the old q-only rule misfiled as "gather").
_FLASH_SCORE_ELEMS = 1024 * 1024 // 2


def resolve_attn(cfg: TransformerConfig, seq_len: int, mesh=None,
                 kv_len=None, causal=True) -> str:
    """Resolve attn_impl="auto" to the best concrete kernel for this
    (seq_len, kv_len, mesh, backend) at trace time (VERDICT r3 #3: the
    framework must pick its best kernel unconditionally, not make users
    tune it).

    ``seq_len`` is the QUERY length; ``kv_len`` the key/value length
    (defaults to ``seq_len`` — ordinary self-attention). The serving
    plane's shapes (horovod_tpu/serving/engine.py) are what force the
    distinction: a decode step is q_len=1 against a KV cache thousands
    of tokens long, and a chunked prefill is a short query block against
    a long cache.

    - sequence-sharded mesh → "ring", but only for full self-attention
      (``kv_len == seq_len``): the ring rotates K/V shards past every
      query shard, which is meaningless for a 1-token query against an
      externally-held cache;
    - non-TPU backend → "gather" (the pallas kernel would run in the
      interpreter: numerically right, not fast);
    - decode (``seq_len == 1``) → "gather" REGARDLESS of kv_len: the
      score tensor is [B,H,1,KV] — linear in KV, nothing for flash's
      q-block tiling to eliminate, and the kernel would pad the single
      query row to a full block;
    - otherwise key on the LIVE score footprint: ``seq_len * kv_len``
      elements (halved for the causal self-attention triangle) against
      the measured S=1024 self-attention crossover. Causal mask mode
      matters: a causal square materializes half the logits a bidirectional
      one does, so bidirectional attention crosses to flash at ~724
      tokens while causal crosses at 1024.
    """
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    kv = seq_len if kv_len is None else int(kv_len)
    if (mesh is not None and cfg.seq_axis in mesh.axis_names
            and mesh.shape[cfg.seq_axis] > 1 and kv == seq_len):
        return "ring"
    if jax.default_backend() != "tpu":
        return "gather"
    if seq_len == 1:
        return "gather"
    score = seq_len * kv
    if causal and kv == seq_len:
        score //= 2  # only the lower triangle is live
    return "flash" if score >= _FLASH_SCORE_ELEMS else "gather"


def _constrain(v, spec):
    """with_sharding_constraint when a spec is present (mesh mode)."""
    return jax.lax.with_sharding_constraint(v, spec) \
        if spec is not None else v


def block(layer, x, cfg: TransformerConfig, attend, positions=None,
          mesh=None, out_spec=None, valid=None):
    """THE transformer block, written once: ``x + Wo attend(q, k, v)`` of the
    normed input, then ``+ ffn`` of the normed result -> ``(x, routing)``
    (``routing`` None for a dense feed-forward; see :func:`_moe_ffn`).

    ``attend(q, k, v) -> [B, S, H, dh]`` is all that differs between the
    trainer's three kernels (:func:`apply_block`) and the serving programs
    (``serving/engine.py``: write the window's K/V to the paged cache, then
    attend over the gathered pages). ``positions [B, S]`` are the tokens'
    places in their sequences (None = 0..S-1), read by RoPE."""
    dt = cfg.compute_dtype
    h = _norm(x, layer["ln1"], cfg)
    with jax.named_scope("attention"):
        q, k, v = _qkv(h, layer, cfg, positions)
        out = jnp.einsum("bshk,hkd->bsd", attend(q, k, v),
                         layer["wo"].astype(dt))
        x = x + _constrain(out, out_spec)
    h = _norm(x, layer["ln2"], cfg)
    with jax.named_scope("mlp"):
        if cfg.n_experts > 0:
            y, routing = _moe_ffn(h, layer, cfg, mesh, valid)
        else:
            y, routing = _ffn(h, layer, cfg), None
        x = x + y
    return x, routing


def _block_fn(cfg, mesh, impl, seq_spec, full_spec):
    """``(layer, x) -> (x, routing)`` with the trainer's attention."""
    if (impl == "ring" and mesh is not None
            and cfg.seq_axis in mesh.axis_names):
        attend = lambda q, k, v: _attend_ring(q, k, v, cfg, mesh)  # noqa: E731
    elif impl == "flash":
        attend = lambda q, k, v: _attend_flash(q, k, v, cfg, mesh)  # noqa: E731
    else:
        attend = lambda q, k, v: _attend_gather(  # noqa: E731
            q, k, v, cfg, full_spec)

    def fn(layer, x):
        x, routing = block(layer, x, cfg, attend, mesh=mesh,
                           out_spec=seq_spec)
        return _constrain(x, seq_spec), routing

    return fn


def apply_block(layer, x, cfg: TransformerConfig, mesh=None, impl=None,
                seq_spec=None, full_spec=None):
    """One transformer block as a standalone ``(layer_params, x) -> x`` —
    the unit `forward` stacks, and the natural pipeline-parallel stage
    (parallel/pipeline.py `pipeline_apply` with the per-layer params
    stacked on a leading stage dim; see tests/test_pipeline.py)."""
    if impl is None:
        impl = resolve_attn(cfg, x.shape[1], mesh)
    return _block_fn(cfg, mesh, impl, seq_spec, full_spec)(layer, x)[0]


def embed_tokens(params, tokens, cfg):
    """Token embeddings in the compute dtype, shaped like ``tokens``."""
    return params["embed"].astype(cfg.compute_dtype)[tokens]


def add_positions(x, params, cfg, positions=None):
    """A learned position table's rows added to ``x`` (rows 0..S-1 of
    ``x [B, S, D]``, or ``positions``, shaped like ``x`` without its last
    axis); RoPE adds nothing here, it turns Q and K (:func:`_qkv`)."""
    if cfg.pos != "learned":
        return x
    table = params["pos_embed"].astype(cfg.compute_dtype)
    if positions is None:
        return x + table[:x.shape[1]][None]
    return x + table[positions]


def head_weights(params, cfg):
    """The output projection ``[vocab, D]``: the embedding where tied."""
    return params["embed"] if cfg.tie_embeddings else params["head"]


def forward(params, tokens, cfg: TransformerConfig, mesh=None,
            return_hidden=False):
    """tokens [B, S] int32 → logits [B, S, vocab] (compute dtype), or the
    final-norm hidden states [B, S, d] with ``return_hidden=True``
    (the chunked loss projects to vocab itself).

    When `mesh` is given, activations carry dp/sp sharding constraints; with
    mesh=None it is ordinary single-device JAX.
    """
    dt = cfg.compute_dtype
    if mesh is not None:
        names = set(mesh.axis_names)
        d = cfg.data_axis if cfg.data_axis in names else None
        s = cfg.seq_axis if cfg.seq_axis in names else None
        seq_spec = jax.sharding.NamedSharding(mesh, P(d, s, None))
        full_spec = jax.sharding.NamedSharding(mesh, P(d, None, None))
    else:
        seq_spec = full_spec = None

    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = add_positions(embed_tokens(params, tokens, cfg), params, cfg)
    x = _constrain(x, seq_spec)

    impl = resolve_attn(cfg, S, mesh)
    fn = _block_fn(cfg, mesh, impl, seq_spec, full_spec)

    def block_(x, layer):
        return fn(layer, x)[0]

    if cfg.remat:
        block_ = jax.checkpoint(block_)
    for layer in params["layers"]:
        x = block_(x, layer)
    x = _norm(x, params["final_ln"], cfg)
    if return_hidden:
        return x
    logits = jnp.einsum("bsd,vd->bsv", x,
                        head_weights(params, cfg).astype(dt))
    return logits


def _nll(hidden, targets, embed):
    """-log p(target) per position from pre-projection hidden states."""
    with jax.named_scope("loss"):
        logits = jnp.einsum("bsd,vd->bsv", hidden,
                            embed.astype(hidden.dtype))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def loss_fn(params, batch, cfg: TransformerConfig, mesh=None):
    """Next-token cross-entropy. batch = {"tokens": [B, S+1] int32}.

    With ``cfg.loss_chunk > 0`` the vocab projection + log-softmax run per
    sequence chunk under jax.checkpoint inside a scan (see the config
    field's rationale); the chunked and full losses are identical.
    """
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    C = cfg.loss_chunk
    S = targets.shape[1]
    head = head_weights(params, cfg)
    if not C or S <= C:
        hidden = forward(params, tokens[:, :-1], cfg, mesh=mesh,
                         return_hidden=True)
        return jnp.mean(_nll(hidden, targets, head))

    if S % C != 0:
        raise ValueError(f"seq len {S} must divide by loss_chunk {C}")
    hidden = forward(params, tokens[:, :-1], cfg, mesh=mesh,
                     return_hidden=True)
    B, _, d = hidden.shape
    h_chunks = hidden.reshape(B, S // C, C, d).swapaxes(0, 1)
    t_chunks = targets.reshape(B, S // C, C).swapaxes(0, 1)

    def body(total, xs):
        h, t = xs
        return total + jnp.sum(_nll(h, t, head)), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                            (h_chunks, t_chunks))
    return total / (B * S)
