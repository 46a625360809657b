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

A configuration may also describe its layers one by one: ``layer_attn`` names
each layer's attention, and a name found in ``latent`` makes that layer
multi-head LATENT attention (:class:`LatentAttention`: low-rank queries, one
compressed key/value row a token shared by all heads, attended in the
absorbed form; optionally a window, or a learned selection of the
``index_topk`` best-scoring keys), ``attn_gate`` a sigmoid gate a head,
``dense_layers`` leading layers with a plain feed-forward before the expert
layers, ``shared_experts`` an expert every token visits, ``router="sigmoid"``
a sigmoid router with a selection bias, and ``experts_held`` the (offset,
count) of the experts THIS device holds: the router scores all of them and
the layer computes its own experts' part of the result (expert parallelism's
one-device half). ``state_space`` names layers whose mixer is a STATE-SPACE
recurrence (:class:`StateSpaceMixer`, :func:`state_space_mix`: no attention,
a constant state a sequence), ``layer_parts`` says which layers are a mixer
ALONE or a feed-forward ALONE (one norm and one residual such a layer),
``ffn="relu2"`` a feed-forward of ``relu(x W1)^2 W2``, and ``expert_latent``
routed experts that work in a space narrower than the residual stream,
between one shared down and one shared up projection. ``delta_rule`` names
layers whose mixer is gated delta-rule LINEAR attention
(:class:`DeltaRuleMixer`, :func:`delta_rule_mix`: a ``[key, value]`` matrix a
head that decays a key channel and is corrected, not added to, by each token).
Nothing names a model.

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
import functools
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..observability import scopes


def _round_up(n, m):
    return -(-n // m) * m


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """One size of multi-head latent attention (DeepSeek-V2, arXiv:2405.04434
    section 2.1). Queries go through a rank ``q_rank`` latent; keys and values
    are ONE latent row a token, ``kv_rank`` normed dims and ``rope_dim``
    rotated dims shared by all heads, which is all the cache holds. A head's
    key is ``nope_dim`` dims made from the latent plus the shared rotated
    dims, its value ``v_dim`` dims made from the latent.

    ``window`` > 0: a query sees the last ``window`` positions, itself
    included. ``index_topk`` > 0: a query sees the ``index_topk`` earlier
    keys that a small scorer rates highest (``index_heads`` heads of
    ``index_dim``, rotary on the first ``index_rope_dim``; DeepSeek-V3.2's
    indexer), all of them while fewer precede it. Neither: a query sees its
    whole context.

    ``q_rank`` 0: no query latent, one direct projection ``d_model ->
    n_heads * (nope_dim + rope_dim)``. ``q_head_norm``: an RMSNorm over each
    head's query (one scale of ``nope_dim + rope_dim`` for all heads) before
    the rotation. ``yarn`` (a :class:`Yarn` or its fields): the rotated dims
    turn at YaRN's frequencies. ``scale_mult``: a factor on the softmax scale
    ``(nope_dim + rope_dim)^-1/2`` (DeepSeek's ``mscale^2`` under YaRN: it
    multiplies the whole logit, so it cannot ride in cos and sin)."""
    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    window: int = 0
    index_heads: int = 0
    index_dim: int = 0
    index_rope_dim: int = 0
    index_topk: int = 0
    q_head_norm: bool = False
    yarn: Optional["Yarn"] = None
    scale_mult: float = 1.0

    def __post_init__(self):
        if isinstance(self.yarn, dict):
            object.__setattr__(self, "yarn", Yarn(**self.yarn))
        if self.index_topk and not self.q_rank:
            raise ValueError("a key selection reads the query latent: "
                             "index_topk needs q_rank > 0")

    @property
    def softmax_scale(self):
        return self.scale_mult / math.sqrt(self.nope_dim + self.rope_dim)

    @property
    def row_width(self):
        """Lanes of a cached row: the latent, the rotated dims, and zeros up
        to whole lane tiles of 128 (what a TPU array's minor dimension is
        stored at anyway, made explicit so that kernels take whole tiles)."""
        return _round_up(self.kv_rank + self.rope_dim, 128)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's scaling of rotary frequencies (arXiv:2309.00071, as
    ``transformers`` computes it): each inverse frequency a blend of the
    plain one and the one divided by ``factor``, by a linear ramp between
    the dims that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max`` positions; cos and sin times ``attention_factor``
    (None = ``0.1 ln(factor) + 1``)."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class MultiHeadAttention:
    """One kind of multi-head attention that a configuration describes by
    name (``TransformerConfig.multihead``), as :class:`LatentAttention`
    describes a latent kind: ``n_heads`` query heads over ``n_kv_heads``
    key/value heads of ``head_dim`` (query head ``j`` reads key/value head
    ``j // (n_heads / n_kv_heads)``; ``head_dim`` is the kind's own, not
    ``d_model / n_heads``). ``window`` > 0: a query sees the last ``window``
    positions, itself included. Rotary rule of the kind: ``rope_theta``,
    the first ``rope_share`` of each head rotated (rotate-half within it),
    ``yarn`` (a :class:`Yarn` or its fields) or plain. ``gate``: a sigmoid
    gate on the attention's output, from a projection of the layer's normed
    input: True one a query head (``d_model -> n_heads``), ``"channel"`` one
    a channel of every head (``d_model -> n_heads * head_dim``;
    arXiv:2505.06708's elementwise form). ``bias``: a bias on the query,
    key/value and output projections.

    ``differential`` (Differential Attention, arXiv:2410.05258): adjacent
    heads pair (``2j``, ``2j + 1``, queries and key/value heads alike, query
    pair ``p`` reading key/value pair ``p // group``); a pair's two softmaxes,
    each over its own key head and BOTH over the pair's two value heads side
    by side (``2 * head_dim`` wide), are subtracted, ``a1 - lambda a2``, with
    ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` from four
    learned ``head_dim`` vectors a layer and ``lambda_init = 0.8 - 0.6
    exp(-0.3 i)`` of the layer's index ``i``; the difference passes an
    RMSNorm over its ``2 * head_dim`` (one learned scale a layer) and is
    scaled by ``1 - lambda_init``. It is ATTENDED as ordinary grouped
    attention at twice the head width (:attr:`attended`): a pair's first
    query padded with zeros behind, its second with zeros before, against the
    pair's two key heads side by side, which is what the fused cache row
    already holds.

    ``kv_from``: the index of an EARLIER layer whose keys and values this
    layer attends (YOCO's shared cache, arXiv:2405.05254). Such a layer
    projects queries only and owns no cache.

    ``softmax_scale``: the factor on ``q . k`` where the kind states its own
    (a source's ``attention_multiplier``); None = ``head_dim ** -0.5``. The
    queries carry it (times ``sqrt(head_dim)``, in float32 before they are
    rounded), so every attention, plain or kernel, stays at ``head_dim **
    -0.5``.

    ``v_head_dim``: the width of a VALUE head where it is not the key's
    (None = ``head_dim``). Queries and keys are ``head_dim`` wide and score
    at ``head_dim ** -0.5``; values, a head's output and ``wo``'s rows are
    ``v_dim`` wide, and a layer's K and V arrays have lanes of their own
    (:attr:`k_width`, :attr:`v_width`). The key and value projections are
    then two arrays, ``wk`` and ``wv``. ``value_scale``: a factor on the
    projected value (in float32, before it is rounded and cached). ``sink``:
    one learned scalar ``b_j`` a query head a layer joins the softmax's
    DENOMINATOR and nothing else, ``a_k = exp(s_k) / (exp(b_j) + sum_k'
    exp(s_k'))``: a column appended to the scores and dropped after the
    softmax, never a stored key.

    ``qk_head_norm``: an RMSNorm over each head's query and each head's key
    (one scale of ``head_dim`` a layer for the queries' heads, one for the
    keys'), before the rotation.

    ``select_topk`` > 0: a learned selection of key/value BLOCKS. Positions
    lie in blocks of ``select_block``; each key/value head's group of query
    heads chooses for itself, a query at a time. An indexer of
    ``index_heads`` heads of ``index_dim`` a group (projections of the
    layer's normed input, no rotation) keeps ONE pooled row a block, the
    elementwise maximum of the block's indexer keys, and scores a whole
    block ``n`` for the query at ``t`` as ``I = sum_j w_j relu(qI_j .
    pooled_n)``. A query in block ``bt`` always attends the first
    ``select_first`` blocks and the ``select_local`` last ones (``bt -
    select_local + 1 .. bt``); the candidates are the whole blocks between,
    and the ``select_topk`` of highest ``I`` join them (ties to the lower
    index; all of them while no more are candidates, so a short context is
    attended whole). Softmax over the visible positions of the chosen
    blocks (:func:`block_scores`, :func:`select_blocks`,
    :func:`blocks_allowed`)."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0
    rope_theta: float = 10000.0
    rope_share: float = 1.0
    yarn: Optional[Yarn] = None
    gate: Union[bool, str] = False
    bias: bool = False
    differential: bool = False
    kv_from: Optional[int] = None
    softmax_scale: Optional[float] = None
    v_head_dim: Optional[int] = None
    value_scale: float = 1.0
    sink: bool = False
    qk_head_norm: bool = False
    select_block: int = 0
    select_topk: int = 0
    select_first: int = 1
    select_local: int = 2
    index_heads: int = 0
    index_dim: int = 0

    def __post_init__(self):
        if isinstance(self.yarn, dict):
            object.__setattr__(self, "yarn", Yarn(**self.yarn))
        if self.gate not in (False, True, "channel"):
            raise ValueError(f"gate is False, True or 'channel', "
                             f"got {self.gate!r}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads do not divide "
                             f"over {self.n_kv_heads} key/value heads")
        if self.differential and (self.n_kv_heads % 2 or self.gate):
            raise ValueError("differential attention pairs adjacent heads "
                             "(an even number of key/value heads) and is "
                             "not written with a gate")
        if self.kv_from is not None and self.window:
            raise ValueError("a layer that attends another layer's keys and "
                             "values reads pages, not a ring: no window")
        if self.softmax_scale is not None and self.differential:
            raise ValueError("softmax_scale is not written for differential "
                             "attention (its queries already carry sqrt(2))")
        if self.v_dim != self.head_dim and (
                self.differential or self.bias or self.gate == "channel"
                or self.kv_from is not None):
            raise ValueError("a value width of its own is written for plain "
                             "grouped heads only (no pairs, bias, channel "
                             "gate or shared keys and values)")
        if self.value_scale != 1.0 and (self.differential or self.bias):
            raise ValueError("value_scale is not written for differential "
                             "attention or beside a bias")
        if self.sink and (self.differential or self.kv_from is not None):
            raise ValueError("a sink is not written for differential "
                             "attention or a layer that attends another's "
                             "keys and values")
        if self.select_topk and (
                self.window or self.differential or self.sink or self.bias
                or self.kv_from is not None or self.select_block < 1
                or self.index_heads < 1 or self.index_dim < 1
                or self.select_first < 1 or self.select_local < 1):
            raise ValueError("a block selection is written for plain grouped "
                             "heads over their whole context (no window, "
                             "pairs, sink, bias or shared keys and values) "
                             "and needs select_block, index_heads, index_dim "
                             "and at least one first and one local block")

    @property
    def query_mult(self):
        """What the queries are multiplied by so that an attention at
        ``head_dim ** -0.5`` scores at ``softmax_scale``; 1.0 = nothing."""
        if self.softmax_scale is None:
            return 1.0
        return float(self.softmax_scale) * math.sqrt(self.head_dim)

    @property
    def group(self):
        """Query heads that read one key/value head."""
        return self.n_heads // self.n_kv_heads

    @property
    def v_dim(self):
        """The width of a value head, of a head's output and of ``wo``'s
        rows."""
        return self.head_dim if self.v_head_dim is None else self.v_head_dim

    @property
    def k_width(self):
        """Lanes of a cached K row: the key heads, fused."""
        return self.n_kv_heads * self.head_dim

    @property
    def v_width(self):
        """Lanes of a cached V row: the value heads, fused."""
        return self.n_kv_heads * self.v_dim

    @property
    def rope_dim(self):
        return int(self.head_dim * self.rope_share)

    @property
    def attended(self):
        """The kind as its attention and its cache see it: itself, or for
        differential attention the same heads in pairs, ``n_kv_heads / 2``
        key/value heads of ``2 * head_dim`` under ``n_heads`` padded
        queries (the same fused cache row)."""
        if not self.differential:
            return self
        return dataclasses.replace(
            self, n_kv_heads=self.n_kv_heads // 2,
            head_dim=2 * self.head_dim, differential=False)

    @property
    def pool_width(self):
        """Lanes of a block's pooled row: the groups' indexer keys, fused."""
        return self.n_kv_heads * self.index_dim

    @property
    def split_kv(self):
        """Whether the key and value projections are two arrays (``wk``,
        ``wv``) and not the fused ``wkv``: where their widths differ."""
        return self.v_dim != self.head_dim


def lambda_init(li):
    """Differential attention's ``lambda_init`` of layer ``li``."""
    return 0.8 - 0.6 * math.exp(-0.3 * li)


@dataclasses.dataclass(frozen=True)
class StateSpaceMixer:
    """One kind of state-space mixer that a configuration describes by name
    (``TransformerConfig.state_space``): Mamba-2's selective state space
    (arXiv:2405.21060). ``n_heads`` heads of ``head_dim`` channels, each with
    a ``[head_dim, state_size]`` float32 state and one scalar decay; the
    input and output maps ``B`` and ``C`` (``state_size`` wide) are shared by
    the heads of one of ``n_groups`` groups (head ``h`` reads group ``h //
    (n_heads / n_groups)``); a depthwise causal convolution over the last
    ``conv_kernel`` tokens comes before. ``block`` (the source's
    ``chunk_size``) is how many positions :func:`state_space_mix` multiplies
    as one block; it changes no value. What a sequence carries from one
    program run to the next: the last ``conv_kernel - 1`` inputs of the
    convolution and the state."""
    n_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int = 4
    block: int = 128

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(f"{self.n_heads} heads do not divide over "
                             f"{self.n_groups} groups")

    @property
    def d_inner(self):
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self):
        """Channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.state_size

    @property
    def in_width(self):
        """Columns of the in-projection: the gate z, x | B | C, a step a
        head."""
        return self.d_inner + self.conv_dim + self.n_heads

    @property
    def tail(self):
        """Convolution inputs a sequence carries over."""
        return self.conv_kernel - 1

    @property
    def state_shape(self):
        """A sequence's float32 state."""
        return self.n_heads, self.head_dim, self.state_size


@dataclasses.dataclass(frozen=True)
class DeltaRuleMixer:
    """One kind of gated delta-rule linear attention that a configuration
    describes by name (``TransformerConfig.delta_rule``): Kimi Delta
    Attention (arXiv:2510.26692; the gated delta rule of arXiv:2412.06464
    with a decay a CHANNEL). ``n_heads`` heads, each with a float32 state
    ``S [head_dim, head_dim]`` (key by value) that a token first decays a key
    channel (``Diag(alpha) S``, ``alpha`` in (0, 1]) and then CORRECTS along
    its unit key: ``S += beta k (v - S^T k)^T``, what the state already holds
    for the key taken out before the value goes in. ``beta`` is a sigmoid,
    doubled under ``neg_eigval`` (``I - beta k k^T`` then has an eigenvalue in
    (-1, 1) along ``k``). Queries, keys and values each pass a depthwise
    causal convolution over the last ``conv_kernel`` tokens; the decay and the
    output gate come through low-rank pairs of width ``low_rank`` (0 =
    ``head_dim``). What a sequence carries from one program run to the next:
    the last ``conv_kernel - 1`` projected q | k | v, and the state."""
    n_heads: int
    head_dim: int
    conv_kernel: int = 4
    low_rank: int = 0
    neg_eigval: bool = True

    @property
    def d_inner(self):
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self):
        """Channels the convolutions run over: q, k and v."""
        return 3 * self.d_inner

    @property
    def rank(self):
        return self.low_rank or self.head_dim

    @property
    def tail(self):
        """Convolution inputs a sequence carries over."""
        return self.conv_kernel - 1

    @property
    def state_shape(self):
        """A sequence's float32 state, held VALUE-major: ``[heads, value,
        key]``, the key channels on the minor dimension, where the decay,
        the key and the query of a token are vectors."""
        return self.n_heads, self.head_dim, self.head_dim


@dataclasses.dataclass(frozen=True)
class SelectiveScanMixer:
    """One kind of PER-CHANNEL selective state space that a configuration
    describes by name (``TransformerConfig.selective_scan``): Mamba-1's
    (arXiv:2312.00752). ``d_inner`` channels, each with ``state_size`` float32
    states of its own decay: ``h[n, c] = exp(step[c] A[n, c]) h[n, c] +
    step[c] x[c] B[n]`` and ``y[c] = sum_n h[n, c] C[n] + D[c] x[c]``, with
    ``step`` (through a rank ``dt_rank`` pair), ``B`` and ``C`` projected from
    the convolved input a token. No heads and no scalar decay
    (:class:`StateSpaceMixer`'s block products cannot say it): every (state,
    channel) pair decays by itself, so the recurrence is vector work along the
    positions. A depthwise causal convolution over the last ``conv_kernel``
    tokens comes before. What a sequence carries from one program run to the
    next: the last ``conv_kernel - 1`` inputs of the convolution and the
    state."""
    d_inner: int
    dt_rank: int
    state_size: int = 16
    conv_kernel: int = 4

    @property
    def conv_dim(self):
        """Channels the convolution runs over: x alone."""
        return self.d_inner

    @property
    def tail(self):
        """Convolution inputs a sequence carries over."""
        return self.conv_kernel - 1

    @property
    def state_shape(self):
        """A sequence's float32 state, held STATE-major: ``[state, channel]``,
        the channels on the minor dimension, where a token's step and input
        are vectors (and where a TPU array's minor dimension is whole lane
        tiles: 16 states there would be stored at 128)."""
        return self.state_size, self.d_inner


@dataclasses.dataclass(frozen=True)
class GatedMemoryUnit:
    """One kind of mixer with no cache and no attention that a configuration
    describes by name (``TransformerConfig.gated_memory``): SambaY's Gated
    Memory Unit (arXiv:2507.06607), ``(silu(u W_1) * m) W_2`` with ``m`` the
    MEMORY of layer ``memory_from`` at the same position: that layer's
    :class:`SelectiveScanMixer` output before its gate, ``d_inner`` wide."""
    d_inner: int
    memory_from: int


# The mixers that carry a state a sequence and attend nothing.
RECURRENT = (StateSpaceMixer, DeltaRuleMixer, SelectiveScanMixer)


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
    # "softmax": the top_k largest softmax weights. "sigmoid": sigmoid
    # scores, the top_k of score + a learned selection bias
    # (``router_bias``), the weights the chosen scores themselves
    # (DeepSeek-V3's ``noaux_tc``), times ``routed_scale``.
    router: str = "softmax"
    routed_scale: float = 1.0
    # Experts every token visits beside the routed ones (one feed-forward of
    # ``shared_experts * d_expert``); 0 = none.
    shared_experts: int = 0
    # Leading layers whose feed-forward is dense (width ``d_ff``) in a model
    # whose other layers hold experts.
    dense_layers: int = 0
    # (offset, count): the experts this device holds of ``n_experts``. The
    # router scores and picks among all; the layer computes the held ones.
    # () = all of them.
    experts_held: tuple = ()
    # Per-layer attention: ``layer_attn[i]`` names layer i's, and a name that
    # is a key of ``latent`` (name -> LatentAttention or its fields) makes it
    # latent attention of that size; any other name, or no entry, is the
    # multi-head attention of ``n_heads``. Entries past ``n_layers`` are
    # ignored (a published pattern longer than the layers that are run).
    layer_attn: tuple = ()
    latent: tuple = ()
    # name -> MultiHeadAttention or its fields: a layer so named is multi-head
    # attention of THAT kind (its own head counts, ``head_dim``, window,
    # rotary rule and gate) and not of ``n_heads``.
    multihead: tuple = ()
    # name -> StateSpaceMixer or its fields: a layer so named has a
    # state-space mixer in the place of attention.
    state_space: tuple = ()
    # name -> DeltaRuleMixer or its fields: a layer so named has gated
    # delta-rule linear attention in the place of attention.
    delta_rule: tuple = ()
    # name -> SelectiveScanMixer or its fields: a layer so named has a
    # per-channel selective state space in the place of attention.
    selective_scan: tuple = ()
    # name -> GatedMemoryUnit or its fields: a layer so named gates the
    # memory of an earlier selective-scan layer in the place of attention.
    gated_memory: tuple = ()
    # Per-layer halves: ``layer_parts[i]`` is "both" (attention, then a
    # feed-forward: the default, and what a missing entry means), "mixer" (the
    # mixer alone) or "ffn" (the feed-forward alone). A layer of one half has
    # that half's norm and residual only.
    layer_parts: tuple = ()
    # >0: the routed experts work at this width. One projection ``d_model ->
    # expert_latent`` before them and one back after their weighted sum, both
    # shared by all experts; the router and the shared expert read the full
    # width.
    expert_latent: int = 0
    # The normed query and key/value latents times sqrt(d_model / rank).
    latent_rescale: bool = False
    # A sigmoid gate a head on the attention's output, from a d_model ->
    # heads projection of the layer's normed input.
    attn_gate: bool = False
    norm: str = "layernorm"     # | "rmsnorm" (scale only, no mean, no bias)
    norm_eps: float = 1e-5
    pos: str = "learned"        # | "rope" (rotate-half, per head) | "none"
    rope_theta: float = 10000.0
    qk_norm: bool = False       # RMSNorm over the whole projected Q and K
    ffn: str = "gelu"           # | "swiglu": silu(x Wg) * (x Wu), then Wd
    #                             | "relu2": relu(x W1)^2, then W2
    # A clamped gated feed-forward (``swigluoai``), dense, shared and routed
    # alike: with ``g = min(x Wg, limit)`` and ``u = clip(x Wu, -limit,
    # limit)`` the hidden activation is ``g sigmoid(alpha g) (u + 1)``.
    # ``swiglu_limit`` 0 = the plain ``silu(x Wg) (x Wu)``.
    swiglu_limit: float = 0.0
    swiglu_alpha: float = 1.0
    # RMSNorm in the form ``x / rms(x) (1 + w)`` (every norm of the block,
    # the final one and a kind's per-head Q/K norm); ``w`` starts at zero.
    norm_plus_one: bool = False
    tie_embeddings: bool = True  # False: a separate output head "head"
    # Three scalars a source may state (Granite's ``embedding_multiplier``,
    # ``residual_multiplier``, ``logits_scaling``): the token embeddings times
    # ``embed_mult``, every half's output times ``residual_mult`` before it
    # joins the stream, the logits DIVIDED by ``logits_div``. At 1.0 nothing
    # is multiplied: the programs are what they were. (A kind's own softmax
    # scale is ``MultiHeadAttention.softmax_scale``.) The trainer's loss
    # projects for itself and refuses a ``logits_div`` it would not apply.
    embed_mult: float = 1.0
    residual_mult: float = 1.0
    logits_div: float = 1.0
    # "gather" (K/V all-gather, XLA logits) | "ring" (seq-sharded K/V over
    # ICI) | "flash" (fused pallas kernel, ops/pallas_attention.py) |
    # "auto" (resolve per seq-len/mesh at trace time — see resolve_attn)
    attn_impl: str = "auto"
    # Q/K block size of the flash kernel (perf knob; clipped to the seq
    # len and auto-shrunk to a divisor by the kernel).
    attn_block: int = 512
    # The loss computes vocab logits + log-softmax in sequence chunks, each
    # chunk's gradients in the same trip of one scan (_chunked_nll), so the
    # [S, vocab] float32 tensor never exists — at S=8k x 30k vocab that
    # tensor plus its backward temps is gigabytes and caps single-chip
    # sequence length before attention does. What the backward pass is
    # handed instead: d(hidden) [B, S, d] and one float32 [vocab, d].
    # 0 = the program picks the chunk from the shapes it sees
    # (_loss_positions); >0 = this many positions, a cap a caller sets for
    # memory.
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
        for field, kind in (("latent", LatentAttention),
                            ("multihead", MultiHeadAttention),
                            ("state_space", StateSpaceMixer),
                            ("delta_rule", DeltaRuleMixer),
                            ("selective_scan", SelectiveScanMixer),
                            ("gated_memory", GatedMemoryUnit)):
            named = getattr(self, field)
            named = named.items() if isinstance(named, dict) else named
            object.__setattr__(self, field, tuple(
                (name, a if isinstance(a, kind) else kind(**a))
                for name, a in named))
        object.__setattr__(self, "layer_attn", tuple(self.layer_attn))
        object.__setattr__(self, "layer_parts", tuple(self.layer_parts))
        for part in self.layer_parts:
            if part not in ("both", "mixer", "ffn"):
                raise ValueError(f"layer_parts entries are 'both', 'mixer' "
                                 f"or 'ffn', got {part!r}")
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.experts_held:
            offset, count = self.experts_held
            if not (self.n_experts and 0 <= offset
                    and 0 < count <= self.n_experts - offset):
                raise ValueError(f"experts_held {self.experts_held} is not "
                                 f"a range of the {self.n_experts} experts")
        for field, allowed in (("norm", ("layernorm", "rmsnorm")),
                               ("pos", ("learned", "rope", "none")),
                               ("ffn", ("gelu", "swiglu", "relu2")),
                               ("router", ("softmax", "sigmoid"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"{field} must be one of {allowed}, got "
                                 f"{getattr(self, field)!r}")
        if (self.swiglu_limit or self.swiglu_alpha != 1.0) and (
                self.ffn != "swiglu" or self.swiglu_limit <= 0):
            raise ValueError("swiglu_limit > 0 (and swiglu_alpha with it) is "
                             "the clamped form of ffn='swiglu'")
        if self.norm_plus_one and self.norm != "rmsnorm":
            raise ValueError("norm_plus_one is RMSNorm's (1 + w) form")
        if self.n_experts and not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} must lie in 1.."
                             f"n_experts {self.n_experts}")

    @property
    def head_dim(self):
        """Of the multi-head attention of ``n_heads``; a kind that a layer
        names (``multihead``) states its own."""
        return self.d_model // self.n_heads

    @property
    def ffn_width(self):
        """Width of the feed-forward: of one expert where there are any."""
        return self.d_expert if self.n_experts and self.d_expert \
            else self.d_ff

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def attn_of(self, li):
        """Layer ``li``'s :class:`LatentAttention`,
        :class:`MultiHeadAttention`, :class:`StateSpaceMixer`,
        :class:`DeltaRuleMixer`, :class:`SelectiveScanMixer` or
        :class:`GatedMemoryUnit`, or None for the multi-head attention of
        ``n_heads``."""
        name = self.layer_attn[li] if li < len(self.layer_attn) else None
        return dict(self.latent + self.multihead + self.recurrent
                    + self.gated_memory).get(name)

    def _reads(self, li, field):
        """Whether a later layer names ``li`` in ``field`` of its kind."""
        return any(getattr(self.attn_of(lj), field, None) == li
                   for lj in range(li + 1, self.n_layers))

    def hands_memory(self, li):
        """Whether a later layer (a :class:`GatedMemoryUnit`) reads layer
        ``li``'s memory."""
        return self._reads(li, "memory_from")

    def shares_kv(self, li):
        """Whether a later layer attends layer ``li``'s keys and values
        (``MultiHeadAttention.kv_from``)."""
        return self._reads(li, "kv_from")

    @property
    def recurrent(self):
        """The described kinds that carry a state a sequence (``(name,
        kind)`` pairs): a model with any is padded, cached and speculated
        differently (``serving/``)."""
        return self.state_space + self.delta_rule + self.selective_scan

    @property
    def described(self):
        """Whether layers are described by kind (``latent``, ``multihead``,
        ``state_space``, ``delta_rule``, ``selective_scan``,
        ``gated_memory``, ``layer_parts``): such a model is filled by chunks
        and its layers' caches differ."""
        return bool(self.latent or self.multihead or self.recurrent
                    or self.gated_memory or self.layer_parts)

    def _part(self, li):
        return self.layer_parts[li] if li < len(self.layer_parts) else "both"

    def has_mixer(self, li):
        """Whether layer ``li`` has an attention or state-space half."""
        return self._part(li) != "ffn"

    def has_ffn(self, li):
        """Whether layer ``li`` has a feed-forward half."""
        return self._part(li) != "mixer"

    def is_moe(self, li):
        return (self.n_experts > 0 and li >= self.dense_layers
                and self.has_ffn(li))

    @property
    def moe_layers(self):
        """The layers that hold experts."""
        return [li for li in range(self.n_layers) if self.is_moe(li)]

    @property
    def selects_blocks(self):
        """Whether any layer's kind selects key/value blocks: such a model's
        cache holds pooled rows beside its pages (``serving/kv_cache``)."""
        return any(a.select_topk for _, a in self.multihead)

    @property
    def n_held(self):
        """Experts whose weights this device holds."""
        return self.experts_held[1] if self.experts_held else self.n_experts


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
    p = {"scale": (jnp.zeros if cfg.norm_plus_one else jnp.ones)(shape, pdt)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(shape, pdt)
    return p


def _ffn_params(k, cfg, lead, F, D=None):
    """One feed-forward's matrices of width ``F`` from three keys (``lead``
    = (experts,) for a stack of them), reading and writing ``D`` dims
    (``d_model`` by default; experts in a latent their latent's)."""
    D, pdt = D or cfg.d_model, jnp.dtype(cfg.param_dtype)
    p = {"w_in": _dense_init(k[0], lead + (D, F), D, pdt),
         "w_out": _dense_init(k[1], lead + (F, D), F, pdt)}
    if cfg.ffn == "swiglu":
        p["w_gate"] = _dense_init(k[2], lead + (D, F), D, pdt)
    return p


def _latent_params(key, cfg, a: LatentAttention):
    """A latent-attention layer's matrices: query down/up through
    ``q_rank`` (or one direct projection where it is 0), the per-head query
    norm, key/value down to ``kv_rank + rope_dim`` and up to each head's
    ``nope_dim + v_dim``, the output projection, the head gate, and the
    selection's scorer."""
    D, pdt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    k = jax.random.split(key, 9)
    H = a.n_heads
    # Under ``latent_rescale`` a normed latent's entries have magnitude
    # sqrt(D / rank), so the matrices that read it are drawn as if it had D
    # entries of magnitude one: queries, keys and values of unit scale, and
    # attention logits a softmax can tell apart, as a trained model's are
    # (at 1 / rank the logits' spread is D / sqrt(q_rank kv_rank) = 7 at the
    # published widths, and rounding decides the output).
    q_in = D if cfg.latent_rescale else a.q_rank
    kv_in = D if cfg.latent_rescale else a.kv_rank
    if a.q_rank:
        p = {"wq_a": _dense_init(k[0], (D, a.q_rank), D, pdt),
             "q_norm": {"scale": jnp.ones((a.q_rank,), pdt)},
             "wq_b": _dense_init(k[1], (a.q_rank, H, a.nope_dim + a.rope_dim),
                                 q_in, pdt)}
    else:
        p = {"wq": _dense_init(k[0], (D, H, a.nope_dim + a.rope_dim), D, pdt)}
    if a.q_head_norm:
        p["q_head_norm"] = {"scale": jnp.ones((a.nope_dim + a.rope_dim,),
                                              pdt)}
    p.update({
        "wkv_a": _dense_init(k[2], (D, a.kv_rank + a.rope_dim), D, pdt),
        "kv_norm": {"scale": jnp.ones((a.kv_rank,), pdt)},
        "wkv_b": _dense_init(k[3], (a.kv_rank, H, a.nope_dim + a.v_dim),
                             kv_in, pdt),
        "wo": _dense_init(k[4], (H, a.v_dim, D), H * a.v_dim, pdt),
    })
    if cfg.attn_gate:
        p["w_attn_gate"] = _dense_init(k[5], (D, H), D, pdt)
    if a.index_topk:
        p["wi_q"] = _dense_init(k[6], (a.q_rank, a.index_heads, a.index_dim),
                                q_in, pdt)
        p["wi_k"] = _dense_init(k[7], (D, a.index_dim), D, pdt)
        p["i_norm"] = {"scale": jnp.ones((a.index_dim,), pdt),
                       "bias": jnp.zeros((a.index_dim,), pdt)}
        p["wi_w"] = _dense_init(k[8], (D, a.index_heads), D, pdt)
    return p


def _multihead_params(key, cfg, a: MultiHeadAttention):
    """A described multi-head layer's matrices: the query projection of
    ``n_heads``, the fused key and value projections of ``n_kv_heads`` (none
    where the layer attends another's, ``kv_from``; two arrays, ``wk`` and
    ``wv``, where a value head has a width of its own), the output
    projection, the head gate; with ``bias`` a bias on each projection; with
    ``differential`` the four ``lambda`` vectors (N(0, 0.1)) and the scale of
    the pairs' norm; with ``sink`` one scalar a query head, zeros; with
    ``qk_head_norm`` the two per-head norms' scales; with a block selection
    the indexer's three projections, a key/value group each."""
    D, pdt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    k = jax.random.split(key, 5)
    p = {"wq": _dense_init(k[0], (D, a.n_heads, a.head_dim), D, pdt),
         "wo": _dense_init(k[2], (a.n_heads, a.v_dim, D),
                           a.n_heads * a.v_dim, pdt)}
    if a.split_kv:
        kk, kv = jax.random.split(k[1])
        p["wk"] = _dense_init(kk, (D, a.n_kv_heads, a.head_dim), D, pdt)
        p["wv"] = _dense_init(kv, (D, a.n_kv_heads, a.v_dim), D, pdt)
    elif a.kv_from is None:
        p["wkv"] = _dense_init(k[1], (D, 2, a.n_kv_heads, a.head_dim), D, pdt)
    if a.sink:
        p["sink"] = jnp.zeros((a.n_heads,), pdt)
    if a.qk_head_norm:
        start = jnp.zeros if cfg.norm_plus_one else jnp.ones
        for name in ("q_head_norm", "k_head_norm"):
            p[name] = {"scale": start((a.head_dim,), pdt)}
    if a.select_topk:
        ki = jax.random.split(jax.random.fold_in(key, 7), 3)
        G, J, d = a.n_kv_heads, a.index_heads, a.index_dim
        p["wi_q"] = _dense_init(ki[0], (D, G, J, d), D, pdt)
        p["wi_k"] = _dense_init(ki[1], (D, G, d), D, pdt)
        p["wi_w"] = _dense_init(ki[2], (D, G, J), D, pdt)
    if a.bias:
        p["bq"] = jnp.zeros((a.n_heads, a.head_dim), pdt)
        p["bo"] = jnp.zeros((D,), pdt)
        if a.kv_from is None:
            p["bkv"] = jnp.zeros((2, a.n_kv_heads, a.head_dim), pdt)
    if a.differential:
        p["diff_lambda"] = (0.1 * jax.random.normal(
            k[4], (4, a.head_dim), jnp.float32)).astype(pdt)
        p["diff_norm"] = {"scale": jnp.ones((2 * a.head_dim,), pdt)}
    if a.gate == "channel":
        p["w_attn_gate"] = _dense_init(k[3], (D, a.n_heads, a.head_dim), D,
                                       pdt)
    elif a.gate:
        p["w_attn_gate"] = _dense_init(k[3], (D, a.n_heads), D, pdt)
    return p


def _state_space_params(key, cfg, a: StateSpaceMixer):
    """A state-space layer's parameters: the in-projection (z | x B C | a
    step a head), the convolution's taps and bias, a head's step bias, log
    decay rate and skip, the gated norm's scale, the out-projection. Steps
    are drawn log-uniform over (0.001, 0.1) and decay rates uniform over (1,
    16), as Mamba-2 initialises them, so that heads remember tens to
    thousands of tokens."""
    D, pdt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    k = jax.random.split(key, 5)
    step = jnp.exp(jax.random.uniform(
        k[3], (a.n_heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_ssm_in": _dense_init(k[0], (D, a.in_width), D, pdt),
        "conv_w": _dense_init(k[1], (a.conv_dim, a.conv_kernel),
                              a.conv_kernel, pdt),
        "conv_b": jnp.zeros((a.conv_dim,), pdt),
        # softplus(dt_bias) = step
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pdt),
        "a_log": jnp.log(jax.random.uniform(
            k[4], (a.n_heads,), jnp.float32, 1.0, 16.0)).astype(pdt),
        "ssm_skip": jnp.ones((a.n_heads,), pdt),
        "ssm_norm": {"scale": jnp.ones((a.d_inner,), pdt)},
        "w_ssm_out": _dense_init(k[2], (a.d_inner, D), a.d_inner, pdt),
    }


def _delta_rule_params(key, cfg, a: DeltaRuleMixer):
    """A delta-rule layer's parameters: the fused q | k | v projection, the
    three convolutions' taps (no bias), one fused narrow projection (the
    decay's and the gate's low-rank inputs and ``beta`` a head), the two
    low-rank output halves, a head's log decay rate, a channel's decay bias,
    the gate's bias, the per-head norm's scale (shared by the heads), the
    out-projection. The decay is drawn as :func:`_state_space_params` draws
    Mamba-2's: a channel's step log-uniform over (0.001, 0.1) behind the
    softplus, a head's rate uniform over (1, 16), and the low-rank half that
    moves the step at a tenth, so that channels remember tens to a thousand
    tokens (at N(0, 1 / fan_in) throughout a state forgets within a few)."""
    D, pdt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    k = jax.random.split(key, 8)
    r, H = a.rank, a.n_heads
    step = jnp.exp(jax.random.uniform(
        k[5], (a.d_inner,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_dr_in": _dense_init(k[0], (D, a.conv_dim), D, pdt),
        "dr_conv_w": _dense_init(k[1], (a.conv_dim, a.conv_kernel),
                                 a.conv_kernel, pdt),
        # columns: the decay's low-rank input | the gate's | beta a head
        "w_dr_low": _dense_init(k[2], (D, 2 * r + H), D, pdt),
        "w_dr_decay": (0.1 * _dense_init(k[3], (r, a.d_inner), r)
                       ).astype(pdt),
        "w_dr_gate": _dense_init(k[4], (r, a.d_inner), r, pdt),
        # softplus(dr_dt_bias) = step
        "dr_dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pdt),
        "dr_a_log": jnp.log(jax.random.uniform(
            k[6], (H,), jnp.float32, 1.0, 16.0)).astype(pdt),
        "dr_gate_bias": jnp.zeros((a.d_inner,), pdt),
        "dr_norm": {"scale": jnp.ones((a.head_dim,), pdt)},
        "w_dr_out": _dense_init(k[7], (a.d_inner, D), a.d_inner, pdt),
    }


def _selective_scan_params(key, cfg, a: SelectiveScanMixer):
    """A selective-scan layer's parameters: the in-projection (x | the gate
    z), the convolution's taps and bias, the projection of the convolved x to
    (the step's low rank | B | C), the step's up-projection and bias, the log
    decay rates ``[state, channel]``, the skip, the out-projection. As Mamba-1
    initialises them: state ``n`` of every channel decays at rate ``n + 1``,
    the step's bias is the inverse softplus of a step drawn log-uniform over
    (0.001, 0.1) and its up-projection uniform within ``dt_rank ** -0.5``, so
    that a channel's states remember from a handful of tokens to a
    thousand."""
    D, pdt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    C, N, R = a.d_inner, a.state_size, a.dt_rank
    k = jax.random.split(key, 6)
    step = jnp.exp(jax.random.uniform(
        k[4], (C,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_scan_in": _dense_init(k[0], (D, 2 * C), D, pdt),
        "scan_conv_w": _dense_init(k[1], (C, a.conv_kernel), a.conv_kernel,
                                   pdt),
        "scan_conv_b": jnp.zeros((C,), pdt),
        "w_scan_x": _dense_init(k[2], (C, R + 2 * N), C, pdt),
        "w_scan_dt": jax.random.uniform(
            k[3], (R, C), jnp.float32, -R ** -0.5, R ** -0.5).astype(pdt),
        # softplus(scan_dt_bias) = step
        "scan_dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pdt),
        "scan_a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, N + 1, dtype=jnp.float32))[:, None], (N, C)).astype(pdt),
        "scan_skip": jnp.ones((C,), pdt),
        "w_scan_out": _dense_init(k[5], (C, D), C, pdt),
    }


def _gated_memory_params(key, cfg, a: GatedMemoryUnit):
    """A gated memory unit's two matrices."""
    D, pdt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    k = jax.random.split(key, 2)
    return {"w_gmu_in": _dense_init(k[0], (D, a.d_inner), D, pdt),
            "w_gmu_out": _dense_init(k[1], (a.d_inner, D), a.d_inner, pdt)}


def _mixer_params(key, cfg, a):
    """The parameters of a layer's mixer of a described kind."""
    make = {MultiHeadAttention: _multihead_params,
            LatentAttention: _latent_params,
            StateSpaceMixer: _state_space_params,
            DeltaRuleMixer: _delta_rule_params,
            SelectiveScanMixer: _selective_scan_params,
            GatedMemoryUnit: _gated_memory_params}[type(a)]
    return make(key, cfg, a)


def init_params(key, cfg: TransformerConfig):
    """The parameter pytree, every array made from ``key`` directly in
    ``cfg.param_dtype``. Called outside ``jit`` each array is one small
    device program, so no float32 copy of a bf16 model ever exists (the
    largest temporary is one tensor's random bits)."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    D, H, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
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
        layer = {}
        a = cfg.attn_of(i)
        if cfg.has_mixer(i):
            layer["ln1"] = _norm_params(cfg, (D,))
            if a is None:
                # column-parallel fused QKV [D, 3, H, dh]; row-parallel out
                layer["wqkv"] = _dense_init(k[0], (D, 3, H, dh), D, pdt)
                layer["wo"] = _dense_init(k[1], (H, dh, D), D, pdt)
                if cfg.qk_norm:
                    layer["q_norm"] = {"scale": jnp.ones((H, dh), pdt)}
                    layer["k_norm"] = {"scale": jnp.ones((H, dh), pdt)}
            else:
                layer.update(_mixer_params(k[0], cfg, a))
        if cfg.has_ffn(i):
            layer["ln2"] = _norm_params(cfg, (D,))
            layer.update(_layer_ffn_params(k, cfg, i))
        params["layers"].append(layer)
    return params


def _layer_ffn_params(k, cfg, i):
    """Layer ``i``'s feed-forward from the layer's keys ``k``: dense, or the
    router, the held experts (at ``expert_latent`` where that is set, with
    the two projections around them) and the shared expert."""
    D, pdt = cfg.d_model, jnp.dtype(cfg.param_dtype)
    if not cfg.is_moe(i):
        return _ffn_params(k[3:6], cfg, (), cfg.d_ff)
    F, L = cfg.ffn_width, cfg.expert_latent
    p = {"router": _dense_init(k[2], (D, cfg.n_experts), D, pdt)}
    if cfg.router == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), pdt)
    if cfg.shared_experts:
        p["shared"] = _ffn_params(
            jax.random.split(jax.random.fold_in(k[2], 1), 3), cfg, (),
            cfg.shared_experts * F)
    if L:
        kl = jax.random.split(jax.random.fold_in(k[2], 2), 2)
        p["w_latent_in"] = _dense_init(kl[0], (D, L), D, pdt)
        p["w_latent_out"] = _dense_init(kl[1], (L, D), L, pdt)
    p.update(_ffn_params(k[3:6], cfg, (cfg.n_held,), F, L))
    return p


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

    def ffn(w_in, w_out):
        p = {"w_in": w_in, "w_out": w_out}
        if cfg.ffn == "swiglu":
            p["w_gate"] = w_in
        return p

    layers = []
    for i in range(cfg.n_layers):
        layer = {}
        a = cfg.attn_of(i)
        if cfg.has_mixer(i):
            layer["ln1"] = dict(norm)
            if a is None:
                layer["wqkv"] = P(None, None, m, None)   # heads over model
                layer["wo"] = P(m, None, None)           # row-parallel
                if cfg.qk_norm:
                    layer["q_norm"] = {"scale": P(m, None)}
                    layer["k_norm"] = {"scale": P(m, None)}
            else:
                # A mixer described by kind is held whole on every device
                # (data-parallel attention): no serving program shards it.
                layer.update(jax.tree.map(
                    lambda _: P(), jax.eval_shape(
                        lambda: _mixer_params(jax.random.PRNGKey(0), cfg,
                                              a))))
        if cfg.has_ffn(i):
            layer["ln2"] = dict(norm)
            if cfg.is_moe(i):
                layer["router"] = P()
                if cfg.router == "sigmoid":
                    layer["router_bias"] = P()
                if cfg.shared_experts:
                    layer["shared"] = ffn(P(None, m), P(m, None))
                if cfg.expert_latent:   # shared by the experts: everywhere
                    layer["w_latent_in"] = layer["w_latent_out"] = P()
                layer.update(ffn(P(e, None, m), P(e, m, None)))
            else:
                layer.update(ffn(P(None, m), P(m, None)))
        layers.append(layer)
    specs = {
        "embed": P(m, None),
        "final_ln": dict(norm),
        "layers": layers,
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

# The named scopes below (embed, layer_norm / rms_norm, attention, mlp with
# experts inside it, loss, head; and grad, grad_reduce, optimizer in
# parallel/data_parallel.make_train_step) are metadata only: they reach every
# HLO operation's op_name, so a step's device time reads by scope
# (docs/observability.md). They change no instruction and no program name;
# observability/scopes.py is the one list of their names.

def _layer_norm(x, p, eps=1e-5):
    with jax.named_scope(scopes.LAYER_NORM):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _rms_norm(x, p, eps, axes=(-1,), plus_one=False):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over ``axes``, in float32;
    ``plus_one``: times ``1 + scale``."""
    with jax.named_scope(scopes.RMS_NORM):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axes, keepdims=True) + eps)
        scale = p["scale"].astype(jnp.float32)
        return (y * (1.0 + scale if plus_one else scale)).astype(x.dtype)


def _norm(x, p, cfg):
    if cfg.norm == "rmsnorm":
        return _rms_norm(x, p, cfg.norm_eps, plus_one=cfg.norm_plus_one)
    return _layer_norm(x, p, cfg.norm_eps)


def _rope(x, positions, theta):
    """Rotary positions on ``x [B, S, H, dh]`` at ``positions [B, S]``: each
    head's first and second half paired (rotate-half), angle
    ``pos * theta^(-2i/dh)``; computed in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (
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
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def rope_inv_freq(a):
    """The inverse frequencies ``[rope_dim / 2]`` (float64 numpy) of a
    described kind's rotation (multi-head or latent: ``rope_dim``,
    ``rope_theta``, ``yarn``) and the factor on its cos and sin: plain
    ``theta^(-2i / rope_dim)``, or YaRN's blend (:class:`Yarn`)."""
    import numpy as np

    dim = a.rope_dim
    plain = a.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = a.yarn
    if y is None:
        return plain, 1.0

    def turns_dim(turns):     # the dim that turns ``turns`` times in all
        return dim * math.log(y.original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(a.rope_theta))

    low = max(math.floor(turns_dim(y.beta_fast)), 0)
    high = min(math.ceil(turns_dim(y.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high if high > low else low + 0.001) - low), 0, 1)
    factor = y.attention_factor if y.attention_factor is not None \
        else 0.1 * math.log(y.factor) + 1.0
    return plain / y.factor * ramp + plain * (1 - ramp), float(factor)


def _rope_kind(x, positions, a):
    """A described kind's rotation of ``x [B, S, H, head_dim]`` at
    ``positions [B, S]``: the first ``rope_dim`` dims of each head, first
    and second half of them paired; float32."""
    if not a.rope_dim:          # a kind that rotates nothing
        return x
    inv_freq, factor = rope_inv_freq(a)
    half = a.rope_dim // 2
    ang = positions.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:a.rope_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            xf[..., a.rope_dim:]], -1).astype(x.dtype)


def _qkv_kind(h, layer, cfg, a: MultiHeadAttention, positions=None):
    """A described multi-head layer's ``q [B, S, n_heads, dh]`` and ``k, v
    [B, S, n_kv_heads, dh]`` from the normed input, rotated by the kind's
    rule to ``positions [B, S]`` (None = 0..S-1): what the attention and the
    cache take. ``k`` and ``v`` are None for a layer that attends another's
    (``kv_from``). A differential kind's come out as its attention takes them
    (``a.attended``): the key/value heads in pairs side by side, every query
    as wide as a pair with zeros in the other head's half, and times
    ``sqrt(2)`` (in float32, before it is rounded) so that the attention's
    ``1 / sqrt(2 dh)`` is the kind's ``1 / sqrt(dh)``."""
    dt = cfg.compute_dtype
    if a.bias or a.differential or a.query_mult != 1.0:
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt),
                       preferred_element_type=jnp.float32)
        if a.bias:
            q = q + layer["bq"].astype(jnp.float32)
        q = (q * math.sqrt(2.0) if a.differential
             else q * a.query_mult).astype(dt)
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
    if a.qk_head_norm:
        q = _rms_norm(q, layer["q_head_norm"], cfg.norm_eps,
                      plus_one=cfg.norm_plus_one)
    kv = _kv_kind(h, layer, cfg, a)
    if positions is None:
        positions = jnp.arange(h.shape[1])[None]
    q = _rope_kind(q, positions, a)
    if a.differential:
        first = (jnp.arange(a.n_heads) % 2 == 0)[:, None]
        zeros = jnp.zeros_like(q)
        q = jnp.where(first, jnp.concatenate([q, zeros], -1),
                      jnp.concatenate([zeros, q], -1))
    return (q, *_keys_values(kv, a, positions))


def _kv_kind(h, layer, cfg, a: MultiHeadAttention):
    """The key and value projections of a described multi-head layer: the
    fused ``[2, B, S, n_kv_heads, dh]`` (with its bias), or the pair ``(k [B,
    S, n_kv_heads, dh], v [B, S, n_kv_heads, v_dim])`` where a value head has
    a width of its own or a scale (``value_scale``: in float32, before the
    value is rounded); None where the layer attends another layer's."""
    if a.kv_from is not None:
        return None
    dt = cfg.compute_dtype
    if a.split_kv or a.value_scale != 1.0:
        wk, wv = ((layer["wk"], layer["wv"]) if a.split_kv
                  else (layer["wkv"][:, 0], layer["wkv"][:, 1]))
        v = jnp.einsum("bsd,dhk->bshk", h, wv.astype(dt),
                       preferred_element_type=jnp.float32)
        return (jnp.einsum("bsd,dhk->bshk", h, wk.astype(dt)),
                (v * a.value_scale).astype(dt))
    kv = jnp.einsum("bsd,dchk->cbshk", h, layer["wkv"].astype(dt))
    if a.bias:
        kv = kv + layer["bkv"].astype(dt)[:, None, None]
    if a.qk_head_norm:
        return (_rms_norm(kv[0], layer["k_head_norm"], cfg.norm_eps,
                          plus_one=cfg.norm_plus_one), kv[1])
    return kv


def _keys_values(kv, a: MultiHeadAttention, positions):
    """``k`` (rotated) and ``v`` of :func:`_kv_kind`'s projection as the
    attention and the cache take them; (None, None) for None."""
    if kv is None:
        return None, None
    k, v = _rope_kind(kv[0], positions, a), kv[1]
    if a.differential:
        k, v = (x.reshape(*x.shape[:2], a.n_kv_heads // 2, 2 * a.head_dim)
                for x in (k, v))
    return k, v


def project_kv(h, layer, cfg, a: MultiHeadAttention, positions):
    """The ``k, v`` of :func:`_qkv_kind` alone: all that a fill which leaves
    the stack at this layer computes of it for the positions whose logits
    nobody reads."""
    return _keys_values(_kv_kind(h, layer, cfg, a), a, positions)


def differential_combine(o, layer, a: MultiHeadAttention, li, eps, dt):
    """Differential attention's difference from the attended pairs: ``o [B,
    S, n_heads, 2 dh]`` (head ``2p`` the first softmax of pair ``p``, head
    ``2p + 1`` the second, each over the pair's two value heads) -> ``[B, S,
    n_heads, dh]``: ``RMSNorm(a1 - lambda a2) * (1 - lambda_init)`` a pair,
    float32, laid out as the output projection's ``n_heads`` heads of
    ``dh``."""
    f32 = jnp.float32
    lam = layer["diff_lambda"].astype(f32)
    init = lambda_init(li)
    lam = (jnp.exp(jnp.sum(lam[0] * lam[1]))
           - jnp.exp(jnp.sum(lam[2] * lam[3])) + init)
    o = o.astype(f32)
    d = o[:, :, 0::2] - lam * o[:, :, 1::2]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps)
    d = d * layer["diff_norm"]["scale"].astype(f32) * (1.0 - init)
    return d.reshape(*o.shape[:2], a.n_heads, a.head_dim).astype(dt)


def grouped_attend(q, k, v, a: MultiHeadAttention, allowed, dt, sink=None):
    """Grouped-query attention with materialised scores: ``q [B, S, Hq,
    dh]`` against ``k [B, T, Hkv, dh]``, ``v [B, T, Hkv, dv]`` under ``allowed
    [B, S, T]`` (or ``[B, Hkv, S, T]``, a key/value head's group its own: a
    block selection) -> ``[B, S, Hq, dv]`` (zeros for a query that is allowed
    nothing). ``sink [Hq]``: a head's scalar joins its rows' denominators (a
    column appended to the scores, dropped after the softmax). The plain
    tier: the trainer's forward pass, a CPU, a mesh, and what the paged
    kernels are tested against."""
    B, S, Hq, dh = q.shape
    qg = q.reshape(B, S, a.n_kv_heads, a.group, dh)
    logits = jnp.einsum("bsgjk,btgk->bgjst", qg, k,
                        preferred_element_type=jnp.float32) \
        / math.sqrt(dh)
    ok = allowed[:, None, None] if allowed.ndim == 3 else allowed[:, :, None]
    logits = jnp.where(ok, logits, -1e30)
    if sink is None:
        probs = jax.nn.softmax(logits, -1)
    else:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(a.n_kv_heads, a.group, 1, 1),
            logits.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([logits, col], -1),
                               -1)[..., :-1]
    probs = jnp.where(ok, probs, 0.0).astype(dt)
    return jnp.einsum("bgjst,btgk->bsgjk", probs, v).reshape(
        B, S, Hq, v.shape[-1])


def attend_allowed(a, q_pos, k_pos, live=None):
    """Which keys a described layer's queries may see (before any
    selection): ``q_pos [B, S]``, ``k_pos [B, T]`` -> ``[B, S, T]``: not
    later than the query, inside its window, and ``live [B, T]``."""
    dist = q_pos[:, :, None] - k_pos[:, None, :]
    ok = dist >= 0
    if a.window:
        ok &= dist < a.window
    if live is not None:
        ok &= live[:, None, :]
    return ok


def _attend_kind(a, dt):
    """``attend(q, k, v)`` of a described multi-head layer over its own
    window (no cache): the forward pass of the trainer and of the tests."""
    def attend(q, k, v, sink=None, index=None):
        pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], k.shape[:2])
        allowed = attend_allowed(a, pos, pos)
        if index is None:
            return grouped_attend(q, k, v, a, allowed, dt, sink)
        with jax.named_scope(scopes.BLOCK_INDEX):
            scores = block_scores(index["q"], index["w"],
                                  pool_blocks(index["k"], a.select_block))
        with jax.named_scope(scopes.BLOCK_SELECT):
            chosen = select_blocks(scores, pos, a)
            allowed = allowed[:, None] & blocks_allowed(chosen, pos, pos, a)
        with jax.named_scope(scopes.BLOCK_ATTENTION):
            return grouped_attend(q, k, v, a, allowed, dt), chosen

    return attend


def block_index(h, layer, cfg, a: MultiHeadAttention):
    """A selecting layer's indexer operands from the normed input ``h [B, S,
    D]``: ``{"q": [B, S, G, J, d] indexer queries, "k": [B, S, G, d] this
    position's indexer key (what its block's pooled row takes the maximum
    of), "w": [B, S, G, J] float32 head weights, times (J d)^-1/2}``, a
    key/value group ``G`` each; nothing is rotated."""
    dt = cfg.compute_dtype
    w = jnp.einsum("bsd,dgj->bsgj", h, layer["wi_w"].astype(dt)).astype(
        jnp.float32) / math.sqrt(a.index_heads * a.index_dim)
    return {"q": jnp.einsum("bsd,dgjk->bsgjk", h, layer["wi_q"].astype(dt)),
            "k": jnp.einsum("bsd,dgk->bsgk", h, layer["wi_k"].astype(dt)),
            "w": w}


def pool_blocks(k_i, block):
    """The pooled rows of a window that starts its sequence: ``k_i [B, S, G,
    d]`` -> ``[B, ceil(S / block), G, d]``, the elementwise maximum over each
    block's positions (a last block that is not whole over those it has)."""
    B, S = k_i.shape[:2]
    pad = -S % block
    low = jnp.asarray(-jnp.inf, k_i.dtype)
    k_i = jnp.pad(k_i, ((0, 0), (0, pad), (0, 0), (0, 0)),
                  constant_values=low)
    return jnp.max(k_i.reshape(B, -1, block, *k_i.shape[2:]), axis=2)


def block_scores(q_i, w, pooled):
    """The block selection's scores ``I [B, S, G, N] = sum_j w_j relu(q_j .
    pooled_n)`` (float32) of indexer queries ``q_i [B, S, G, J, d]``, head
    weights ``w [B, S, G, J]`` and pooled rows ``pooled [B, N, G, d]``; a row
    nobody wrote scores whatever it holds, and :func:`select_blocks` never
    reads it."""
    per_head = jnp.einsum("bsgjd,bngd->bsgjn", q_i, pooled,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bsgjn,bsgj->bsgn", jax.nn.relu(per_head), w)


def select_blocks(scores, q_pos, a: MultiHeadAttention):
    """The learned choice: ``scores [B, S, G, N]``, ``q_pos [B, S]`` -> ``[B,
    S, G, select_topk]`` int32, the blocks of highest score among a query's
    candidates (the WHOLE blocks behind the first ones and before the local
    ones, ``select_first <= n <= bt - select_local``), ties to the lower
    index, ``-1`` where fewer are candidates."""
    n = jnp.arange(scores.shape[-1])
    last = q_pos // a.select_block - a.select_local                  # [B, S]
    candidate = (n >= a.select_first) & (n <= last[..., None, None])
    val, idx = jax.lax.top_k(jnp.where(candidate, scores, -jnp.inf),
                             min(a.select_topk, scores.shape[-1]))
    return jnp.where(val > -jnp.inf, idx, -1).astype(jnp.int32)


def blocks_allowed(chosen, q_pos, k_pos, a: MultiHeadAttention):
    """Which keys a selecting layer's queries may see by their BLOCK:
    ``chosen [B, S, G, k]`` (:func:`select_blocks`), ``q_pos [B, S]``,
    ``k_pos [B, T]`` -> ``[B, G, S, T]``: the first blocks, the local ones
    and the chosen ones (causality and liveness are :func:`attend_allowed`'s).
    """
    kb = (k_pos // a.select_block)[:, None, None, :]              # [B,1,1,T]
    bt = (q_pos // a.select_block)[:, None, :, None]              # [B,1,S,1]
    fixed = (kb < a.select_first) | (kb > bt - a.select_local)
    picked = jnp.any(chosen.transpose(0, 2, 1, 3)[..., None, :]
                     == kb[..., None], -1)                        # [B,G,S,T]
    return fixed | picked


def _rope_head(x, positions, theta, width):
    """RoPE on the first ``width`` dims of ``x [B, S, ..., d]``."""
    flat = x.reshape(*x.shape[:2], -1, x.shape[-1])
    turned = _rope(flat[..., :width], positions, theta)
    return jnp.concatenate([turned, flat[..., width:]], -1).reshape(x.shape)


def _rope_latent(x, positions, a: LatentAttention):
    """A latent kind's rotation of ``x [B, S, H, rope_dim]``: plain, or at
    YaRN's frequencies where the kind has them."""
    if a.yarn is None:
        return _rope(x, positions, a.rope_theta)
    return _rope_kind(x, positions, a)


def _latent_qkv(h, layer, cfg, a: LatentAttention, positions=None):
    """A latent layer's attention operands from the normed input ``h [B, S,
    D]``, for BOTH forms of the same attention: -> ``(q [B, S, H, row_width],
    row [B, S, row_width], index, q_heads [B, S, H, nope_dim + row_width -
    kv_rank])``. An ``attend`` reads the form it runs, and what it leaves
    unread is dead code to the compiler.

    ``row`` is what the cache holds of a token: the key/value latent after
    its norm (and scale), the shared key dims after the rotation to
    ``positions``, zeros up to ``row_width``.

    ``q`` is each head's query against such rows, the ABSORBED form: its
    ``nope_dim`` part multiplied through the head's key up-projection into
    the latent's ``kv_rank`` dims (so that no head's keys are ever made), its
    rotated part, zeros: ``q . row`` is the head's logit before the scale,
    and the probabilities times ``row[..., :kv_rank]`` go through the value
    up-projection after the attention (:func:`latent_values`).

    ``q_heads`` is each head's query as projected, the EXPANDED form: its
    ``nope_dim`` part against the head's own keys (a row's latent times
    ``wkv_b``), then what meets a row's tail (the rotated part, zeros).

    Which form is cheaper follows from the number of queries that attend the
    same rows. Per (query, key) a head costs ``row_width + kv_rank``
    multiply-adds absorbed against ``nope + rope + v`` expanded, 3.4 x the
    operations at the published widths; but the expanded form first makes
    every attended row's keys and values, ``kv_rank * (nope + v)`` a head a
    row, as much as 120 pairs. One query a slot (a decode step), or rows
    that differ query by query (a selection), attend absorbed; a block of
    some hundreds of queries over one context is cheaper expanded
    (``ops/pallas_latent.py`` ``expands`` has the rule; the serving engine's
    chunk program takes it).

    ``index`` (a layer with a selection): ``{"q": [B, S, J, d] scorer
    queries, "k": [B, S, d] this token's scorer key (what the cache holds),
    "w": [B, S, J] float32 head weights}``, else None."""
    dt = cfg.compute_dtype
    if positions is None:
        positions = jnp.arange(h.shape[1])[None]
    a_kv = math.sqrt(cfg.d_model / a.kv_rank) if cfg.latent_rescale else 1.0
    if a.q_rank:
        a_q = math.sqrt(cfg.d_model / a.q_rank) if cfg.latent_rescale else 1.0
        c_q = _rms_norm(
            jnp.einsum("bsd,dr->bsr", h, layer["wq_a"].astype(dt)),
            layer["q_norm"], cfg.norm_eps)
        c_q = (c_q * a_q).astype(dt)
        q = jnp.einsum("bsr,rhd->bshd", c_q, layer["wq_b"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
    if a.q_head_norm:
        q = _rms_norm(q, layer["q_head_norm"], cfg.norm_eps)
    q_rope = _rope_latent(q[..., a.nope_dim:], positions, a)
    kv = jnp.einsum("bsd,dr->bsr", h, layer["wkv_a"].astype(dt))
    c_kv = _rms_norm(kv[..., :a.kv_rank], layer["kv_norm"], cfg.norm_eps)
    c_kv = (c_kv * a_kv).astype(dt)
    k_r = _rope_latent(kv[:, :, None, a.kv_rank:], positions, a)[:, :, 0]
    q_lat = jnp.einsum("bshd,rhd->bshr", q[..., :a.nope_dim],
                       layer["wkv_b"][..., :a.nope_dim].astype(dt))
    pad = a.row_width - a.kv_rank - a.rope_dim
    q_tail = [q_rope, jnp.zeros((*q.shape[:3], pad), dt)]
    q_abs = jnp.concatenate([q_lat, *q_tail], -1)
    q_heads = jnp.concatenate([q[..., :a.nope_dim], *q_tail], -1)
    row = jnp.concatenate(
        [c_kv, k_r, jnp.zeros((*kv.shape[:2], pad), dt)], -1)
    index = None
    if a.index_topk:
        q_i = jnp.einsum("bsr,rjd->bsjd", c_q, layer["wi_q"].astype(dt))
        k_i = _layer_norm(
            jnp.einsum("bsd,dk->bsk", h, layer["wi_k"].astype(dt)),
            layer["i_norm"], INDEX_NORM_EPS)
        w = jnp.einsum("bsd,dj->bsj", h, layer["wi_w"].astype(dt)).astype(
            jnp.float32) / math.sqrt(a.index_heads * a.index_dim)
        index = {
            "q": _rope_head(q_i, positions, a.rope_theta, a.index_rope_dim),
            "k": _rope_head(k_i, positions, a.rope_theta, a.index_rope_dim),
            "w": w}
    return q_abs, row, index, q_heads


# The eps of the selection scorer's LayerNorm (DeepSeek-V3.2's indexer).
INDEX_NORM_EPS = 1e-6


def index_scores(q_i, w, k_i, allowed):
    """The selection's scores ``I [B, S, T] = sum_j w_j relu(q_j . k)``
    (float32) of scorer queries ``q_i [B, S, J, d]``, head weights ``w [B,
    S, J]`` and keys ``k_i [B, T, d]``; ``-inf`` where ``allowed [B, S, T]``
    is false."""
    per_head = jnp.einsum("bsjd,btd->bsjt", q_i, k_i,
                          preferred_element_type=jnp.float32)
    scores = jnp.einsum("bsjt,bsj->bst", jax.nn.relu(per_head), w)
    return jnp.where(allowed, scores, -jnp.inf)


def select_keys(scores, k):
    """The ``k`` best-scoring keys of every query: ``scores [.., T]``
    (``-inf`` = not allowed) -> ``[.., min(k, T)]`` int32 key indices, ``-1``
    where fewer than ``k`` keys are allowed."""
    val, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return jnp.where(val > -jnp.inf, idx, -1)


def latent_attend(q, rows, a: LatentAttention, allowed, dt):
    """Absorbed latent attention with materialised scores: ``q [B, S, H,
    W]`` against ``rows [B, T, W]`` under ``allowed [B, S, T]`` ->
    ``[B, S, H, kv_rank]`` (the probabilities times the rows' latent part;
    zeros for a query that is allowed nothing). The plain tier: the
    trainer's forward pass, a CPU, a mesh."""
    logits = jnp.einsum("bshw,btw->bhst", q, rows,
                        preferred_element_type=jnp.float32) * a.softmax_scale
    logits = jnp.where(allowed[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1)
    probs = jnp.where(allowed[:, None], probs, 0.0).astype(dt)
    return jnp.einsum("bhst,btr->bshr", probs, rows[..., :a.kv_rank])


def latent_values(o, wkv_b, a: LatentAttention):
    """Absorbed attention's output ``o [B, S, H, kv_rank]`` (probabilities
    times the rows' latent part) through the value up-projection of ``wkv_b
    [kv_rank, H, nope_dim + v_dim]`` -> ``[B, S, H, v_dim]``."""
    return jnp.einsum("bshr,rhd->bshd", o, wkv_b[..., a.nope_dim:])


def _attend_latent(a, dt):
    """``attend(q, row, index, q_heads, wkv_b)`` of a latent layer over its
    own window (no cache), absorbed: the forward pass of the trainer and of
    the tests. -> (output ``[B, S, H, v_dim]``, the selection or None)."""
    def attend(q, row, index, q_heads, wkv_b):
        pos = jnp.broadcast_to(jnp.arange(row.shape[1])[None], row.shape[:2])
        allowed = attend_allowed(a, pos, pos)
        selected = None
        if index is not None:
            selected = select_keys(
                index_scores(index["q"], index["w"], index["k"], allowed),
                a.index_topk)
            allowed = selection_mask(selected, row.shape[1])
        o = latent_attend(q, row, a, allowed, dt)
        return latent_values(o, wkv_b, a), selected

    return attend


def selection_mask(selected, n_keys):
    """``selected [B, S, k]`` key indices (``-1`` = none) -> ``[B, S,
    n_keys]`` bool."""
    b, s, _ = selected.shape
    hit = jnp.zeros((b, s, n_keys + 1), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        jnp.where(selected >= 0, selected, n_keys)].set(True)
    return hit[..., :n_keys]


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


def _gated(gate, h, cfg):
    """The gated hidden activation from the gate and up projections: ``silu
    (gate) h``, or under ``swiglu_limit`` the clamped form ``g sigmoid(alpha
    g) (u + 1)`` with ``g = min(gate, limit)``, ``u = clip(h, -limit,
    limit)``."""
    if not cfg.swiglu_limit:
        return jax.nn.silu(gate) * h
    limit = cfg.swiglu_limit
    gate = jnp.minimum(gate, limit)
    return (gate * jax.nn.sigmoid(cfg.swiglu_alpha * gate)
            * (jnp.clip(h, -limit, limit) + 1))


def _activation(h, layer, x, cfg, eq):
    """The feed-forward's hidden activation from the up projection ``h``:
    GELU of it, its ReLU squared, or the gated form of the gate projection
    and it (:func:`_gated`)."""
    if cfg.ffn == "swiglu":
        gate = jnp.einsum(eq, x, layer["w_gate"].astype(cfg.compute_dtype))
        return _gated(gate, h, cfg)
    if cfg.ffn == "relu2":
        return jnp.square(jax.nn.relu(h))
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
    precision of the reference. ``router="sigmoid"``: sigmoid scores, the
    ``top_k`` of score + ``router_bias``, the weights the chosen scores
    (the bias chooses and does not weigh), times ``routed_scale``."""
    gates = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                       layer["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(gates)
        _, top = jax.lax.top_k(
            scores + layer["router_bias"].astype(jnp.float32), cfg.top_k)
        w = jnp.take_along_axis(scores, top, -1)
    else:
        w, top = jax.lax.top_k(jax.nn.softmax(gates, -1), cfg.top_k)
    if cfg.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    if cfg.routed_scale != 1.0:
        w = w * cfg.routed_scale
    return w, top


def _held(top, cfg):
    """``top [.., k]`` experts -> (their places among the experts held
    here, ``n_held`` for one that is elsewhere; which are held)."""
    offset, count = cfg.experts_held
    local = top - offset
    held = (local >= 0) & (local < count)
    return jnp.where(held, local, count), held


def _moe_dense(x, w, top, layer, cfg):
    """Dense dispatch: every expert for every token, combined through the
    routing weights as a ``[b, s, E]`` mask — compilable under any mesh,
    exact. Expert weights are ep-sharded; XLA turns the einsum over the
    expert dim into compute local to each expert shard plus a psum. Costs
    ``n_experts / top_k`` times the routed work; the bandwidth-optimal
    alltoall dispatch is in horovod_tpu.parallel.expert_parallel."""
    dt = cfg.compute_dtype
    if cfg.experts_held:      # an expert elsewhere one-hots to no column
        top = _held(top, cfg)[0]
    combine = jnp.sum(jax.nn.one_hot(top, cfg.n_held, dtype=jnp.float32)
                      * w[..., None], -2).astype(dt)             # [b,s,E]
    h = jnp.einsum("bsd,edf->bsef", x, layer["w_in"].astype(dt))
    h = _activation(h, layer, x, cfg, "bsd,edf->bsef")
    y = jnp.einsum("bsef,efd->bsed", h, layer["w_out"].astype(dt))
    return jnp.einsum("bsed,bse->bsd", y, combine)


# Rows of one block of the held experts' products. XLA's TPU ragged dot
# takes its row tile from the number of rows it is given, the largest power
# of two that divides it and 512 at most (``ragged_dot_tiling`` in the
# compiled text); every (expert, tile) pair it visits multiplies a whole
# tile, and a tile of no expert's rows is not visited: rows behind the
# groups cost nothing. Where a chip holds an eighth of the experts a group
# is 16-20 rows of a chunk, so under tiles of 512 (any multiple of 512 rows:
# a chunk's 5120 or 4096, or a block of 1024) the product is bound by the
# MXU at 25 rows multiplied for one. 3 x 256 rows take tiles of 256 and
# hold a chunk's held eighth (640 +- 24 of 5120, 512 +- 21 of 4096) in one
# block. Device ms on a TPU v5 lite (PERF.md section 6, PR 38). One product
# [rows, 3072] x [32, 3072, 1024], 640 rows in 32 groups: 0.704 at 5120
# rows, 0.700 at 1024, 0.412 at 640, 896, 1152 and 5248. One expert layer of
# a 512-token chunk, Laguna's / dots3's shape, 640 / 521 pairs held: the
# whole tail 2.52 / 5.70; blocks of 384 2.19 / 4.55, 640 2.08 / 4.67, 768
# 2.04 / 4.73, 896 2.14 / 4.66, 1024 the whole tail's, 1152 2.21 / 4.74,
# 1280 2.00 / 5.03.
_HELD_BLOCK = 768


def _expert_products(rows, sizes, layer, cfg):
    """The experts' feed-forward of ``rows [R, D]`` sorted by expert,
    ``sizes [E]`` rows each: one ``jax.lax.ragged_dot`` per projection."""
    dt = cfg.compute_dtype
    h = jax.lax.ragged_dot(rows, layer["w_in"].astype(dt), sizes)
    if cfg.ffn == "swiglu":
        gate = jax.lax.ragged_dot(rows, layer["w_gate"].astype(dt), sizes)
        h = _gated(gate, h, cfg)
    elif cfg.ffn == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jax.lax.ragged_dot(h, layer["w_out"].astype(dt), sizes)


def _held_blocks(flat, order, sizes, layer, cfg):
    """The products of the sorted pairs that precede ``sizes.sum()``, a
    block of ``_HELD_BLOCK`` rows at a time: -> (``[T*k, D]`` with zero rows
    behind them, the rows multiplied). The count stays on the device and
    every block that holds a pair runs (all of them where every pair is
    held): nothing is dropped. The last block is moved back to end with the
    rows, so a few rows may be multiplied twice, to the same result. Not
    differentiable in reverse (the trip count is traced)."""
    total, block = order.shape[0], _HELD_BLOCK
    n = sizes.sum()
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    def one(i, y):
        lo = jnp.minimum(i * block, total - block)
        mine = jnp.clip(ends, lo, lo + block) - jnp.clip(starts, lo, lo + block)
        at = jax.lax.dynamic_slice_in_dim(order, lo, block)
        out = _expert_products(flat[at // cfg.top_k], mine, layer, cfg)
        # A row of no group holds whatever was there.
        out = jnp.where((lo + jnp.arange(block) < n)[:, None], out, 0)
        return jax.lax.dynamic_update_slice_in_dim(y, out, lo, 0)

    blocks = (n + block - 1) // block
    y = jax.lax.fori_loop(
        0, blocks, one, jnp.zeros((total, flat.shape[1]), cfg.compute_dtype))
    return y, blocks * block


def _moe_grouped(x, w, top, layer, cfg):
    """Grouped dispatch: the ``tokens x top_k`` routed (token, expert) pairs
    sorted by expert, one ``jax.lax.ragged_dot`` per projection over the
    sorted rows, the results unsorted and summed per token under the
    routing weights: -> (output, the rows the products ran over). Work and
    (for few tokens) weight bytes follow the pairs and the experts they
    touch; nothing is dropped and no capacity exists. On a TPU each product
    is one ``ragged-dot`` instruction of the compiled program (a Mosaic
    kernel XLA brings): the name a trace reads (docs/observability.md).

    Under ``experts_held`` the pairs routed to an expert elsewhere sort
    behind every group and belong to none: their weight is zero, and of
    more rows than one block the products run over the blocks that hold a
    pair (:func:`_held_blocks`; the same instructions, inside a ``while``)."""
    B, S, D = x.shape
    k, E = cfg.top_k, cfg.n_held
    if cfg.experts_held:
        top, held = _held(top, cfg)
        w = jnp.where(held, w, 0.0)
    experts = top.reshape(-1)                                     # [T*k]
    order = jnp.argsort(experts, stable=True)
    flat, ran = x.reshape(-1, D), experts.shape[0]
    blocked = bool(cfg.experts_held) and ran > _HELD_BLOCK
    rows = None if blocked else flat[order // k]                  # [T*k, D]
    sizes = jnp.bincount(experts, length=E).astype(jnp.int32)
    with jax.named_scope(scopes.EXPERTS):
        if blocked:
            y, ran = _held_blocks(flat, order, sizes, layer, cfg)
        else:
            y = _expert_products(rows, sizes, layer, cfg)
            if cfg.experts_held:      # as in a block: rows of no group
                y = jnp.where(
                    (jnp.arange(ran) < sizes.sum())[:, None], y, 0)
    y = y[jnp.argsort(order)].reshape(B, S, k, D)
    return jnp.einsum("bskd,bsk->bsd", y, w.astype(cfg.compute_dtype)), ran


def _moe_ffn(x, layer, cfg, mesh=None, valid=None):
    """Top-k routed MoE, dropless: -> (output ``[b, s, D]``, routing).
    On one device (``mesh`` None) the experts take the grouped form
    (:func:`_moe_grouped`), prefill and decode alike; under a mesh the dense
    form (:func:`_moe_dense`), which XLA shards over the ``expert`` and
    ``model`` axes and which is right, at ``n_experts / top_k`` times the
    work: a grouped product under an expert-sharded mesh is not written.

    The routing is ``{"top": [b, s, k] experts, "counts": [n_held], "rows":
    []}``: the (token, expert) pairs each expert held here received from the
    rows ``valid [b, s]`` marks (all by default), and the rows the experts'
    products ran over (grouped: the sorted pairs given to them; dense: every
    token for every expert): what ``serve_stats()["moe"]`` counts. A shared
    expert (``shared_experts``) is added for every row. Under
    ``expert_latent`` the routed experts read ``x W_latent_in`` and their
    weighted sum goes through ``W_latent_out``; the router and the shared
    expert read ``x`` itself."""
    dt = cfg.compute_dtype
    w, top = _route(x, layer, cfg)
    rows_in = x
    if cfg.expert_latent:
        with jax.named_scope(scopes.EXPERT_LATENT):
            rows_in = jnp.einsum("bsd,dl->bsl", x,
                                 layer["w_latent_in"].astype(dt))
    if mesh is None:
        y, rows = _moe_grouped(rows_in, w, top, layer, cfg)
    else:
        y = _moe_dense(rows_in, w, top, layer, cfg)
        rows = top[..., 0].size * cfg.n_held
    if cfg.expert_latent:
        with jax.named_scope(scopes.EXPERT_LATENT):
            y = jnp.einsum("bsl,ld->bsd", y, layer["w_latent_out"].astype(dt))
    if cfg.shared_experts:
        y = y + _ffn(x, layer["shared"], cfg)
    # Counted: the pairs this device computes (all of them, or those of the
    # experts it holds: one elsewhere one-hots to no column).
    mine = _held(top, cfg)[0] if cfg.experts_held else top
    hit = jax.nn.one_hot(mine, cfg.n_held, dtype=jnp.int32)     # [b,s,k,E]
    if valid is not None:
        hit = hit * valid[..., None, None]
    return y, {"top": top, "counts": hit.sum((0, 1, 2)),
               "rows": jnp.asarray(rows, jnp.int32)}


def _ssd_step(x, step, rate, b_in, c_out, state):
    """:func:`_ssd_blocks` for a window of ONE position: ``S = exp(step
    rate) S + step x (x) B`` and ``y = S C``, elementwise over the state and
    one sum over its last axis, so that a decode step reads a slot's state
    once and writes it once, in place. (The blocked form at a block of one
    makes the outer product a product of its own and reads the state twice:
    0.5 GB more a layer at 128 slots.)"""
    f32 = jnp.float32
    B, _, H, P = x.shape
    G, N = b_in.shape[2:]
    step = step[:, 0].astype(f32)                                    # [B, H]
    dx = (x[:, 0].astype(f32) * step[..., None]).reshape(B, G, H // G, P)
    kept = jnp.exp(step * rate.astype(f32)).reshape(B, G, H // G)
    state = state.astype(f32).reshape(B, G, H // G, P, N)
    state = state * kept[..., None, None] \
        + dx[..., None] * b_in[:, 0].astype(f32)[:, :, None, None, :]
    y = jnp.sum(state * c_out[:, 0].astype(f32)[:, :, None, None, :], -1)
    return y.reshape(B, 1, H, P), state.reshape(B, H, P, N)


def _ssd_blocks(x, step, rate, b_in, c_out, state, block):
    """The state-space recurrence of a window in its chunked form (Mamba-2's
    state-space duality, arXiv:2405.21060 section 6), float32 throughout.

    ``x [B, S, H, P]`` inputs, ``step [B, S, H]`` (0 = a position that leaves
    the state alone), ``rate [H]`` (negative), ``b_in, c_out [B, S, G, N]``,
    ``state [B, H, P, N]`` entering -> (``y [B, S, H, P]``, the state
    leaving). Per head, with ``a_t = step_t * rate``: ``S_t = exp(a_t) S_{t-1}
    + step_t x_t (x) B_t`` and ``y_t = S_t C_t``. Inside a block of ``block``
    positions the sum over earlier positions is two matrix products (``C
    B^T`` under the decays, then times ``step x``); a block's effect on the
    state is one more, and the blocks are chained by a scan over their
    states. A window of one is the recurrence itself, the one-token update
    (:func:`_ssd_step`): the same values with no product to make."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    B, S, H, P = x.shape
    G, N = b_in.shape[2:]
    if S == 1:
        return _ssd_step(x, step, rate, b_in, c_out, state)
    Q = min(block, S)
    nb = -(-S // Q)
    pad = [(0, 0), (0, nb * Q - S)]

    def blocks(v):     # [B, S, ..] -> [B, nb, Q, ..], dead positions behind
        v = jnp.pad(v.astype(f32), pad + [(0, 0)] * (v.ndim - 2))
        return v.reshape(B, nb, Q, *v.shape[2:])

    step = blocks(step)                                          # [B,nb,Q,H]
    dx = (blocks(x) * step[..., None]).reshape(B, nb, Q, G, H // G, P)
    b_in, c_out = blocks(b_in), blocks(c_out)                    # [B,nb,Q,G,N]
    cs = jnp.cumsum(step * rate.astype(f32), axis=2)             # [B,nb,Q,H]
    # Inside a block: position q takes exp(cs_q - cs_s) (C_q . B_s) of s <= q.
    cs_h = cs.transpose(0, 1, 3, 2)                              # [B,nb,H,Q]
    seg = cs_h[..., :, None] - cs_h[..., None, :]                # [.., q, s]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", c_out, b_in, precision=hi)
    mix = cb[:, :, :, None] * decay.reshape(B, nb, G, H // G, Q, Q)
    y = jnp.einsum("bcghqs,bcsghp->bcqghp", mix, dx, precision=hi)
    # A block's own contribution to the state at its end, and its decay.
    to_end = jnp.exp(cs[:, :, -1:] - cs).reshape(B, nb, Q, G, H // G)
    grown = jnp.einsum("bcsgn,bcsghp->bcghpn", b_in, dx * to_end[..., None],
                       precision=hi)                       # [B,nb,G,H/G,P,N]
    kept = jnp.exp(cs[:, :, -1]).reshape(B, nb, G, H // G)

    def chain(s, xs):          # -> the state leaving, the state entering
        kept_c, grown_c = xs
        return s * kept_c[..., None, None] + grown_c, s

    state, entering = jax.lax.scan(
        chain, state.astype(f32).reshape(B, G, H // G, P, N),
        (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(grown, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                # [B,nb,G,H/G,P,N]
    carried = jnp.einsum("bcqgn,bcghpn->bcqghp", c_out, entering,
                         precision=hi)
    y = y + carried * jnp.exp(cs).reshape(B, nb, Q, G, H // G)[..., None]
    return (y.reshape(B, nb * Q, H, P)[:, :S], state.reshape(B, H, P, N))


def state_space_mix(u, layer, a: StateSpaceMixer, cfg, tail=None, state=None,
                    live=None, recur=None):
    """THE state-space mixer, written once: the normed input ``u [B, S, D]``
    of a window of ``S`` consecutive tokens a sequence -> (``out [B, S, D]``,
    the convolution tail leaving ``[B, conv_kernel - 1, conv_dim]`` in the
    compute dtype, the state leaving ``[B, H, P, N]`` float32).

    ``tail`` and ``state`` are what the sequence carried in (None = a
    sequence that starts here: zeros). ``live [B, S]`` marks the window's
    real positions, which come FIRST (padding is behind the tokens; an
    inactive slot has none): a dead position advances neither the tail nor
    the state, and its output is garbage nobody reads. `forward` calls this
    with no state over the whole sequence, the serving chunk program with the
    slot's, the decode step with a window of one.

    ``recur(x, step, rate, b_in, c_out) -> (y, the state leaving)`` stands in
    for the recurrence where the caller owns the state and ``state`` is None
    (``serving/engine.py`` ``_state_layer``): the decode step's kernel over
    the layer's own array where it lies, the same values as :func:`_ssd_step`,
    and the chunk program's kernel of the chunked form with the slot's rows
    bound to it, the same values as :func:`_ssd_blocks`. Everything around it
    is this function's either way.

    ``[z | xBC | dt] = u W_in``; ``xBC`` through the depthwise causal
    convolution and SiLU; ``step = softplus(dt + dt_bias)``, ``rate =
    -exp(a_log)``; the recurrence (:func:`_ssd_blocks`) plus ``skip * x``;
    the gate ``silu(z)`` first and then an RMS norm over each group's
    channels; ``W_out``. The step, the decay, the state and the gated norm
    are float32; the projections and the convolution's inputs are the
    compute dtype's."""
    dt, f32 = cfg.compute_dtype, jnp.float32
    B, S, _ = u.shape
    H, P, G, N, K = (a.n_heads, a.head_dim, a.n_groups, a.state_size,
                     a.conv_kernel)
    if tail is None:
        tail = jnp.zeros((B, a.tail, a.conv_dim), dt)
    if recur is None:
        if state is None:
            state = jnp.zeros((B, H, P, N), f32)
        recur = functools.partial(_ssd_blocks, state=state, block=a.block)
    if live is None:
        live = jnp.ones((B, S), bool)
    zxd = jnp.einsum("bsd,dw->bsw", u, layer["w_ssm_in"].astype(dt))
    z = zxd[..., :a.d_inner]
    xbc = zxd[..., a.d_inner:a.d_inner + a.conv_dim]
    step = jax.nn.softplus(zxd[..., a.d_inner + a.conv_dim:].astype(f32)
                           + layer["dt_bias"].astype(f32))
    step = jnp.where(live[..., None], step, 0.0)
    # The convolution over the carried inputs and the window's; the tail
    # that leaves is the last live inputs.
    seq = jnp.concatenate([tail.astype(dt), xbc], 1)      # [B, K-1+S, C]
    at = jnp.sum(live, 1)[:, None] + jnp.arange(a.tail)[None]
    tail = jnp.take_along_axis(seq, at[..., None], axis=1)
    taps = layer["conv_w"].astype(f32)
    conv = layer["conv_b"].astype(f32) + sum(
        seq[:, j:j + S].astype(f32) * taps[:, j] for j in range(K))
    xbc = jax.nn.silu(conv).astype(dt)
    x = xbc[..., :a.d_inner].reshape(B, S, H, P)
    b_in = xbc[..., a.d_inner:a.d_inner + G * N].reshape(B, S, G, N)
    c_out = xbc[..., a.d_inner + G * N:].reshape(B, S, G, N)
    y, state = recur(x, step, -jnp.exp(layer["a_log"].astype(f32)), b_in,
                     c_out)
    y = y + layer["ssm_skip"].astype(f32)[:, None] * x.astype(f32)
    y = y.reshape(B, S, G, -1) * jax.nn.silu(z.astype(f32)).reshape(
        B, S, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    y = (y.reshape(B, S, -1)
         * layer["ssm_norm"]["scale"].astype(f32)).astype(dt)
    return (jnp.einsum("bsf,fd->bsd", y, layer["w_ssm_out"].astype(dt)),
            tail, state)


# Positions that :func:`selective_scan_mix` takes as one trip of its scan over
# a window; it changes no value.
_SCAN_BLOCK = 16


def _scan_step(x, step, rate, b_in, c_out, state):
    """The per-channel selective recurrence for ONE position, float32: ``x,
    step [B, C]``, ``rate [N, C]`` (negative), ``b_in, c_out [B, N]``, ``state
    [B, N, C]`` -> (``y [B, C]``, the state leaving): ``h = exp(step rate) h +
    (step x) B`` elementwise over (state, channel), ``y = sum_n h C``. A
    position whose ``step`` is 0 leaves the state bit for bit."""
    state = state * jnp.exp(step[:, None, :] * rate) \
        + (step * x)[:, None, :] * b_in[:, :, None]
    return jnp.sum(state * c_out[:, :, None], 1), state


def _scan_blocks(x, step, rate, b_in, c_out, state, block):
    """:func:`_scan_step` over a window, float32: ``x, step [B, S, C]``,
    ``b_in, c_out [B, S, N]``, ``state [B, N, C]`` entering -> (``y [B, S,
    C]``, the state leaving). The window is walked ``block`` positions a trip
    of one scan, each position the update itself, so nothing of the size of a
    state a position outlives a block (the ``[S, N, C]`` history of a window
    of 512 is 168 MB at 5120 channels of 16 states). A window of one is the
    update, with no scan around it."""
    f32 = jnp.float32
    B, S, C = x.shape
    x, step, b_in, c_out = (v.astype(f32) for v in (x, step, b_in, c_out))
    state, rate = state.astype(f32), rate.astype(f32)
    if S == 1:
        y, state = _scan_step(x[:, 0], step[:, 0], rate, b_in[:, 0],
                              c_out[:, 0], state)
        return y[:, None], state
    Q = min(block, S)
    nb = -(-S // Q)

    def blocks(v):     # [B, S, W] -> [nb, Q, B, W], dead positions behind
        v = jnp.pad(v, ((0, 0), (0, nb * Q - S), (0, 0)))
        return v.reshape(B, nb, Q, -1).transpose(1, 2, 0, 3)

    def trip(state, xs):
        ys = []
        for x_t, step_t, b_t, c_t in zip(*xs):
            y_t, state = _scan_step(x_t, step_t, rate, b_t, c_t, state)
            ys.append(y_t)
        return state, jnp.stack(ys)

    state, y = jax.lax.scan(trip, state, tuple(
        blocks(v) for v in (x, step, b_in, c_out)))
    return y.reshape(nb * Q, B, C).transpose(1, 0, 2)[:, :S], state


def selective_scan_mix(u, layer, a: SelectiveScanMixer, cfg, tail=None,
                       state=None, live=None, recur=None, with_memory=False):
    """THE per-channel selective state space (Mamba-1), written once: the
    normed input ``u [B, S, D]`` of a window of ``S`` consecutive tokens a
    sequence -> (``out [B, S, D]``, the convolution tail leaving ``[B,
    conv_kernel - 1, d_inner]`` in the compute dtype, the state leaving ``[B,
    N, d_inner]`` float32). ``tail``, ``state``, ``live`` and ``recur`` are
    :func:`state_space_mix`'s: what the sequence carried in (None = zeros),
    the window's real positions (first; a dead one advances nothing), and a
    caller's own recurrence ``recur(x, step, rate, b_in, c_out) -> (y, the
    state leaving)`` over operands shaped as :func:`_scan_blocks` takes them.
    ``with_memory``: ``out`` is the pair ``(out, m)`` with ``m [B, S,
    d_inner]`` the MEMORY, the scan's output (skip included) before the gate,
    in the compute dtype: what a :class:`GatedMemoryUnit` of a later layer
    gates.

    ``[x | z] = u W_in``; ``x`` through the depthwise causal convolution (with
    its bias) and SiLU; ``[dt | B | C] = x W_x``; ``step = softplus(dt W_dt +
    dt_bias)``, ``rate = -exp(a_log)``; the recurrence (:func:`_scan_blocks`)
    plus ``skip * x``; times ``silu(z)``; ``W_out``. The step, the decay and
    the state are float32; the projections and the convolution's inputs are
    the compute dtype's."""
    dt, f32 = cfg.compute_dtype, jnp.float32
    B, S, _ = u.shape
    C, N, R, K = a.d_inner, a.state_size, a.dt_rank, a.conv_kernel
    if tail is None:
        tail = jnp.zeros((B, a.tail, C), dt)
    if recur is None:
        if state is None:
            state = jnp.zeros((B, N, C), f32)
        recur = functools.partial(_scan_blocks, state=state,
                                  block=_SCAN_BLOCK)
    if live is None:
        live = jnp.ones((B, S), bool)
    xz = jnp.einsum("bsd,dw->bsw", u, layer["w_scan_in"].astype(dt))
    x, z = xz[..., :C], xz[..., C:]
    seq = jnp.concatenate([tail.astype(dt), x], 1)          # [B, K-1+S, C]
    at = jnp.sum(live, 1)[:, None] + jnp.arange(a.tail)[None]
    tail = jnp.take_along_axis(seq, at[..., None], axis=1)
    taps = layer["scan_conv_w"].astype(f32)
    conv = layer["scan_conv_b"].astype(f32) + sum(
        seq[:, j:j + S].astype(f32) * taps[:, j] for j in range(K))
    x = jax.nn.silu(conv).astype(dt)
    low = jnp.einsum("bsc,cw->bsw", x, layer["w_scan_x"].astype(dt))
    step = jnp.einsum("bsr,rc->bsc", low[..., :R],
                      layer["w_scan_dt"].astype(dt))
    step = jax.nn.softplus(step.astype(f32)
                           + layer["scan_dt_bias"].astype(f32))
    step = jnp.where(live[..., None], step, 0.0)
    y, state = recur(x, step, -jnp.exp(layer["scan_a_log"].astype(f32)),
                     low[..., R:R + N], low[..., R + N:])
    y = y + layer["scan_skip"].astype(f32) * x.astype(f32)
    out = jnp.einsum(
        "bsc,cd->bsd", (y * jax.nn.silu(z.astype(f32))).astype(dt),
        layer["w_scan_out"].astype(dt))
    return ((out, y.astype(dt)) if with_memory else out), tail, state


def gated_memory_mix(u, layer, a: GatedMemoryUnit, cfg, memory):
    """THE gated memory unit: the normed input ``u [B, S, D]`` and the
    ``memory [B, S, d_inner]`` of the layer it names, position by position ->
    ``(silu(u W_1) * memory) W_2 [B, S, D]``. Nothing is carried."""
    dt = cfg.compute_dtype
    gate = jnp.einsum("bsd,dc->bsc", u, layer["w_gmu_in"].astype(dt))
    gated = jax.nn.silu(gate.astype(jnp.float32)) \
        * memory.astype(jnp.float32)
    return jnp.einsum("bsc,cd->bsd", gated.astype(dt),
                      layer["w_gmu_out"].astype(dt))


# Positions of a sub-block of the chunked delta rule: inside one, a decay
# between two positions is exponentiated as their DIFFERENCE, exactly; across
# two, as a product of two factors that are each at most one.
_DELTA_SUB = 16
# Positions that :func:`delta_rule_mix` takes as one block of the chunked
# form (the family's); it changes no value, and ``benchmark/flops_linear``
# counts the recurrence at it.
_DELTA_BLOCK = 64


def _delta_step(q, k, v, g, beta, state):
    """:func:`_delta_blocks` for a window of ONE position, the update itself:
    ``S' = Diag(exp g) S``, ``u = v - S'^T k``, ``S = S' + beta k u^T``, ``o =
    S^T q``, elementwise over the state (held value-major, ``[B, H, value,
    key]``) and sums over its key axis. The read-out is taken from the
    DECAYED state, ``o = S'^T q + (beta k . q) u``, the same value: both
    sums then read the state in one pass and the update is a second, in
    place, where ``S^T q`` of the new state would be a third."""
    f32 = jnp.float32
    q, k, v, g = (t[:, 0].astype(f32) for t in (q, k, v, g))      # [B, H, d]
    kept = state.astype(f32) * jnp.exp(g)[:, :, None, :]
    held = jnp.sum(kept * k[:, :, None, :], -1)                   # S'^T k
    seen = jnp.sum(kept * q[:, :, None, :], -1)                   # S'^T q
    bk = beta[:, 0].astype(f32)[..., None] * k
    u = v - held
    state = kept + u[..., :, None] * bk[..., None, :]
    o = seen + jnp.sum(bk * q, -1, keepdims=True) * u
    return o[:, None], state


def _decayed_dots(lefts, y, G):
    """For each ``x`` of ``lefts``: ``P[.., i, j] = sum_c x[.., i, c] y[.., j,
    c] exp(G[.., i, c] - G[.., j, c])`` for ``j <= i`` and 0 for ``j > i``,
    over a block of ``C`` positions (``x, y, G [.., C, d]``; ``G`` the running
    sum of a channel's log decays, never rising; ``C`` whole sub-blocks of
    :data:`_DELTA_SUB`).

    ``exp(-G_j)`` alone overflows float32 after a few positions of strong
    decay, so it is never formed. Inside a sub-block the difference is taken
    first, exactly. Across sub-blocks ``a > b`` it is split at the first
    position of ``a``, ``ref_a``: ``exp(G_i - ref_a) exp(ref_a - G_j)``, both
    exponents at most 0, and the sum over the channels is a matrix product."""
    hi = jax.lax.Precision.HIGHEST
    c = _DELTA_SUB
    *lead, C, d = y.shape
    ns = C // c

    def sub(t):
        return t.reshape(*lead, ns, c, d)

    Gs, ys = sub(G), sub(y)
    ref = Gs[..., :1, :]                                      # [.., a, 1, d]
    earlier = (jnp.arange(ns)[:, None] > jnp.arange(ns)[None, :])[
        :, :, None, None]                                     # [a, b, 1, 1]
    gap = ref[..., :, None, :, :] - Gs[..., None, :, :, :]   # [.., a, b, j, d]
    y_ref = jnp.where(earlier, ys[..., None, :, :, :]
                      * jnp.exp(jnp.where(earlier, gap, 0.0)), 0.0)
    y_ref = y_ref.reshape(*lead, ns, C, d)
    within = Gs[..., :, None, :] - Gs[..., None, :, :]       # [.., a, i, j, d]
    lower = jnp.tril(jnp.ones((c, c), bool))
    weight = jnp.where(lower[..., None],
                       jnp.exp(jnp.where(lower[..., None], within, 0.0)), 0.0)
    eye = jnp.eye(ns, dtype=y.dtype)
    found = []
    for x in lefts:
        xs = sub(x)
        across = jnp.einsum("...aid,...ajd->...aij", xs * jnp.exp(Gs - ref),
                            y_ref, precision=hi)              # [.., a, i, C]
        diag = jnp.sum(xs[..., :, None, :] * ys[..., None, :, :] * weight, -1)
        diag = jnp.einsum("...aij,ab->...aibj", diag, eye)
        found.append((across + diag.reshape(*lead, ns, c, C)
                      ).reshape(*lead, C, C))
    return found


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of strictly lower-triangular ``A [.., n, n]``, by
    halves from the bottom up: the inverses of all diagonal blocks of ``s``
    rows at once, two neighbours joined by ``-bottom^-1 A_21 top^-1`` into
    the inverse of a block of ``2 s``, from ``s = 1`` (where the inverse is
    1) to ``n`` (padded to a power of two with the identity): ``log2 n``
    rounds of two small batched products, each a step of exact block
    substitution. The finite product ``(I - A)(I + A^2)(I + A^4) ..`` is the
    same matrix and is not used: its factors hold binomially large powers of
    ``A`` that cancel, and keys that share a direction (SiLU leaves them a
    positive mean, so ``k_i . k_j`` is a few tenths for EVERY pair) lose
    every digit of float32 in that cancellation."""
    hi = jax.lax.Precision.HIGHEST
    *lead, n, _ = A.shape
    m = 1 << (n - 1).bit_length()
    if m != n:
        A = jnp.pad(A, [(0, 0)] * len(lead) + [(0, m - n)] * 2)
    D = jnp.ones((*lead, m, 1, 1), A.dtype)
    s = 1
    while s < m:
        pairs = m // (2 * s)
        blocks = jnp.moveaxis(jnp.diagonal(
            A.reshape(*lead, pairs, 2 * s, pairs, 2 * s), axis1=-4,
            axis2=-2), -1, -3)                          # [.., pairs, 2s, 2s]
        top, bottom = D[..., 0::2, :, :], D[..., 1::2, :, :]
        corner = -jnp.matmul(
            jnp.matmul(bottom, blocks[..., s:, :s], precision=hi), top,
            precision=hi)
        D = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], -1),
            jnp.concatenate([corner, bottom], -1)], -2)
        s *= 2
    return D[..., 0, :n, :n]


def _delta_blocks(q, k, v, g, beta, state, block):
    """The gated delta rule of a window in its chunked form (the WY
    representation of arXiv:2412.06464 section 3 with Kimi Delta Attention's
    decay a channel), float32 throughout.

    ``q, k, v [B, S, H, d]`` (unit keys, scaled unit queries), ``g [B, S, H,
    d]`` a key channel's log decay (at most 0), ``beta [B, S, H]`` (``g`` and
    ``beta`` 0 = a position that leaves the state alone), ``state [B, H, d,
    d]`` entering, value-major -> (``o [B, S, H, d]``, the state leaving).
    Per head: ``S' = Diag(exp g_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t -
    S'^T k_t)^T``, ``o_t = S_t^T q_t``.

    Each token corrects what the state holds for its key, so inside a block
    the positions do not superpose as a state-space layer's do. With ``G_i``
    the running sum of ``g`` inside the block, ``A_ij = beta_i (k_i exp(G_i -
    G_j)) . k_j`` for ``j < i`` and ``T = (I + A)^-1 Diag(beta)``: the
    corrections ``u_i`` that the tokens really write are ``U - W S_0`` with
    ``U = T V`` and ``W = T (K exp G)``, one triangular inverse a block and
    head (:func:`_unit_lower_inverse`), and then ``o_i = (q_i exp G_i)^T S_0 +
    sum_{j<=i} ((q_i exp(G_i - G_j)) . k_j) u_j`` and ``S_C = Diag(exp G_C)
    S_0 + sum_j (k_j exp(G_C - G_j)) u_j^T`` are matrix products.
    Everything that does not read the entering state is made for all
    blocks at once; the blocks are chained by a scan over their states. A
    window of one is the update itself (:func:`_delta_step`)."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    B, S, H, d = q.shape
    if S == 1:
        return _delta_step(q, k, v, g, beta, state)
    C = _round_up(min(block, S), _DELTA_SUB)
    nb = -(-S // C)

    def blocks(t):    # [B, S, H, ..] -> [B, nb, H, C, ..], dead ones behind
        t = jnp.pad(t.astype(f32), [(0, 0), (0, nb * C - S)]
                    + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(B, nb, C, *t.shape[2:]), 2, 3)

    q, k, v, g, beta = (blocks(t) for t in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                                # [B,nb,H,C,d]
    kk, qk = _decayed_dots((k, q), k, G)                      # [B,nb,H,C,C]
    at = jnp.arange(C)
    A = jnp.where(at[:, None] > at[None, :], kk, 0.0) * beta[..., None]
    T = _unit_lower_inverse(A) * beta[..., None, :]
    decayed = jnp.exp(G)
    W = jnp.matmul(T, k * decayed, precision=hi)              # [B,nb,H,C,d]
    U = jnp.matmul(T, v, precision=hi)
    G_end = G[..., -1:, :]
    reads = jnp.concatenate([W, q * decayed], -2)             # [B,nb,H,2C,d]

    def chain(s, xs):          # s [B, H, value, key]
        reads_c, U_c, qk_c, k_end, kept = xs
        held = jnp.einsum("bhck,bhvk->bhcv", reads_c, s, precision=hi)
        u = U_c - held[:, :, :C]
        o = held[:, :, C:] + jnp.matmul(qk_c, u, precision=hi)
        s = s * kept[:, :, None, :] + jnp.einsum(
            "bhcv,bhck->bhvk", u, k_end, precision=hi)
        return s, o

    state, o = jax.lax.scan(
        chain, state.astype(f32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (
            reads, U, qk, k * jnp.exp(G_end - G), jnp.exp(G_end[..., 0, :]))))
    o = jnp.moveaxis(o, (0, 3), (1, 2))                       # [B,nb,C,H,d]
    return o.reshape(B, nb * C, H, d)[:, :S], state


def delta_rule_mix(u, layer, a: DeltaRuleMixer, cfg, tail=None, state=None,
                   live=None, recur=None):
    """THE delta-rule mixer, written once, with :func:`state_space_mix`'s
    contract: the normed input ``u [B, S, D]`` of a window of ``S``
    consecutive tokens a sequence -> (``out [B, S, D]``, the convolution tail
    leaving ``[B, conv_kernel - 1, 3 d_inner]`` in the compute dtype, the
    state leaving ``[B, H, d, d]`` float32, value-major).

    ``tail`` and ``state`` are what the sequence carried in (None = a
    sequence that starts here: zeros). ``live [B, S]`` marks the window's
    real positions, which come FIRST: a dead position advances neither the
    tail nor the state, and its output is garbage nobody reads. `forward`
    calls this with no state over the whole sequence, the serving chunk
    program with the slot's, the decode step with a window of one.
    ``recur(q, k, v, g, beta) -> (o, the state leaving)`` stands in for the
    recurrence where the caller owns the state where it lies and ``state`` is
    None; everything around it is this function's either way.

    ``[q | k | v] = u W_in`` through three depthwise causal convolutions (no
    bias) and SiLU; each head's ``q`` and ``k`` to unit length (``x
    rsqrt(sum x^2 + 1e-6)``), ``q`` times ``d^-1/2``; the decay a key channel
    ``g = -exp(a_log) softplus((u W_fa) W_fb + dt_bias)``; ``beta =
    sigmoid(u W_b)``, doubled under ``neg_eigval``; the recurrence
    (:func:`_delta_blocks`); an RMS norm over each head's ``d`` outputs (one
    scale for all heads) times ``sigmoid((u W_ga) W_gb + gate_bias)``;
    ``W_out``. The convolutions' sums, the normalisation, the decay,
    ``beta``, the state and the gated norm are float32; the projections and
    the convolutions' inputs are the compute dtype's."""
    dt, f32 = cfg.compute_dtype, jnp.float32
    B, S, _ = u.shape
    H, d, K, r = a.n_heads, a.head_dim, a.conv_kernel, a.rank
    if tail is None:
        tail = jnp.zeros((B, a.tail, a.conv_dim), dt)
    if recur is None:
        if state is None:
            state = jnp.zeros((B, *a.state_shape), f32)
        recur = functools.partial(_delta_blocks, state=state,
                                  block=_DELTA_BLOCK)
    if live is None:
        live = jnp.ones((B, S), bool)
    qkv = jnp.einsum("bsd,dw->bsw", u, layer["w_dr_in"].astype(dt))
    low = jnp.einsum("bsd,dw->bsw", u, layer["w_dr_low"].astype(dt))
    # The convolutions over the carried inputs and the window's; the tail
    # that leaves is the last live inputs.
    seq = jnp.concatenate([tail.astype(dt), qkv], 1)      # [B, K-1+S, 3 HD]
    at = jnp.sum(live, 1)[:, None] + jnp.arange(a.tail)[None]
    tail = jnp.take_along_axis(seq, at[..., None], axis=1)
    taps = layer["dr_conv_w"].astype(f32)
    qkv = jax.nn.silu(sum(
        seq[:, j:j + S].astype(f32) * taps[:, j] for j in range(K)))
    q, k, v = (qkv[..., i * a.d_inner:(i + 1) * a.d_inner].reshape(B, S, H, d)
               for i in range(3))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / math.sqrt(d)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    step = jax.nn.softplus(
        jnp.einsum("bsr,rf->bsf", low[..., :r],
                   layer["w_dr_decay"].astype(dt),
                   preferred_element_type=f32)
        + layer["dr_dt_bias"].astype(f32)).reshape(B, S, H, d)
    g = -jnp.exp(layer["dr_a_log"].astype(f32))[:, None] * step
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jax.nn.sigmoid(low[..., 2 * r:].astype(f32)) \
        * (2.0 if a.neg_eigval else 1.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    o, state = recur(q, k, v, g, beta)
    gate = jax.nn.sigmoid(
        jnp.einsum("bsr,rf->bsf", low[..., r:2 * r],
                   layer["w_dr_gate"].astype(dt), preferred_element_type=f32)
        + layer["dr_gate_bias"].astype(f32))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
        * layer["dr_norm"]["scale"].astype(f32)
    y = (o.reshape(B, S, -1) * gate).astype(dt)
    return (jnp.einsum("bsf,fd->bsd", y, layer["w_dr_out"].astype(dt)),
            tail, state)


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


def _head_gate(h, layer, dt):
    """The gate on an attention's output, ``[B, S, H, 1]`` (one a head) or
    ``[B, S, H, dh]`` (one a channel) by the shape of its matrix: a sigmoid
    (float32) of a projection of the layer's normed input ``h``."""
    w = layer["w_attn_gate"].astype(dt)
    if w.ndim == 3:
        return jax.nn.sigmoid(jnp.einsum(
            "bsd,dhk->bshk", h, w).astype(jnp.float32)).astype(dt)
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, w).astype(jnp.float32))
    return gate[..., None].astype(dt)


def _block_keys(chosen):
    """``chosen [B, S, G, k]`` blocks (``-1`` = none) as ONE list a query,
    ``[B, S, G * k]``: group ``g``'s block ``n`` is key ``n * G + g``, which
    is how a selecting layer reports its choice in its routing
    (``{"selected": ..}``, beside a latent layer's selected keys)."""
    G = chosen.shape[2]
    g = jnp.arange(G, dtype=chosen.dtype)[:, None]
    return jnp.where(chosen >= 0, chosen * G + g, -1).reshape(
        *chosen.shape[:2], -1)


def block(layer, x, cfg: TransformerConfig, attend, positions=None,
          mesh=None, out_spec=None, valid=None, li=0):
    """THE transformer block, written once: ``x + Wo attend(q, k, v)`` of the
    normed input, then ``+ ffn`` of the normed result -> ``(x, routing)``
    (``routing`` None for a dense feed-forward; see :func:`_moe_ffn`).

    ``attend(q, k, v) -> [B, S, H, dh]`` is all that differs between the
    trainer's three kernels (:func:`apply_block`) and the serving programs
    (``serving/engine.py``: write the window's K/V to the paged cache, then
    attend over the gathered pages). ``positions [B, S]`` are the tokens'
    places in their sequences (None = 0..S-1), read by RoPE.

    ``li`` is the layer's place in the model, for a configuration that
    describes its layers one by one (``cfg.attn_of``, ``cfg.is_moe``). A
    latent layer hands ``attend`` the operands of both forms of its attention
    instead, ``attend(q [B, S, H, W], row [B, S, W], index, q_heads, wkv_b)
    -> (o [B, S, H, v_dim], selected keys or None)`` (:func:`_latent_qkv`;
    absorbed, ``attend`` takes its result through :func:`latent_values`),
    gates the result a head where the model does, and returns the selection
    in its routing (``{"selected": ..}``). A multi-head layer
    of a described kind (:class:`MultiHeadAttention`) hands it ``attend(q
    [B, S, Hq, dh], k [B, S, Hkv, dh], v [B, S, Hkv, dv]) -> [B, S, Hq, dv]``
    (:func:`_qkv_kind`; ``sink=`` the layer's ``[Hq]`` scalars where the kind
    has them; ``index=`` the indexer's operands where the kind selects
    blocks (:func:`block_index`), and ``attend`` then returns ``(o, chosen
    blocks [B, S, G, k])``, reported as the routing's ``selected``) and gates
    the result a head or a channel where the kind says. A RECURRENT layer (:class:`StateSpaceMixer`,
    :class:`DeltaRuleMixer`) hands it the mixer itself, ``attend(mix) -> out
    [B, S, D]`` with ``mix(tail, state, live) -> (out, tail, state)``
    (:func:`state_space_mix` or :func:`delta_rule_mix` on this layer's normed
    input):
    the caller supplies what the sequences carried in and keeps what they
    carry out. A selective-scan layer whose memory a later layer reads
    (``cfg.hands_memory``) gives ``attend`` a ``mix`` whose ``out`` is the
    pair ``(out, memory)``: ``attend`` keeps the memory and returns ``out``.
    A multi-head layer that attends ANOTHER layer's keys and values
    (``kv_from``) hands ``attend(q, None, None)``: the caller has them. A
    GATED MEMORY layer hands ``attend(mix) -> out`` with ``mix(memory) ->
    out`` (:func:`gated_memory_mix`): the caller has the memory of the layer
    it names. A layer of one half (``cfg.layer_parts``) runs that half
    alone, under its own norm, and ``attend`` may be None for a layer with
    no mixer."""
    dt = cfg.compute_dtype
    a = cfg.attn_of(li)
    selected = routing = None

    def joined(x, out):
        """The stream plus a half's output (times ``residual_mult``)."""
        if cfg.residual_mult != 1.0:
            out = (out * cfg.residual_mult).astype(out.dtype)
        return x + out

    if cfg.has_mixer(li):
        h = _norm(x, layer["ln1"], cfg)
    if isinstance(a, RECURRENT) and cfg.has_mixer(li):
        # Looked up here, when a program is traced: tests and the benchmark's
        # planted faults replace the functions in this module.
        if isinstance(a, SelectiveScanMixer):
            scope, mix = scopes.STATE_SPACE, functools.partial(
                selective_scan_mix, h, layer, a, cfg,
                with_memory=cfg.hands_memory(li))
        else:
            scope, mix = ((scopes.STATE_SPACE, state_space_mix)
                          if isinstance(a, StateSpaceMixer)
                          else (scopes.LINEAR_ATTENTION, delta_rule_mix))
            mix = functools.partial(mix, h, layer, a, cfg)
        with jax.named_scope(scope):
            out = attend(mix)
            x = joined(x, _constrain(out, out_spec))
    elif isinstance(a, GatedMemoryUnit) and cfg.has_mixer(li):
        with jax.named_scope(scopes.GATED_MEMORY):
            out = attend(functools.partial(gated_memory_mix, h, layer, a,
                                           cfg))
            x = joined(x, _constrain(out, out_spec))
    elif cfg.has_mixer(li):
        with jax.named_scope(scopes.ATTENTION):
            if a is None:
                q, k, v = _qkv(h, layer, cfg, positions)
                out = jnp.einsum("bshk,hkd->bsd", attend(q, k, v),
                                 layer["wo"].astype(dt))
            elif isinstance(a, MultiHeadAttention):
                extra = {"sink": layer["sink"]} if a.sink else {}
                if a.select_topk:
                    with jax.named_scope(scopes.BLOCK_INDEX):
                        extra["index"] = block_index(h, layer, cfg, a)
                o = attend(*_qkv_kind(h, layer, cfg, a, positions), **extra)
                if a.select_topk:
                    o, selected = o
                    selected = _block_keys(selected)
                if a.differential:
                    o = differential_combine(o, layer, a, li, cfg.norm_eps,
                                             dt)
                if a.gate:
                    o = o * _head_gate(h, layer, dt)
                out = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
                if a.bias:
                    out = out + layer["bo"].astype(dt)
            else:
                o, selected = attend(
                    *_latent_qkv(h, layer, cfg, a, positions),
                    layer["wkv_b"].astype(dt))
                if cfg.attn_gate:
                    o = o * _head_gate(h, layer, dt)
                out = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
            x = joined(x, _constrain(out, out_spec))
    if cfg.has_ffn(li):
        h = _norm(x, layer["ln2"], cfg)
        with jax.named_scope(scopes.MLP):
            if cfg.is_moe(li):
                y, routing = _moe_ffn(h, layer, cfg, mesh, valid)
            else:
                y = _ffn(h, layer, cfg)
            x = joined(x, y)
    if selected is not None:
        routing = dict(routing or {}, selected=selected)
    return x, routing


def _block_fn(cfg, mesh, impl, seq_spec, full_spec):
    """``(layer, x, li, carried) -> (x, routing, carried)`` with the
    trainer's attention."""
    if (impl == "ring" and mesh is not None
            and cfg.seq_axis in mesh.axis_names):
        attend = lambda q, k, v: _attend_ring(q, k, v, cfg, mesh)  # noqa: E731
    elif impl == "flash":
        attend = lambda q, k, v: _attend_flash(q, k, v, cfg, mesh)  # noqa: E731
    else:
        attend = lambda q, k, v: _attend_gather(  # noqa: E731
            q, k, v, cfg, full_spec)

    def fn(layer, x, li=0, carried=None):
        """``carried``: what earlier layers handed on for later ones (the keys
        and values of a layer whose cache others share, a selective-scan
        layer's memory), by name -> ``(x, routing, carried)``, this layer's
        own added."""
        a = cfg.attn_of(li)
        carried = dict(carried or {})
        if isinstance(a, RECURRENT):   # every sequence starts here
            def mine(mix):
                out = mix()[0]
                if cfg.hands_memory(li):
                    out, carried[f"memory{li}"] = out
                return out
        elif isinstance(a, GatedMemoryUnit):
            mine = lambda mix: mix(  # noqa: E731
                carried[f"memory{a.memory_from}"])
        elif isinstance(a, MultiHeadAttention):
            kind = _attend_kind(a.attended, cfg.compute_dtype)

            def mine(q, k, v, **extra):
                if a.kv_from is not None:
                    k, v = carried[f"kv{a.kv_from}"]
                elif cfg.shares_kv(li):
                    carried[f"kv{li}"] = (k, v)
                return kind(q, k, v, **extra)
        else:
            mine = attend if a is None else _attend_latent(
                a, cfg.compute_dtype)
        x, routing = block(layer, x, cfg, mine, mesh=mesh,
                           out_spec=seq_spec, li=li)
        return _constrain(x, seq_spec), routing, carried

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
    """Token embeddings in the compute dtype, shaped like ``tokens`` (times
    ``cfg.embed_mult``)."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    if cfg.embed_mult != 1.0:
        x = (x * cfg.embed_mult).astype(x.dtype)
    return x


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


def head_logits(x, params, cfg, spec="bsd,vd->bsv"):
    """The final projection of ``x`` onto the vocabulary, in the compute
    dtype (the trainer's loss projects inside its own scope,
    :func:`_chunked_nll`)."""
    with jax.named_scope(scopes.HEAD):
        logits = jnp.einsum(
            spec, x, head_weights(params, cfg).astype(cfg.compute_dtype))
        if cfg.logits_div != 1.0:
            logits = (logits / cfg.logits_div).astype(logits.dtype)
        return logits


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
    with jax.named_scope(scopes.EMBED):
        x = add_positions(embed_tokens(params, tokens, cfg), params, cfg)
    x = _constrain(x, seq_spec)

    impl = resolve_attn(cfg, S, mesh)
    fn = _block_fn(cfg, mesh, impl, seq_spec, full_spec)

    def block_(x, layer, li, carried):
        x, _, carried = fn(layer, x, li, carried)
        return x, carried

    if cfg.remat:
        block_ = jax.checkpoint(block_, static_argnums=(2,))
    carried = {}
    for li, layer in enumerate(params["layers"]):
        x, carried = block_(x, layer, li, carried)
    x = _norm(x, params["final_ln"], cfg)
    if return_hidden:
        return x
    return head_logits(x, params, cfg)


def _chunk_lse(h, t, head):
    """One chunk of the chunked loss, in float32 -> (its logits [B, C, V],
    their log-sum-exp [B, C, 1], the one-hot mask of its targets, its summed
    -log p(target)). The target's logit is picked by comparing a vocabulary
    iota with the target, a masked sum beside the softmax's own reductions,
    where ``take_along_axis`` would gather: a gather's transpose is a
    scatter, which inside a loop body runs one row at a time. The product is
    rounded to the compute dtype and widened, as a plain projection in that
    dtype rounds it, so the loss is the same however many chunks make it."""
    logits = jnp.einsum("bsd,vd->bsv", h, head).astype(jnp.float32)
    top = jnp.max(logits, -1, keepdims=True)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top), -1, keepdims=True))
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) == t[..., None]
    picked = jnp.sum(jnp.where(hit, logits, 0.0), -1)
    return logits, lse, hit, jnp.sum(lse[..., 0] - picked)


@jax.custom_vjp
def _chunked_nll(h_chunks, t_chunks, head):
    """Mean -log p(target) over chunks ``h_chunks`` [n, B, C, d] and
    ``t_chunks`` [n, B, C], with ``head`` [V, d]. Called plainly (an
    evaluation loop) it makes the logits and the sum, nothing else; under
    differentiation :func:`_chunked_nll_fwd` takes its place."""
    head_c = head.astype(h_chunks.dtype)

    def body(total, xs):
        return total + _chunk_lse(*xs, head_c)[-1], None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (h_chunks, t_chunks))
    return total / t_chunks.size


def _chunked_nll_fwd(h_chunks, t_chunks, head):
    """The loss and, in the same trip that makes a chunk's logits, its
    gradients: ``dlogits = (softmax - onehot) / rows`` in the compute dtype,
    ``dh = dlogits . head`` stacked by chunk, ``dhead += dlogits^T . h`` in
    one float32 [V, d] carried through the scan. Nothing is kept to replay
    and no second scan exists: the backward rule is a scaling."""
    dt = h_chunks.dtype
    head_c = head.astype(dt)
    scale = 1.0 / t_chunks.size

    def body(carry, xs):
        total, dhead = carry
        h, t = xs
        logits, lse, hit, nll = _chunk_lse(h, t, head_c)
        p = jnp.exp(logits - lse)
        dlogits = (jnp.where(hit, p - 1.0, p) * scale).astype(dt)
        dh = jnp.einsum("bsv,vd->bsd", dlogits, head_c)
        dhead = dhead + jnp.einsum("bsv,bsd->vd", dlogits, h,
                                   preferred_element_type=jnp.float32)
        return (total + nll, dhead), dh

    (total, dhead), dh = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros(head.shape, jnp.float32)),
        (h_chunks, t_chunks))
    return total * scale, (dh, dhead.astype(head.dtype))


def _chunked_nll_bwd(res, g):
    dh, dhead = res
    return (dh * g).astype(dh.dtype), None, (dhead * g).astype(dhead.dtype)


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


# Rows (batch x positions) that one trip of the loss's loop takes at most
# where the caller set no ``loss_chunk``. Read once on a v5e at 8 x 512,
# d 1024, V 50257 (PERF.md, PR 52), the loss alone / ``step_dev_ms`` of
# gpt2m-train-s512 (78.90 with the unchunked loss before it):
#   1024 rows, 4 trips: 8.11 ms / 73.77
#   2048 rows, 2 trips: 7.69 ms / 73.65
#   4096 rows, 1 trip:  7.57 ms / 72.90 (XLA inlines a loop of one trip, and
#     on one chip the embedding's AdamW update then fuses into the
#     weight-gradient product; in a loop it is 2.2 ms of its own)
_LOSS_ROWS = 4096


def _loss_positions(B, S, cap, whole):
    """Positions a trip of the loss's loop takes of each of ``B`` sequences
    of ``S``. A caller's ``cap`` (``cfg.loss_chunk`` > 0) is taken as it is.
    Else the program picks: the largest divisor of S that keeps a trip at or
    under ``_LOSS_ROWS`` rows; the whole sequence where the rows already fit,
    where ``whole`` says that positions are sharded over a mesh axis (a loop
    over S would walk from device to device), and where no divisor comes
    within a factor of two of ``_LOSS_ROWS`` (S prime, B above it): a loop of
    S trips of B rows is not an answer."""
    if cap:
        if S > cap and S % cap != 0:
            raise ValueError(f"seq len {S} must divide by loss_chunk {cap}")
        return min(cap, S)
    most = _LOSS_ROWS // B
    if whole or most >= S:
        return S
    C = max((c for c in range(1, most + 1) if S % c == 0), default=0)
    return C if 2 * B * C >= _LOSS_ROWS else S


def loss_fn(params, batch, cfg: TransformerConfig, mesh=None):
    """Next-token cross-entropy. batch = {"tokens": [B, S+1] int32}.

    One path: the vocab projection + log-softmax run per chunk of positions
    inside one scan that also takes each chunk's gradients
    (:func:`_chunked_nll`), so no ``[B, S, vocab]`` tensor is kept for the
    backward pass. ``cfg.loss_chunk`` 0: the program picks the chunk from
    the shapes it sees (:func:`_loss_positions`); > 0: the caller's. The
    value does not depend on the chunk.
    """
    if cfg.logits_div != 1.0:
        raise ValueError("loss_fn projects for itself and does not apply "
                         "logits_div: such a model is served, not trained")
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    B, S = targets.shape
    sharded = (mesh is not None and cfg.seq_axis in mesh.axis_names
               and mesh.shape[cfg.seq_axis] > 1)
    C = _loss_positions(B, S, cfg.loss_chunk, sharded)
    head = head_weights(params, cfg)
    hidden = forward(params, tokens[:, :-1], cfg, mesh=mesh,
                     return_hidden=True)
    # Everything after the hidden states is the loss's: the projection, the
    # softmax, the mean, and the scan that walks the chunks.
    with jax.named_scope(scopes.LOSS):
        d = hidden.shape[-1]
        h_chunks = hidden.reshape(B, S // C, C, d).swapaxes(0, 1)
        t_chunks = targets.reshape(B, S // C, C).swapaxes(0, 1)
        return _chunked_nll(h_chunks, t_chunks, head)
