"""Model FLOPs from shapes, for utilisation metrics.

What the algorithm needs, not what the compiler emits: a multiply-add is two
operations; the backward pass costs twice the forward; recomputation
(``remat``, the chunked loss) and the optimizer's elementwise update are not
counted. Causal attention is counted at the half it needs: query ``i`` reads
``i + 1`` keys, so a sequence of ``S`` does ``S * (S + 1) / 2`` score rows'
worth of work in each of the two attention matrix products.

The configuration is the dict of a file under ``benchmark/configs`` (GPT-2's
key names: ``n_layer``, ``n_embd``, ``n_head``, ``n_inner``, ``vocab_size``).
"""


def matmul_params(cfg):
    """Weights that take part in a matrix product for every token: fused QKV,
    attention output, the two MLP matrices, per layer; and the tied output
    head. Embedding look-ups, positions and LayerNorm do no matrix work."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    per_layer = 3 * d * d + d * d + 2 * d * f
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def forward_flops(cfg, batch, seq):
    """One causal forward pass over ``batch`` sequences of ``seq`` positions,
    logits for every position."""
    tokens = batch * seq
    dense = 2 * matmul_params(cfg) * tokens
    # QK^T and PV: 2 products x 2 ops x d per (query, key) pair, causal pairs.
    pairs = batch * seq * (seq + 1) // 2
    attn = cfg["n_layer"] * 2 * 2 * cfg["n_embd"] * pairs
    return dense + attn


def train_flops(cfg, batch, seq):
    """Forward and backward of one training step (3x the forward)."""
    return 3 * forward_flops(cfg, batch, seq)
