"""Operations and bytes of the latent attention layers' serving kernels
(``horovod_tpu/ops/pallas_latent.py``) and of the grouped products of the
experts a chip holds, from the server's counters over a stretch and the
configuration's sizes. What the algorithm needs, as
``flops.py`` counts: a multiply-add is two operations; bytes are the LEAST a
kernel has to move, so a share of the roofline computed from them cannot be
flattered by traffic the kernel chose to have.

The counters (``hvd.serve_stats()["attn"]``, by program kind; each already
summed over the layers of its kind):

- ``kv_scored``: (query, key) pairs the selection scored: every live key of
  every query of every selecting layer;
- ``kv_selected``: the pairs those layers then attended over: min(live keys,
  ``index_topk``) a query;
- ``kv_window``: the pairs the window layers attended over: min(live keys,
  ``sliding_window_size``) a query;
- ``queries``: tokens through the program (times the layers of a kind = the
  (query, layer) pairs of that kind).

For the expert products the counters are ``hvd.serve_stats()["moe"]``'s, as
``flops_moe.py`` reads them, counted over the experts HELD here alone:
``pairs`` (routed (token, expert) rows whose expert is on this chip) and
``expert_reads`` (held experts of one layer that one program run touched).

The configuration is the dict of a file under ``benchmark/configs`` with the
source's key names; ``layer_types[:num_hidden_layers]`` says how many layers
of each kind run, ``moe_intermediate_size`` is one routed expert's width.
"""

BYTES = 2       # bfloat16 operands
SCORE_BYTES = 4  # float32 scores, int32 indices


def _layers(cfg, kind):
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(kind)


def index_scores(cfg, counts):
    """``sum_j w_j relu(q_j . k)``: a product of ``index_head_dim`` for each
    of ``index_n_heads`` heads a scored pair, and the ReLU, weight and sum;
    the score written once, each query's scorer heads read once."""
    j, d = cfg["index_n_heads"], cfg["index_head_dim"]
    flops = counts["kv_scored"] * (2 * j * d + 3 * j)
    rows = counts["queries"] * _layers(cfg, "full_attention")
    return flops, (counts["kv_scored"] * SCORE_BYTES
                   + rows * j * (d * BYTES + SCORE_BYTES))


def _latent_attention(cfg, prefix, pairs, rows):
    """Absorbed latent attention over ``pairs`` (query, key) pairs of
    ``rows`` (query, layer) pairs: a head's logit is a product over the
    latent and the rotated dims, its output a product over the latent."""
    h = cfg[prefix + "num_attention_heads"]
    latent, rope = cfg[prefix + "kv_lora_rank"], cfg[prefix + "qk_rope_head_dim"]
    flops = pairs * h * 2 * (latent + rope + latent)
    return flops, h * rows * (latent + rope + latent) * BYTES


def sparse_attention(cfg, counts):
    """Each selected pair's row read once for its query (the selection
    differs query by query, so no row is shared), queries in, outputs out."""
    flops, io = _latent_attention(
        cfg, "", counts["kv_selected"],
        counts["queries"] * _layers(cfg, "full_attention"))
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return flops, io + counts["kv_selected"] * row * BYTES


def window_attention(cfg, counts):
    """The ring is shared by a call's queries, so the least traffic is the
    queries in and the outputs out."""
    return _latent_attention(
        cfg, "swa_", counts["kv_window"],
        counts["queries"] * _layers(cfg, "sliding_attention"))


def expert_products(cfg, counts):
    """The gate, up and down products of the held experts' rows
    (``flops_moe.py``'s count at ``moe_intermediate_size``): each touched
    expert's three matrices cross the bus once; every row is read at width D
    twice and written at width F twice, then read at F and written at D.
    Rows routed to experts on other chips are no work of this chip's."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    pairs = counts["pairs"]
    return (2 * 3 * d * f * pairs,
            (3 * d * f * counts["expert_reads"] + 3 * (d + f) * pairs) * BYTES)


KERNELS = {"index_scores": index_scores,
           "sparse_attention": sparse_attention,
           "window_attention": window_attention,
           "expert_products": expert_products}


def least_seconds(cfg, kernel, counts, peak):
    """The roofline's floor for one program kind's counters: the larger of
    operations over the chip's bf16 peak and bytes over its memory
    bandwidth (``peaks.json`` entry)."""
    flops, nbytes = KERNELS[kernel](cfg, counts)
    return max(flops / (peak["bf16_tflops"] * 1e12),
               nbytes / (peak["hbm_gbps"] * 1e9))
