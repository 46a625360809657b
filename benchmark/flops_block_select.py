"""Operations and bytes of a layer that SELECTS key/value blocks a key/value
group (``horovod_tpu/ops/pallas_paged_attention.py`` ``paged_block_attention``;
the scorer over pooled block rows, ``pallas_latent.index_scores``), from the
server's counters over a stretch and the configuration's published keys. What
the SELECTION's definition needs, as ``flops.py`` counts: a multiply-add is two
operations; bytes are the LEAST a kernel has to move, whatever it happens to
read (a tile of queries that walks the union of its queries' blocks, or a
masked product over blocks a query did not choose, is priced at what the
queries chose), so a share of the roofline computed from them cannot be
flattered by traffic or products the kernel chose to have.

The counters (``hvd.serve_stats()["attn"]``, by program kind; each already
summed over the layers and counted a key/value group each,
``serving/engine.py`` ``_block_work``):

- ``kv_selected``: (query, key) pairs a group's query heads multiply: the
  visible keys of a query's first, chosen and local blocks (the server also
  reports it as ``qk_block_pairs``);
- ``kv_block_rows``: rows of chosen blocks a tile of queries must read, a
  block once a tile (a tile: the queries of one block of positions; of the
  blocks its queries choose, the last query's: the least any kernel reads);
- ``block_rows_scored``: pooled rows a query's indexer scores (its candidate
  blocks);
- ``queries``: tokens through the program (times the layers = the (query,
  layer) pairs).

A counter a record lacks counts nothing (``kv_block_rows``,
``block_rows_scored``: names no other model's record has).

The configuration is the dict of a file under ``benchmark/configs`` with the
source's key names; every one of ``num_hidden_layers`` selects
(``assumed.selection``: ``index_heads`` heads of ``index_dim`` a group).
"""

BYTES = 2       # bfloat16 operands
SCORE_BYTES = 4  # float32 scores


def block_attention(cfg, counts):
    """A pair costs each of the group's ``heads / kv_heads`` query heads a
    product over ``head_dim`` for the logit and one for the output (4 x 128 x
    16 operations at the published sizes); a chosen block's K and V rows of
    ONE key/value head cross the bus once a tile; the queries in and the
    outputs out."""
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    flops = counts["kv_selected"] * (heads // kv_heads) * 2 * (d + d)
    rows = counts["queries"] * cfg["num_hidden_layers"]
    return flops, (counts.get("kv_block_rows", 0) * (d + d)
                   + rows * heads * (d + d)) * BYTES


def index_scores(cfg, counts):
    """``sum_j w_j relu(q_j . pooled)``: a product of ``index_dim`` for each
    of ``index_heads`` heads a scored row, and the ReLU, weight and sum; the
    score written once, each query's indexer heads read once a group."""
    sel = cfg["assumed"]["selection"]
    j, d = sel["index_heads"], sel["index_dim"]
    scored = counts.get("block_rows_scored", 0)
    flops = scored * (2 * j * d + 3 * j)
    rows = (counts["queries"] * cfg["num_hidden_layers"]
            * cfg["num_key_value_heads"])
    return flops, (scored * SCORE_BYTES
                   + rows * j * (d * BYTES + SCORE_BYTES))


KERNELS = {"block_attention": block_attention,
           "chunk_block_attention": block_attention,
           "index_scores": index_scores}


def least_seconds(cfg, kernel, counts, peak):
    """The roofline's floor for one program kind's counters: the larger of
    operations over the chip's bf16 peak and bytes over its memory
    bandwidth (``peaks.json`` entry)."""
    flops, nbytes = KERNELS[kernel](cfg, counts)
    return max(flops / (peak["bf16_tflops"] * 1e12),
               nbytes / (peak["hbm_gbps"] * 1e9))
