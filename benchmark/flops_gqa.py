"""Operations and bytes of the paged grouped-query attention kernel
(``horovod_tpu/ops/pallas_paged_attention.py``, ``paged_full_attention`` and
``paged_window_attention``) from the server's counters over a stretch and the
configuration's sizes. What the algorithm needs, as ``flops.py`` counts: a
multiply-add is two operations; bytes are the LEAST a kernel has to move, so
a share of the roofline computed from them cannot be flattered by traffic the
kernel chose to have (a chunk's query blocks each walk the slot's pages: the
rows are counted once a call).

The counters (``hvd.serve_stats()["attn"]``, by program kind; each already
summed over the layers of its kind):

- ``kv_full_rows``: K/V rows the full layers have to read: a slot's live rows
  once a call and layer;
- ``kv_window_rows``: the same for the window layers: min(live rows,
  ``sliding_window`` - 1 + the call's queries) a slot;
- ``qk_full_pairs`` / ``qk_window_pairs``: the (query, key) pairs attended:
  every live key of a query, or min(live keys, ``sliding_window``);
- ``queries``: tokens through the program (times the layers of a kind = the
  (query, layer) pairs of that kind).

The configuration is the dict of a file under ``benchmark/configs`` with the
source's key names; ``layer_types[:num_hidden_layers]`` says how many layers
of each kind run and ``heads_by_kind`` how many query heads a kind has.
"""

BYTES = 2       # bfloat16 operands


def _layers(cfg, kind):
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(kind)


def _attention(cfg, kind, pairs, rows, queries):
    """``pairs`` (query, key) pairs of every query head: a product over
    ``head_dim`` for the logit and one for the output; ``rows`` K and V rows
    of ``num_key_value_heads * head_dim`` read once; the queries in and the
    outputs out."""
    heads, d = cfg["heads_by_kind"][kind], cfg["head_dim"]
    flops = pairs * heads * 2 * (d + d)
    nbytes = (rows * 2 * cfg["num_key_value_heads"] * d
              + queries * _layers(cfg, kind) * heads * 2 * d) * BYTES
    return flops, nbytes


def full_attention(cfg, counts):
    return _attention(cfg, "full_attention", counts["qk_full_pairs"],
                      counts["kv_full_rows"], counts["queries"])


def window_attention(cfg, counts):
    if not _layers(cfg, "sliding_attention"):
        return 0, 0     # a model of full layers alone: no call, no counter
    return _attention(cfg, "sliding_attention", counts["qk_window_pairs"],
                      counts["kv_window_rows"], counts["queries"])


KERNELS = {"full_attention": (full_attention,),
           "window_attention": (window_attention,),
           # both kinds' calls of one program: each call has its own floor
           "chunk_attention": (full_attention, window_attention)}


def least_seconds(cfg, kernel, counts, peak):
    """The roofline's floor for one program kind's counters: for each
    kernel call kind the larger of operations over the chip's bf16 peak and
    bytes over its memory bandwidth (``peaks.json`` entry), summed."""
    return sum(max(flops / (peak["bf16_tflops"] * 1e12),
                   nbytes / (peak["hbm_gbps"] * 1e9))
               for flops, nbytes in (fn(cfg, counts)
                                     for fn in KERNELS[kernel]))
