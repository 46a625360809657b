"""Operations and bytes of the paged grouped-query attention kernel
(``horovod_tpu/ops/pallas_paged_attention.py``, ``paged_full_attention`` and
``paged_window_attention``) for a model whose KINDS of layer differ in
key/value heads and whose keys and values have widths of their own, from the
server's counters over a stretch and the configuration's published keys.
``flops_gqa.py`` prices both kinds' rows at one ``num_key_value_heads *
head_dim`` and both products at ``d + d``; here a kind has its own. What the
algorithm needs, as ``flops.py`` counts: a multiply-add is two operations;
bytes are the LEAST a kernel has to move, whatever layout the kernel chose (a
key of 192 read as 256 lanes, or stored padded, is priced at 192), so a share
of the roofline computed from them cannot be flattered by traffic the kernel
chose to have.

The counters (``hvd.serve_stats()["attn"]``, by program kind; each already
summed over the layers of its kind) are ``flops_gqa.py``'s: ``kv_full_rows``,
``kv_window_rows``, ``qk_full_pairs``, ``qk_window_pairs``, ``queries``.

The configuration is the dict of a file under ``benchmark/configs`` with the
source's key names: ``hybrid_layer_pattern[:num_hidden_layers]`` says how many
layers of each kind run (0 full, 1 window); a full layer has
``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
heads, keys of ``head_dim`` and values of ``v_head_dim``; a window layer the
same keys with the prefix ``swa_``.
"""

BYTES = 2       # bfloat16 operands
PREFIX = {"full": "", "window": "swa_"}


def _layers(cfg, kind):
    return cfg["hybrid_layer_pattern"][:cfg["num_hidden_layers"]].count(
        int(kind == "window"))


def _attention(cfg, kind, pairs, rows, queries):
    """``pairs`` (query, key) pairs of every query head: a product over the
    key's width for the logit and one over the value's for the output;
    ``rows`` K rows and V rows of the kind's own key/value heads read once;
    the queries in (key-wide) and the outputs out (value-wide)."""
    pre = PREFIX[kind]
    heads, kv_heads = (cfg[pre + "num_attention_heads"],
                       cfg[pre + "num_key_value_heads"])
    wide = cfg[pre + "head_dim"] + cfg[pre + "v_head_dim"]
    flops = pairs * heads * 2 * wide
    nbytes = (rows * kv_heads * wide
              + queries * _layers(cfg, kind) * heads * wide) * BYTES
    return flops, nbytes


def full_attention(cfg, counts):
    return _attention(cfg, "full", counts["qk_full_pairs"],
                      counts["kv_full_rows"], counts["queries"])


def window_attention(cfg, counts):
    return _attention(cfg, "window", counts["qk_window_pairs"],
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
