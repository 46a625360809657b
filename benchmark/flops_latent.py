"""Operations and bytes of full-context latent attention
(``horovod_tpu/ops/pallas_latent.py``, ``paged_latent_attention``) from the
server's counters over a stretch and the configuration's sizes. What the
algorithm needs, as ``flops.py`` counts: a multiply-add is two operations;
bytes are the LEAST a kernel has to move (a chunk's query blocks each walk
the slot's pages: the rows are counted once a call).

THE SAME WORK WHATEVER IMPLEMENTS IT. Latent attention can be computed in two
forms, and the floor of a call kind is the SMALLER of the two forms' floors,
so that a later change of form leaves the yardstick where it is and no share
can read over 100 %:

- absorbed (what the server runs today): a head's query is multiplied into
  the latent, so a (query, key) pair is a product over ``kv_lora_rank +
  qk_rope_head_dim`` for the logit and one over ``kv_lora_rank`` for the
  output, a head; nothing is expanded;
- expanded: every live row is expanded once a call into each head's key
  (``qk_nope_head_dim``) and value (``v_head_dim``), ``2 x kv_lora_rank x
  heads x (nope + v)`` operations a row, and a pair is then a product over
  ``qk_nope_head_dim + qk_rope_head_dim`` and one over ``v_head_dim``, a head.

Either way the bytes are the rows (``kv_lora_rank + qk_rope_head_dim`` lanes,
the 64 lanes of padding are no work) once a call, the queries in and the
outputs out at the form's own widths. Each form's floor is the larger of its
operations over the chip's bf16 peak and its bytes over the memory bandwidth.

The counters (``hvd.serve_stats()["attn"]``, by program kind; each already
summed over the layers):

- ``kv_latent_rows``: latent rows the layers have to read: a slot's live rows
  once a call and layer;
- ``qk_latent_pairs``: the (query, key) pairs attended: every live key of
  every query, times the layers;
- ``queries``: tokens through the program (times the layers = the (query,
  layer) pairs).

The configuration is the dict of a file under ``benchmark/configs`` with the
source's key names.
"""

BYTES = 2       # bfloat16 operands


def latent_attention(cfg, counts):
    """-> ((operations, bytes) absorbed, (operations, bytes) expanded) of one
    program kind's counters."""
    h = cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    pairs, rows = counts["qk_latent_pairs"], counts["kv_latent_rows"]
    rows_bytes = rows * (rank + rope) * BYTES
    q_layers = counts["queries"] * cfg["num_hidden_layers"]
    absorbed = (pairs * h * 2 * (rank + rope + rank),
                rows_bytes + q_layers * h * (rank + rope + rank) * BYTES)
    expanded = (pairs * h * 2 * (nope + rope + v)
                + rows * 2 * rank * h * (nope + v),
                rows_bytes + q_layers * h * (nope + rope + v) * BYTES)
    return absorbed, expanded


KERNELS = {"latent_attention": latent_attention}


def least_seconds(cfg, kernel, counts, peak):
    """The roofline's floor for one program kind's counters: the smaller of
    the forms' floors, each the larger of operations over the chip's bf16
    peak and bytes over its memory bandwidth (``peaks.json`` entry)."""
    return min(max(flops / (peak["bf16_tflops"] * 1e12),
                   nbytes / (peak["hbm_gbps"] * 1e9))
               for flops, nbytes in KERNELS[kernel](cfg, counts))
