"""Operations and bytes of what a decoder-hybrid-decoder adds to the program
(per-channel selective-scan layers, differential attention on rings, on pages
and on ANOTHER layer's pages), from the server's counters over a stretch and
the configuration's sizes. What the algorithm needs, as ``flops.py`` counts: a
multiply-add is two operations; bytes are the LEAST a part has to move, so a
share of the roofline computed from them cannot be flattered by traffic the
program chose to have. The same work whatever implements it: nothing here
reads how the program computes a layer (its attention multiplies padded
queries: twice the score products, not counted).

Parts, each ``part(cfg, counts) -> (operations, bytes)``:

- ``scan_update``: a decode step's selective-scan layers, from
  ``counts["scan_rows"]`` ((slot, layer) rows carried),
  ``counts["scan_bytes"]`` (their tail and float32 state read and written back) and
  ``counts["calls"]``: every layer's weights once a step; each row's
  projections (in, ``x_proj``, ``dt_proj``, out), its convolution and ``9 N``
  operations a channel (``N`` = ``d_state``: the decay's product, its
  exponential, the state's multiply and add, the input's outer product, the
  read-out's multiply and add, the step's product); the token in and out;
- ``scan_window``: a chunk's selective-scan layers, from
  ``counts["scan_tokens"]`` ((token, layer) positions passed over),
  ``counts["scan_rows"]``, ``counts["scan_bytes"]`` and ``counts["calls"]``:
  the same a token; weights once a call and layer, the state in and out once,
  the tokens in and out. ``peaks.json`` has no vector peak: the recurrence's
  operations are divided by the bf16 matrix peak like the projections', so the
  share prices the projections and the bytes and shows the recurrence as the
  gap;
- ``shared_attention``: the full layer and the layers that attend ITS pages,
  from ``counts["qk_full_pairs"]`` ((query, key) pairs, summed over those
  layers), ``counts["kv_full_rows"]`` + ``counts["kv_shared_rows"]`` (K/V rows
  read: a slot's live rows once a call and LAYER, the sharing layers
  included) and ``counts["tail_rows"]`` (queries through them): differential
  attention's ``2 (d + 2 d)`` operations a (query head, key) (a logit over
  ``d``, a value sum over the pair's ``2 d``), rows of ``num_key_value_heads
  * d`` lanes for K and for V, a query of ``d`` in and an output of ``2 d``
  out a head;
- ``window_attention``: the window layers the same way, from
  ``counts["qk_window_pairs"]``, ``counts["kv_window_rows"]`` and
  ``counts["queries"]``;
- ``chunk_attention``: both kinds' calls of one program, each with its own
  floor.

The counters are ``hvd.serve_stats()["state"]`` and ``["attn"]`` by program
kind; the configuration is the dict of a file under ``benchmark/configs`` with
the source's key names, ``layer_kinds`` naming each layer's mixer,
``assumed.mamba`` the scan's sizes and ``head_dim`` a head's width.
"""

BYTES = 2       # bfloat16 weights and activations


def _layers(cfg, *kinds):
    return sum(cfg["layer_kinds"].count(kind) for kind in kinds)


def _scan(cfg):
    m = cfg["assumed"]["mamba"]
    return m["d_inner"], m["d_state"], m["d_conv"], m["dt_rank"]


def _scan_weights(cfg):
    """Parameters of one selective-scan layer's matrices."""
    c, n, kernel, r = _scan(cfg)
    hidden = cfg["hidden_size"]
    return (hidden * 2 * c + c * (r + 2 * n) + r * c + c * hidden
            + c * (kernel + n + 3))


def _scan_token_flops(cfg):
    """Operations a token a layer: the four projections, the convolution and
    the recurrence."""
    c, n, kernel, r = _scan(cfg)
    hidden = cfg["hidden_size"]
    return (2 * (hidden * 2 * c + c * (r + 2 * n) + r * c + c * hidden)
            + 2 * kernel * c + 9 * n * c)


def _scan_part(cfg, counts, tokens):
    nbytes = (counts["scan_bytes"]
              + counts["calls"] * _layers(cfg, "mamba") * _scan_weights(cfg)
              * BYTES + tokens * 2 * cfg["hidden_size"] * BYTES)
    return tokens * _scan_token_flops(cfg), nbytes


def scan_update(cfg, counts):
    return _scan_part(cfg, counts, counts["scan_rows"])


def scan_window(cfg, counts):
    return _scan_part(cfg, counts, counts["scan_tokens"])


def _attention(cfg, kind, pairs, rows, query_layers):
    """``pairs`` (query, key) pairs of every query head; ``rows`` K and V rows
    read once; ``query_layers`` (query, layer) pairs in and out."""
    heads, d = cfg["heads_by_kind"][kind], cfg["head_dim"]
    flops = pairs * heads * 2 * (d + 2 * d)
    nbytes = (rows * 2 * cfg["num_key_value_heads"] * d
              + query_layers * heads * (d + 2 * d)) * BYTES
    return flops, nbytes


def shared_attention(cfg, counts):
    return _attention(
        cfg, "full", counts["qk_full_pairs"],
        counts["kv_full_rows"] + counts["kv_shared_rows"],
        counts["tail_rows"] * _layers(cfg, "full", "cross"))


def window_attention(cfg, counts):
    return _attention(cfg, "window", counts["qk_window_pairs"],
                      counts["kv_window_rows"],
                      counts["queries"] * _layers(cfg, "window"))


PARTS = {"scan_update": (scan_update,), "scan_window": (scan_window,),
         "shared_attention": (shared_attention,),
         "window_attention": (window_attention,),
         "chunk_attention": (shared_attention, window_attention)}
# The names the shared entries of ``BENCHMARK.json`` ask every model for: a
# decode step's state layers, a chunk's, the full layer's kernel.
PARTS.update(state_update=PARTS["scan_update"],
             state_scan=PARTS["scan_window"],
             full_attention=PARTS["shared_attention"])


def least_seconds(cfg, part, counts, peak):
    """The roofline's floor for one program kind's counters: for each of the
    part's call kinds the larger of operations over the chip's bf16 peak and
    bytes over its memory bandwidth (``peaks.json`` entry), summed."""
    return sum(max(flops / (peak["bf16_tflops"] * 1e12),
                   nbytes / (peak["hbm_gbps"] * 1e9))
               for flops, nbytes in (fn(cfg, counts) for fn in PARTS[part]))
