"""Operations and bytes of what gated delta-rule linear-attention layers add
to the program, from the server's counters over a stretch and the
configuration's sizes. What the algorithm needs, as ``flops.py`` counts: a
multiply-add is two operations; bytes are the LEAST a part has to move, so a
share of the roofline computed from them cannot be flattered by traffic the
program chose to have. The same work whatever implements it: nothing here
reads how the program computes a layer.

Two parts, each ``part(cfg, counts) -> (operations, bytes)``:

- ``delta_update``: a decode step's linear layers, from ``counts["delta_rows"]``
  ((slot, layer) rows carried), ``counts["delta_bytes"]`` (their tail and
  float32 state read and written back) and ``counts["calls"]``: every layer's
  weights once a step; each row's projections (q, k, v and out, the decay's
  and the gate's low-rank pairs, ``beta``) and convolutions, and ``7 d^2``
  operations a head for the decay, ``S'^T k``, the rank-one add and the
  read-out; the token in and out;
- ``delta_scan``: a chunk's linear layers, from ``counts["delta_tokens"]``
  ((token, layer) positions passed over), ``counts["delta_rows"]``,
  ``counts["delta_bytes"]`` and ``counts["calls"]``: the projections and the
  convolutions a token; the recurrence at the chunked algorithm's own count for
  blocks of ``C`` = 64 positions, a token and head ``5 C d + 6 d^2``: the
  decayed key-key and query-key products of a block's lower triangle (``C d``
  each), the triangular system solved by substitution for ``2 d`` right-hand
  columns (``2 C d``), the query-key product times the corrections (``C d``),
  and four products with the ``[d, d]`` state (``W S``, ``(q exp G) S``, the
  block's effect on it: ``6 d^2``); weights once a call and layer, the state
  in and out, the tokens in and out.

The counters are ``hvd.serve_stats()["state"]`` by program kind; the
configuration is the dict of a file under ``benchmark/configs`` with the
source's key names, ``layer_types[layers_run]`` saying which layers are
``linear_attention`` and ``kda_low_rank`` the width of the low-rank pairs.
"""

BYTES = 2       # bfloat16 weights and activations
BLOCK = 64      # positions of a block of the chunked form


def _sizes(cfg):
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    return heads, d, heads * d, lin["short_conv_kernel_size"], \
        cfg["kda_low_rank"]


def _linear_layers(cfg):
    first, end = cfg["layers_run"]
    return cfg["layer_types"][first:end].count("linear_attention")


def _weights(cfg):
    """Parameters of one linear layer's matrices."""
    heads, _, hd, kernel, r = _sizes(cfg)
    hidden = cfg["hidden_size"]
    return (4 * hidden * hd + 2 * (hidden * r + r * hd) + hidden * heads
            + 3 * hd * kernel)


def _token_flops(cfg):
    """Operations a token a layer outside the recurrence: every matrix once
    (the depthwise convolutions' taps among them)."""
    return 2 * _weights(cfg)


def _least_bytes(cfg, counts, tokens):
    """The rows' tail and state both ways, every linear layer's weights once
    a call, ``tokens`` (token, layer) positions in and out."""
    return (counts["delta_bytes"]
            + counts["calls"] * _linear_layers(cfg) * _weights(cfg) * BYTES
            + tokens * 2 * cfg["hidden_size"] * BYTES)


def delta_update(cfg, counts):
    heads, d, *_ = _sizes(cfg)
    rows = counts["delta_rows"]
    flops = rows * (_token_flops(cfg) + 7 * heads * d * d)
    return flops, _least_bytes(cfg, counts, rows)


def delta_scan(cfg, counts):
    heads, d, *_ = _sizes(cfg)
    tokens = counts["delta_tokens"]
    flops = tokens * (_token_flops(cfg)
                      + heads * (5 * BLOCK * d + 6 * d * d))
    return flops, _least_bytes(cfg, counts, tokens)


PARTS = {"delta_update": delta_update, "delta_scan": delta_scan}


def least_seconds(cfg, part, counts, peak):
    """The roofline's floor for one program kind's counters: the larger of
    operations over the chip's bf16 peak and bytes over its memory bandwidth
    (``peaks.json`` entry)."""
    flops, nbytes = PARTS[part](cfg, counts)
    return max(flops / (peak["bf16_tflops"] * 1e12),
               nbytes / (peak["hbm_gbps"] * 1e9))
