"""Operations and bytes of what a hybrid of state-space, attention and
latent-expert layers adds to the program, from the server's counters over a
stretch and the configuration's sizes. What the algorithm needs, as
``flops.py`` counts: a multiply-add is two operations; bytes are the LEAST a
part has to move, so a share of the roofline computed from them cannot be
flattered by traffic the program chose to have.

Three parts, each ``part(cfg, counts) -> (operations, bytes)``:

- ``expert_products``: the routed experts' TWO products in the latent (no gate
  matrix: ``relu(l W1)^2 W2`` at ``moe_latent_size x moe_intermediate_size``)
  from ``counts["pairs"]`` (routed (token, expert) pairs of the experts held
  here) and ``counts["expert_reads"]`` ((layer, expert) weights a program run
  touched: both matrices cross the bus once, however many rows came);
- ``state_update``: a decode step's state-space layers, from ``counts["rows"]``
  ((slot, layer) rows carried), ``counts["bytes"]`` (their tail and float32
  state read and written back) and ``counts["calls"]``: every layer's in- and
  out-projection once a step, each live row's projections, convolution, the
  update of ``[heads, head_dim, state]`` and its read-out;
- ``state_scan``: a chunk's state-space layers, from ``counts["tokens"]``
  ((token, layer) positions scanned), ``counts["rows"]``, ``counts["bytes"]``
  and ``counts["calls"]``: the projections and the convolution a token, the
  in-block products at the chunked algorithm's own count for blocks of
  ``chunk_size`` positions (every (query, key) pair of a block: ``C B^T`` a
  group and the decayed mix times ``x`` a head), the block's effect on the
  state and the carried state's read-out; weights once a call and layer, the
  state in and out, the tokens in and out.

The counters are ``hvd.serve_stats()["state"]`` and ``["moe"]`` by program
kind; the configuration is the dict of a file under ``benchmark/configs`` with
the source's key names.
"""

BYTES = 2       # bfloat16 weights and activations


def _ssm(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_inner = heads * p
    conv_dim = d_inner + 2 * groups * n
    return heads, p, groups, n, d_inner, conv_dim, d_inner + conv_dim + heads


def _state_layers(cfg):
    first, end = cfg["layers_run"]
    return cfg["hybrid_override_pattern"][first:end].count("M")


def _projection_weights(cfg):
    """Bytes of one state-space layer's in- and out-projection."""
    *_, d_inner, _, in_width = _ssm(cfg)
    return cfg["hidden_size"] * (in_width + d_inner) * BYTES


def _token_flops(cfg):
    """Operations a token a layer outside the recurrence: both projections
    and the depthwise convolution."""
    *_, d_inner, conv_dim, in_width = _ssm(cfg)
    return (2 * cfg["hidden_size"] * (in_width + d_inner)
            + 2 * cfg["conv_kernel"] * conv_dim)


def expert_products(cfg, counts):
    latent, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    pairs = counts["pairs"]
    return (2 * 2 * latent * f * pairs,
            (2 * latent * f * counts["expert_reads"]
             + 2 * (latent + f) * pairs) * BYTES)


def state_update(cfg, counts):
    heads, p, _, n, *_ = _ssm(cfg)
    rows, calls = counts["rows"], counts["calls"]
    # decay, outer product and add into the state; the read-out with C.
    flops = rows * (_token_flops(cfg) + 5 * heads * p * n)
    nbytes = (counts["bytes"]
              + calls * _state_layers(cfg) * _projection_weights(cfg)
              + rows * 2 * cfg["hidden_size"] * BYTES)
    return flops, nbytes


def state_scan(cfg, counts):
    heads, p, groups, n, *_ = _ssm(cfg)
    q = cfg["chunk_size"]
    tokens, calls = counts["tokens"], counts["calls"]
    flops = tokens * (_token_flops(cfg)
                      + 2 * q * (groups * n + heads * p)   # in-block products
                      + 2 * 2 * heads * p * n)    # into and out of the state
    nbytes = (counts["bytes"]
              + calls * _state_layers(cfg) * _projection_weights(cfg)
              + tokens * 2 * cfg["hidden_size"] * BYTES)
    return flops, nbytes


PARTS = {"expert_products": expert_products, "state_update": state_update,
         "state_scan": state_scan}


def least_seconds(cfg, part, counts, peak):
    """The roofline's floor for one program kind's counters: the larger of
    operations over the chip's bf16 peak and bytes over its memory bandwidth
    (``peaks.json`` entry)."""
    flops, nbytes = PARTS[part](cfg, counts)
    return max(flops / (peak["bf16_tflops"] * 1e12),
               nbytes / (peak["hbm_gbps"] * 1e9))
