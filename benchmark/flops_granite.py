"""Operations and bytes of the parts of a dense state-space and attention
hybrid whose configuration carries ``granitemoehybrid``'s key names
(``mamba_n_heads``, ``mamba_d_head``, ``mamba_n_groups``, ``mamba_d_state``,
``mamba_d_conv``, ``mamba_chunk_size``, ``layer_types`` of ``mamba`` and
``attention``), from the server's counters over a stretch. Nothing is counted
anew here: the configuration is put under the names that
``flops_hybrid.py`` (the state-space update and scan) and ``flops_gqa.py``
(the paged grouped-query kernel) read, and their functions are called at this
shape, so the four rooflines of this model are the accepted counts.

``part(cfg, counts) -> (operations, bytes)`` for ``state_update``,
``state_scan``, ``full_attention`` and ``chunk_attention``; the counters are
``hvd.serve_stats()["state"]`` and ``["attn"]`` by program kind.
"""

from benchmark import flops_gqa, flops_hybrid


def as_hybrid(cfg):
    """``cfg`` under ``flops_hybrid``'s names: every layer of kind ``mamba``
    an ``M`` of a pattern that is run whole."""
    pattern = "".join("M" if kind == "mamba" else "*"
                      for kind in cfg["layer_types"])
    return {"mamba_num_heads": cfg["mamba_n_heads"],
            "mamba_head_dim": cfg["mamba_d_head"],
            "n_groups": cfg["mamba_n_groups"],
            "ssm_state_size": cfg["mamba_d_state"],
            "conv_kernel": cfg["mamba_d_conv"],
            "chunk_size": cfg["mamba_chunk_size"],
            "hidden_size": cfg["hidden_size"],
            "hybrid_override_pattern": pattern,
            "layers_run": [0, len(pattern)]}


def as_gqa(cfg):
    """``cfg`` under ``flops_gqa``'s names: every layer of kind ``attention``
    a ``full_attention`` layer, no window layer."""
    heads = cfg["num_attention_heads"]
    return {"layer_types": ["full_attention" if kind == "attention"
                            else kind for kind in cfg["layer_types"]],
            "num_hidden_layers": cfg["num_hidden_layers"],
            "heads_by_kind": {"full_attention": heads,
                              "sliding_attention": heads},
            "head_dim": cfg["hidden_size"] // heads,
            "num_key_value_heads": cfg["num_key_value_heads"]}


def _attn_counts(counts):
    return dict({"qk_window_pairs": 0, "kv_window_rows": 0}, **counts)


PARTS = {
    "state_update": lambda cfg, c: flops_hybrid.state_update(as_hybrid(cfg), c),
    "state_scan": lambda cfg, c: flops_hybrid.state_scan(as_hybrid(cfg), c),
    "full_attention": lambda cfg, c: flops_gqa.full_attention(
        as_gqa(cfg), _attn_counts(c)),
    # the chunk program's calls of the one kernel: this model has no window
    "chunk_attention": lambda cfg, c: flops_gqa.full_attention(
        as_gqa(cfg), _attn_counts(c)),
}


def least_seconds(cfg, part, counts, peak):
    """The roofline's floor for one program kind's counters: the larger of
    operations over the chip's bf16 peak and bytes over its memory bandwidth
    (``peaks.json`` entry)."""
    flops, nbytes = PARTS[part](cfg, counts)
    return max(flops / (peak["bf16_tflops"] * 1e12),
               nbytes / (peak["hbm_gbps"] * 1e9))
