"""Operations and bytes of a MoE layer's grouped expert products, from the
routed pairs and the shapes. What the algorithm needs, as ``flops.py`` counts:
a multiply-add is two operations.

A routed (token, expert) PAIR is one row of the grouped products: it is
multiplied by the expert's gate and up matrices ``[D, F]`` and the result by
its down matrix ``[F, D]``. An EXPERT READ is one expert of one layer touched
by one program run: its three matrices cross the memory bus once, however
many rows it received (an expert no row reached is not read).

The configuration is the dict of a file under ``benchmark/configs`` with the
source's key names (``hidden_size``, ``intermediate_size``: the width of one
expert) and ``model.param_dtype``.
"""

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def expert_product_flops(cfg, pairs):
    """Gate, up and down products of ``pairs`` rows."""
    return 2 * 3 * cfg["hidden_size"] * cfg["intermediate_size"] * pairs


def expert_product_bytes(cfg, pairs, expert_reads):
    """Least traffic of the three products: each touched expert's three
    matrices once; every row read at width D twice (gate, up) and written at
    width F twice, then read at F and written at D."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    size = DTYPE_BYTES[cfg["model"]["param_dtype"]]
    weights = 3 * d * f * size * expert_reads
    rows = (2 * (d + f) + (f + d)) * size * pairs
    return weights + rows


def products_least_seconds(cfg, pairs, expert_reads, peak):
    """The roofline's floor: the larger of operations over the chip's bf16
    peak and bytes over its memory bandwidth (``peaks.json`` entry)."""
    return max(expert_product_flops(cfg, pairs) / (peak["bf16_tflops"] * 1e12),
               expert_product_bytes(cfg, pairs, expert_reads)
               / (peak["hbm_gbps"] * 1e9))


def least_seconds(cfg, part, counts, peak):
    """:func:`products_least_seconds` under the signature every ``flops_*.py``
    gives ``readers/trace_roofline.py``: one program kind's counters
    (``pairs``, ``expert_reads``), the one part ``expert_products``."""
    if part != "expert_products":
        raise KeyError(part)
    return products_least_seconds(cfg, counts["pairs"],
                                  counts["expert_reads"], peak)
