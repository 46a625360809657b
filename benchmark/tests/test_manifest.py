"""``BENCHMARK.json``'s ``per_layer`` says each reading once, and the one
roofline reader prices a part by the module the cell's configuration names.

The list and its files against each other; ``trace_roofline`` on a hand-made
trace against the formula written out here (the nine readers it took the
place of computed exactly this); every accepted cell's configuration against
the parts its entries ask for.
"""
import importlib
import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import trace_roofline, trace_scope_in_program
from conftest import CHECKOUT

BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
MAX_ENTRIES = 100       # the contract allows 128: the rest is the next cells'


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = _load(CHECKOUT, "BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: _load(CHECKOUT, c["file"]) for c in BENCH["configs"]}
PEAKS = _load(BENCH_DIR, "peaks.json")
PEAK = PEAKS["devices"]["TPU v5 lite"]


def _file(name):
    return _load(BENCH_DIR, "layer_metrics", name + ".json")


def _cells_of(metric):
    if "workloads" in metric:
        return metric["workloads"]
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    return e2e.get("workloads", list(CELLS))


def test_every_entry_has_its_file_and_every_file_its_entry():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    files = {f[:-len(".json")]
             for f in os.listdir(os.path.join(BENCH_DIR, "layer_metrics"))}
    assert files == set(names)
    assert len(names) <= MAX_ENTRIES
    assert "index_select_kernel_dev_ms" in names


def test_no_reading_is_listed_twice():
    """Two entries with one file's content, unit, side, layer and end-to-end
    metric are one reading: it gets one name and the union of the cells."""
    seen = {}
    for m in BENCH["per_layer"]:
        key = (json.dumps(_file(m["name"]), sort_keys=True), m["unit"],
               m["better"], m["layer"], m["moves"])
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_a_name_carries_no_cells_traffic():
    """``.over`` and ``.train`` say which end-to-end metric a quantity moves
    where it moves two; nothing else follows the dot."""
    for m in BENCH["per_layer"]:
        suffix = m["name"].partition(".")[2]
        assert suffix in ("", "over", "train"), m["name"]


def test_every_cell_an_entry_lists_is_a_cell_that_reports_what_it_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        listed = m.get("workloads", [])
        assert len(listed) == len(set(listed)), m["name"]
        for cell in listed:
            assert cell in CELLS, (m["name"], cell)
            assert cell in e2e[m["moves"]].get("workloads", CELLS), (
                m["name"], cell)


# every counter name a ``flops_*.py`` reads
NAMES = ["pairs", "expert_reads", "calls", "rows", "bytes", "tokens",
         "kv_bytes", "queries", "kv_scored", "kv_selected", "kv_window",
         "kv_full_rows", "kv_window_rows", "qk_full_pairs", "qk_window_pairs",
         "kv_latent_rows", "qk_latent_pairs", "delta_rows", "delta_bytes",
         "delta_tokens", "kv_shared_rows", "tail_rows", "fill_rows",
         "scan_rows", "scan_bytes", "scan_tokens", "resets"]

ROOFLINES = [(m["name"], cell) for m in BENCH["per_layer"]
             if _file(m["name"])["reader"] == "trace_roofline"
             for cell in _cells_of(m)]


@pytest.mark.parametrize("metric,cell", ROOFLINES)
def test_a_listed_cells_configuration_prices_the_part(metric, cell):
    """A roofline entry lists a cell only if the cell's configuration names a
    module for the entry's part, and that module gives the part a floor above
    nought from the configuration's own keys."""
    part = _file(metric)["params"]["part"]
    config = CONFIGS[CELLS[cell]["config"]]
    assert part in config["flops"], (metric, cell)
    module = importlib.import_module("benchmark." + config["flops"][part])
    assert module.least_seconds(config, part, dict.fromkeys(NAMES, 1000),
                                PEAK) > 0


def test_every_configuration_names_its_pricing_by_part():
    for name, config in CONFIGS.items():
        assert isinstance(config["flops"], dict), name
        for part, module in config["flops"].items():
            assert os.path.exists(os.path.join(BENCH_DIR, module + ".py"))
            asked = [m for m, cell in ROOFLINES
                     if CELLS[cell]["config"] == name
                     and _file(m)["params"]["part"] == part]
            assert asked, (name, part, "no entry of its cells asks for it")


def test_no_reader_imports_a_pricing_module_by_name():
    readers = os.path.join(BENCH_DIR, "readers")
    rooflines = [f for f in os.listdir(readers) if "roofline" in f]
    assert rooflines == ["trace_roofline.py"]
    for f in os.listdir(readers):
        if f.endswith(".py"):
            with open(os.path.join(readers, f)) as src:
                text = src.read()
            assert "import flops_" not in text.replace("benchmark ", ""), f
            assert "benchmark.flops_" not in text, f


# ---- trace_roofline against the formula, on a hand-made trace --------------

MS = 1_000_000
# (instruction, start, length): two runs of jit_chunk, one of jit_decode, a
# stray program; the kernel ``k`` runs in each, ``other`` is not matched
OPS = [("%k.1 = bf16[8] custom-call()", 0 * MS, 3 * MS),
       ("%other.2 = bf16[8] fusion()", 3 * MS, 1 * MS),
       ("%k.3 = bf16[8] custom-call()", 10 * MS, 2 * MS),
       ("%k.4 = bf16[8] custom-call()", 20 * MS, 5 * MS),
       ("%k.5 = bf16[8] custom-call()", 30 * MS, 7 * MS)]
MODULES = [("jit_chunk(1)", 0 * MS, 5 * MS), ("jit_chunk(1)", 10 * MS, 4 * MS),
           ("jit_decode(2)", 20 * MS, 6 * MS),
           ("jit_stray(3)", 30 * MS, 8 * MS)]
K_NS = {None: 17 * MS, r"^jit_chunk\b": 5 * MS, r"^jit_decode\b": 5 * MS,
        r"^jit_(chunk|decode)\b": 10 * MS}


def _trace():
    names = sorted({n for n, _, _ in OPS + MODULES})
    ix = {n: i for i, n in enumerate(names)}

    def line(name, evs):
        return {"name": name, "n": [ix[n] for n, _, _ in evs],
                "s": [s for _, s, _ in evs], "d": [d for _, _, d in evs]}

    return {"names": names, "planes": [{"name": "/device:TPU:0", "lines": [
        line(tr.OPS_LINE, OPS), line(tr.MODULES_LINE, MODULES)]}]}


def _ctx(config, counts):
    return {"trace": _trace(), "fields": {"trace_counts": counts},
            "record": {"device": {"kind": "TPU v5 lite"}},
            "spec": {"config": config}, "peaks": PEAKS}


def _counts(kinds):
    """{name: {kind: n}} with every name a pricing reads, a kind a number."""
    return {name: {kind: 1000 * (i + 1) for i, kind in enumerate(kinds)}
            for name in NAMES}


# one part of each pricing module, on the configuration that names it
PRICED = [("olmoe-1b-7b", "expert_products"),
          ("dots3-note-prev", "sparse_attention"),
          ("laguna-s-2.1", "chunk_attention"),
          ("mimo-v2-flash", "window_attention"),
          ("nemotron-3-super-120b", "state_scan"),
          ("sarvam-105b", "latent_attention"),
          ("solar-open2-250b", "delta_scan"),
          ("solar-open2-250b", "chunk_attention"),
          ("phi-4-mini-flash-reasoning", "full_attention"),
          ("granite-4.0-h-micro", "state_update")]


def _floor(config, part, counts, kinds):
    module = importlib.import_module("benchmark." + config["flops"][part])
    return sum(module.least_seconds(
        config, part, {name: counts[name][kind] for name in NAMES}, PEAK)
        for kind in kinds)


@pytest.mark.parametrize("name,part", PRICED)
@pytest.mark.parametrize("program", list(K_NS))
def test_the_share_is_the_floor_over_the_kernels_time(name, part, program):
    """By ``pattern``: the part's floor summed over the kinds asked for (or
    over every kind the counters hold), over the kernel's device time inside
    the programs asked for (or wherever it runs), in percent."""
    config = CONFIGS[name]
    kinds = ["chunk", "decode"]
    counts = _counts(kinds)
    params = {"part": part, "counts_field": "trace_counts", "pattern": r"^k\."}
    want_kinds = kinds
    if program is not None:
        params["program"] = program
        if "|" not in program:
            want_kinds = params["kinds"] = [program[5:-2]]
    got = trace_roofline.read(_ctx(config, counts), params)
    want = 100.0 * _floor(config, part, counts, want_kinds) / (
        K_NS[program] * 1e-9)
    assert got == pytest.approx(want, rel=1e-12) and got > 0


@pytest.mark.parametrize("program", [None, r"^jit_decode\b"])
def test_the_share_by_scope_divides_by_the_scopes_self_time(monkeypatch,
                                                            program):
    config = CONFIGS["granite-4.0-h-micro"]
    counts = _counts(["decode"])
    asked = []

    def scope_ns(ctx, scope, prog):
        asked.append((scope, prog))
        return 4 * MS, 2.0

    monkeypatch.setattr(trace_scope_in_program, "scope_ns", scope_ns)
    params = {"part": "state_update", "counts_field": "trace_counts",
              "kinds": ["decode"], "scope": "state_space"}
    if program:
        params["program"] = program
    got = trace_roofline.read(_ctx(config, counts), params)
    assert asked == [("state_space", program or "")]
    assert got == pytest.approx(
        100.0 * _floor(config, "state_update", counts, ["decode"]) / 4e-3,
        rel=1e-12)


def test_the_moe_floor_is_the_hand_counted_one():
    """``flops_moe`` under the common signature, on sizes small enough to
    count: D 4, F 8, bf16; 10 pairs on 3 expert reads."""
    from benchmark import flops_moe
    cfg = {"hidden_size": 4, "intermediate_size": 8,
           "model": {"param_dtype": "bfloat16"}}
    flops, nbytes = 2 * 3 * 4 * 8 * 10, (3 * 4 * 8 * 3 + 36 * 10) * 2
    want = max(flops / 197e12, nbytes / 819e9)
    assert flops_moe.products_least_seconds(cfg, 10, 3, PEAK) == want
    assert flops_moe.least_seconds(
        cfg, "expert_products", {"pairs": 10, "expert_reads": 3}, PEAK) == want
    ctx = _ctx(dict(cfg, flops={"expert_products": "flops_moe"}),
               {"pairs": {"decode": 10}, "expert_reads": {"decode": 3},
                "calls": {"decode": 1}})
    got = trace_roofline.read(ctx, {
        "part": "expert_products", "counts_field": "trace_counts",
        "pattern": r"^k\."})
    assert got == pytest.approx(100.0 * want / 17e-3, rel=1e-12)


def test_what_a_run_lacks_reads_none_and_raises_nothing():
    config = CONFIGS["laguna-s-2.1"]
    counts = _counts(["decode"])
    params = {"part": "full_attention", "counts_field": "trace_counts",
              "kinds": ["decode"], "pattern": r"^k\.",
              "program": r"^jit_decode\b"}
    assert trace_roofline.read(_ctx(config, counts), params) > 0
    # a part the configuration does not list
    assert trace_roofline.read(
        _ctx(config, counts), dict(params, part="state_scan")) is None
    assert trace_roofline.read(
        _ctx(CONFIGS["gpt2-large"], counts), params) is None
    assert trace_roofline.read(
        _ctx({k: v for k, v in config.items() if k != "flops"}, counts),
        params) is None
    # no such kernel, no such program, no counters, no work counted
    assert trace_roofline.read(
        _ctx(config, counts), dict(params, pattern="^absent")) is None
    assert trace_roofline.read(
        _ctx(config, counts), dict(params, program="^jit_absent")) is None
    assert trace_roofline.read(_ctx(config, None), params) is None
    zero = {name: {"decode": 0} for name in NAMES}
    assert trace_roofline.read(_ctx(config, zero), params) is None
    # counters from before the names the part's pricing reads
    old = {"queries": {"decode": 5}}
    assert trace_roofline.read(_ctx(config, old), params) is None


def test_a_model_of_full_layers_alone_has_no_window_floor():
    """Solar's chunk attention is its full layers' alone: ``flops_gqa``'s
    ``chunk_attention`` adds nothing for a kind no layer has, and asks the
    counters for no window name."""
    from benchmark import flops_gqa
    config = CONFIGS["solar-open2-250b"]
    counts = {"qk_full_pairs": 4_000_000, "kv_full_rows": 90_000,
              "queries": 2048}
    assert flops_gqa.least_seconds(config, "chunk_attention", counts, PEAK) \
        == flops_gqa.least_seconds(config, "full_attention", counts, PEAK) > 0


# ---- the recorded traces read what they read -------------------------------

# What the tree before PR 64 read from the three cuts of real v5e traces kept
# beside ``trace_reduce.py`` (train, four-chip train, gpt2-large's server; they
# predate every kernel a renamed entry reads, so those read nothing, then and
# now): (file, ``trace_steps``) -> {entry: value}.
RECORDED = {
    ("recorded_trace", 2): {
        "step_dev_ms": 78.8946105,
        "allreduce_exposed_ms": 0.0,
        "device_idle_share.train": 0.03013366028881137,
        "prefill_dev_share.over": 0.0,
        "device_idle_share.over": 0.03013366028881137,
        "allreduce_async_exposed_ms": 0.0,
    },
    ("recorded_trace_dp4", 1): {
        "step_dev_ms": 107.763733,
        "allreduce_ms": 24.830584,
        "allreduce_exposed_ms": 24.830584,
        "device_idle_share.train": 0.01194693368020694,
        "prefill_dev_share.over": 0.0,
        "device_idle_share.over": 0.01194693368020694,
        "allreduce_async_exposed_ms": 0.0,
    },
    ("recorded_trace_serve", None): {
        "device_idle_share.train": 3.1182651865727107,
        "prefill_dev_share.over": 55.615678836358995,
        "device_idle_share.over": 3.1182651865727107,
        "decode_step_dev_ms": 44.063156000000006,
        "idle_sched_ms.over": 0.43484375,
        "idle_report_ms.over": 0.0046925,
        "idle_launch_ms.over": 0.22615225,
        "idle_fetch_ms.over": 2.4674375,
        "idle_unspanned_share.over": 2.006954786848286,
    },
}
NO_TRACE = ("record_field", "startup_account", "xplane_scopes",
            "model_flops_utilization")


@pytest.mark.parametrize("cut,steps", list(RECORDED))
def test_a_recorded_trace_reads_what_it_read(cut, steps):
    trace = tr.load(os.path.join(BENCH_DIR, cut + ".json.gz"))
    got = {}
    for m in BENCH["per_layer"]:
        src = _file(m["name"])
        if src["reader"] in NO_TRACE:
            continue
        reader = importlib.import_module("benchmark.readers." + src["reader"])
        ctx = {"trace": trace, "fields": {"trace_steps": steps},
               "record": {"device": {"kind": "TPU v5 lite"}},
               "spec": {"config": {}}, "peaks": PEAKS}
        value = reader.read(ctx, src.get("params", {}))
        if value is not None:
            got[m["name"]] = value
    assert got == RECORDED[(cut, steps)]
