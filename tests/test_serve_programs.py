"""``ServeLoop``'s programs kept across starts (``serving/programs.py``).

A tiny float32 model on the CPU with JAX's persistent compilation cache in a
temporary directory: a second loop of the same arguments loads every program
it warms with no trace and no lowering and computes the same bits; each part
of the key misses when it changes; an entry that does not load is a counted
miss and a fresh compile; with no cache directory the loop's programs are the
``jax.jit`` objects of the engine.
"""

import contextlib
import dataclasses
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from horovod_tpu.models import transformer as tfm
from horovod_tpu.observability import startup
from horovod_tpu.serving import kv_cache, programs
from horovod_tpu.serving.loop import ServeLoop

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OWNED = ("jit_prefill", "jit_decode", "jit_bprefill", "jit_chunk")
PROGRAMS = ("prefill_fn", "decode_fn", "bprefill_fn", "chunk_fn")
_SETTINGS = {"jax_compilation_cache_dir": None,
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """JAX's persistent cache switched on in a directory of this module's,
    every program kept however quickly it compiled; as it was afterwards."""
    path = str(tmp_path_factory.mktemp("jax_cache"))
    was = {name: getattr(jax.config, name) for name in _SETTINGS}
    for name, value in {**_SETTINGS,
                        "jax_compilation_cache_dir": path}.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield path
    for name, value in was.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.fixture
def acct(monkeypatch, tmp_path):
    """A fresh start-up account that JAX's events and the store reach."""
    monkeypatch.setenv(startup.LOG_ENV, str(tmp_path / "log.jsonl"))
    a = startup.Account()
    a.listen()
    monkeypatch.setattr(startup, "account", a)
    yield a
    jax.monitoring.unregister_event_duration_listener(a.on_duration)
    jax.monitoring.unregister_event_listener(a.on_event)
    jax.monitoring.unregister_scalar_listener(a.on_scalar)


CFG = tfm.tiny()


def _params(cfg=CFG):
    return tfm.init_params(jax.random.PRNGKey(0), cfg)


def _loop(params=None, cfg=CFG, geo=None, **kw):
    kw.setdefault("max_batch", 4)
    return ServeLoop(_params(cfg) if params is None else params, cfg,
                     geo=geo or kv_cache.geometry(64, 8, 64), **kw)


def _decode(loop):
    """One decode step over a clean cache -> its logits on the host."""
    B, mb = loop.max_batch, loop.geo.table_width
    tables = np.arange(B * mb, dtype=np.int32).reshape(B, mb) % 63 + 1
    loop.cache, logits, *_ = loop.decode_fn(
        loop.params, loop.cache, np.arange(B, dtype=np.int32) + 7,
        np.zeros(B, np.int32), tables, np.ones(B, bool))
    return np.asarray(logits)


def _chunk(loop):
    q, mb = loop.prefill_chunk, loop.geo.table_width
    tables = np.arange(mb, dtype=np.int32)[None] + 1
    loop.cache, logits, *_ = loop.chunk_fn(
        loop.params, loop.cache, np.arange(q, dtype=np.int32)[None] + 3,
        np.zeros(1, np.int32), tables, np.ones(1, bool))
    return np.asarray(logits)


@pytest.fixture(scope="module")
def warmed(cache_dir):
    """The directory after one loop's cold start: its four programs kept."""
    loop = _loop()
    loop.warmup()
    return cache_dir


def _kept(cache_dir):
    return sorted(os.path.basename(f)[:-len("-cache")] for f in glob.glob(
        os.path.join(cache_dir, "hvd_*-cache")))


@contextlib.contextmanager
def _no_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_without_a_cache_directory_the_programs_are_plain_jit():
    with _no_cache_dir():
        assert not programs.on()
        loop = _loop()
    jitted = type(jax.jit(lambda: 0))
    for name in PROGRAMS:
        assert type(getattr(loop, name)) is jitted, name


def test_a_cold_start_keeps_one_small_entry_a_program(warmed):
    kept = _kept(warmed)
    assert [k.split("-")[0] for k in kept] == [
        "hvd_jit_bprefill", "hvd_jit_chunk", "hvd_jit_decode",
        "hvd_jit_prefill"]
    for key in kept:
        # The trees, avals and shardings; the executable stays JAX's entry.
        assert os.path.getsize(os.path.join(warmed, key + "-cache")) < 65536
    for row in OWNED:
        assert len(glob.glob(os.path.join(warmed, row + "-*-cache"))) == 1


def test_a_second_loop_loads_what_it_warms_and_computes_the_same(warmed,
                                                                 acct):
    jax.clear_caches()
    loop = _loop()
    for name in PROGRAMS:
        fn = getattr(loop, name)
        assert isinstance(fn, programs.Program)
        assert fn.lower == fn._jit.lower and fn.__name__ == name[:-3]
    loop.warmup()
    stats = acct.stats()
    assert stats["counts"]["program_hits"] == 4
    assert stats["counts"]["program_misses"] == 0
    for row in OWNED:
        assert stats["sums"][row]["trace"] == 0.0 == stats["sums"][row]["lower"]
        assert stats["sums"][row]["compile"] == 0.0 < stats["sums"][row]["load"]
    loaded = _decode(loop), _chunk(loop)
    # Other parameters of the same avals run the same executable.
    loop.params = jax.tree_util.tree_map(lambda x: x + 0, loop.params)
    assert _decode(loop).shape == loaded[0].shape
    assert acct.stats()["counts"]["program_hits"] == 4
    assert acct.stats()["counts"]["program_misses"] == 0

    with _no_cache_dir():
        plain = _loop()
    assert not isinstance(plain.decode_fn, programs.Program)
    np.testing.assert_array_equal(loaded[0], _decode(plain))
    np.testing.assert_array_equal(loaded[1], _chunk(plain))


def _other_dtype():
    return dict(params=jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), _params()))


@pytest.mark.parametrize("change,program,hits", [
    (dict, _decode, 1),
    (lambda: dict(cfg=dataclasses.replace(CFG, norm_eps=1e-6)), _decode, 0),
    (lambda: dict(geo=kv_cache.geometry(32, 8, 64)), _decode, 0),
    (lambda: dict(max_batch=2), _decode, 0),
    (lambda: dict(prefill_chunk=8), _chunk, 0),
    (lambda: dict(prefill_chunk=8), _decode, 1),     # not that program's
    (_other_dtype, _decode, 0),
], ids=["same", "cfg", "geo", "max_batch", "prefill_chunk",
        "prefill_chunk_decode", "params_dtype"])
def test_each_part_of_the_key_misses_when_it_changes(warmed, acct, change,
                                                     program, hits):
    program(_loop(**change()))
    counts = acct.stats()["counts"]
    assert (counts["program_hits"], counts["program_misses"]) == (
        hits, 1 - hits)


def test_an_edit_to_the_package_misses_everything(warmed, acct, monkeypatch):
    assert len(programs.package_hash()) == 64
    monkeypatch.setattr(programs, "package_hash", lambda: "edited")
    loop = _loop()
    _decode(loop), _chunk(loop)
    counts = acct.stats()["counts"]
    assert (counts["program_hits"], counts["program_misses"]) == (0, 2)


def test_the_environment_is_in_the_key(warmed, monkeypatch):
    fn = _loop().decode_fn
    base = fn.key(("tree", ()))
    assert base == fn.key(("tree", ())) != fn.key(("other tree", ()))
    for name in ("XLA_FLAGS", "LIBTPU_INIT_ARGS"):
        monkeypatch.setenv(name, "--a_flag=1")
        assert fn.key(("tree", ())) != base
        monkeypatch.delenv(name)
    monkeypatch.setattr(jax, "__version__", "0.0.0")
    assert fn.key(("tree", ())) != base


@pytest.mark.parametrize("damage", ["truncated", "orphaned"])
def test_an_entry_that_does_not_load_is_a_miss_and_compiles(
        tmp_path, acct, cache_dir, damage, caplog):
    """Its own directory: the store's entry cut short, or JAX's executable
    gone from under it (an eviction)."""
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    try:
        want = _decode(_loop())
        assert acct.stats()["counts"]["program_misses"] == 1
        (entry,) = glob.glob(str(tmp_path / "hvd_jit_decode-*-cache"))
        (executable,) = glob.glob(str(tmp_path / "jit_decode-*-cache"))
        if damage == "truncated":
            with open(entry, "rb") as f:
                blob = f.read()
            with open(entry, "wb") as f:
                f.write(blob[:len(blob) // 2])
        else:
            os.remove(executable)
        jax.clear_caches()
        got = _decode(_loop())
        counts = acct.stats()["counts"]
        assert (counts["program_hits"], counts["program_misses"]) == (0, 2)
        np.testing.assert_array_equal(got, want)
        assert "did not load" in caplog.text
        # The fresh compile left a sound entry behind: the next loop loads it.
        jax.clear_caches()
        np.testing.assert_array_equal(_decode(_loop()), want)
        assert acct.stats()["counts"]["program_hits"] == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()


def test_a_call_with_other_avals_takes_its_own_executable(warmed, acct):
    loop = _loop()
    first = _decode(loop)
    # The same step from tokens on the device: the executable in hand.
    B, mb = loop.max_batch, loop.geo.table_width
    rest = (np.zeros(B, np.int32), np.zeros((B, mb), np.int32),
            np.zeros(B, bool))
    loop.cache, lg, *_ = loop.decode_fn(
        loop.params, loop.cache, jnp.zeros(B, jnp.int32), *rest)
    assert acct.stats()["counts"]["program_hits"] == 1
    assert acct.stats()["counts"]["program_misses"] == 0
    # Parameters of another dtype: refused by that executable before it
    # runs, compiled through ``jit``, counted; then the first ones again.
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                  loop.params)
    loop.cache, lg, *_ = loop.decode_fn(
        half, loop.cache, np.zeros(B, np.int32), *rest)
    assert lg.shape == first.shape
    loop.cache, lg, *_ = loop.decode_fn(
        loop.params, loop.cache, np.zeros(B, np.int32), *rest)
    counts = acct.stats()["counts"]
    assert counts["program_hits"] + counts["program_misses"] == 2


def test_under_a_transformation_it_is_the_jit_object(warmed, acct):
    loop = _loop()
    B, mb = loop.max_batch, loop.geo.table_width
    args = (np.arange(B, dtype=np.int32) + 7, np.zeros(B, np.int32),
            np.arange(B * mb, dtype=np.int32).reshape(B, mb) % 63 + 1,
            np.ones(B, bool))
    outer = jax.jit(lambda params, cache, *rest:
                    loop.decode_fn(params, cache, *rest)[1])
    logits = np.asarray(outer(loop.params, loop.cache, *args))
    counts = acct.stats()["counts"]
    assert (counts["program_hits"], counts["program_misses"]) == (0, 0)
    np.testing.assert_allclose(logits, _decode(loop), rtol=1e-5, atol=1e-6)


CHILD = r"""
import json, sys
import numpy as np
import jax
for name, value in json.loads(sys.argv[1]).items():
    jax.config.update(name, value)
import horovod_tpu as hvd
from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import kv_cache
from horovod_tpu.serving.loop import ServeLoop, poisson_requests
cfg = tfm.tiny()
loop = ServeLoop(tfm.init_params(jax.random.PRNGKey(0), cfg), cfg,
                 geo=kv_cache.geometry(64, 8, 64), max_batch=4)
loop.warmup()
stats = hvd.startup_stats()
_, done = loop.run(poisson_requests(6, 1e6, np.random.default_rng(0)))
print(json.dumps({"counts": stats["counts"], "sums": stats["sums"],
                  "tokens": [r.generated for r in done]}))
"""


def test_a_second_process_starts_warm(tmp_path):
    """The real thing: two processes, one directory."""
    settings = json.dumps({**_SETTINGS, "jax_compilation_cache_dir":
                           str(tmp_path / "cache")})
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT,
               HVD_STARTUP_LOG=str(tmp_path / "startup.jsonl"))
    runs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", CHILD, settings], env=env,
                           timeout=300, capture_output=True, text=True)
        assert p.returncode == 0, p.stderr[-2000:]
        runs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["counts"]["program_misses"] == 4
    assert cold["counts"]["program_hits"] == 0
    assert warm["counts"]["program_hits"] == 4
    assert warm["counts"]["program_misses"] == 0
    assert warm["counts"]["cache_misses"] == 0
    for row in OWNED:
        assert cold["sums"][row]["lower"] > 0.0
        assert warm["sums"][row]["trace"] == 0.0 == warm["sums"][row]["lower"]
    assert warm["tokens"] == cold["tokens"] and len(warm["tokens"]) == 6
