"""``ServeLoop``'s compiled programs, kept across starts.

JAX makes its persistent cache's key FROM a program's lowered module, so a
start whose every executable is on disk still traces and lowers each program
(a Pallas kernel down to Mosaic) before it can look. The loop knows what a
program will be before it traces it, so it keeps, beside JAX's entry and in
the same cache, what is needed to run that entry with no trace and no
lowering: the executable's argument and result trees, avals, shardings and
donation, under a key made of what decides the program.

**When it is on.** Exactly when the user has switched JAX's persistent
compilation cache on (``jax.config.jax_compilation_cache_dir``, in a job of
one process, :func:`on`): the loop then wraps each program in a
:class:`Program`. With no directory its programs are the ``jax.jit`` objects
the engine made, untouched. There is no knob of its own.

**What is stored.** One small entry a program (some KB), through JAX's own
cache object, so it lives under that cache's size cap and eviction. It is
``jax.experimental.serialize_executable``'s pickle of the compiled program
with the executable's bytes LEFT OUT: in their place stands the key of JAX's
own entry for the same compile, which is read back (and its recency
refreshed) at a load. One copy of a program's bytes on disk, and a tree
whose lowered modules did not change shares them with its parent.

**The key** (a stale hit would run the wrong program): the program's name
and static arguments, ``cfg`` and ``geo`` in full, the kernels the engine
chose, the mesh (axis names, shape, devices) or its absence, the tree,
avals and shardings of every argument (parameters included), the backend
with its platform version (libtpu's), ``jax`` and ``jaxlib``, ``XLA_FLAGS``
and ``LIBTPU_INIT_ARGS``, JAX's trace context (x64, matmul precision, ...),
and a content hash of every ``.py`` under ``horovod_tpu/`` (the donation is
the engine's code, and a Mosaic kernel carries its callers' line numbers;
any edit to the package misses everything, by design). What it cannot see:
a function of the package replaced at run time (a test's ``monkeypatch``) in
a process that has a cache directory.

**A miss** (no entry, JAX's entry evicted, an entry that does not load for
any reason, a call with other avals) lowers and compiles through the ``jit``
object as before, is counted, and leaves an entry behind where JAX kept the
executable. Nothing raises for the store's sake. The start-up account
(:mod:`horovod_tpu.observability.startup`) has ``counts.program_hits`` and
``counts.program_misses``, and a load's seconds in that program's ``load``.
"""

import contextlib
import functools
import hashlib
import io
import logging
import os
import time

import jax

from ..observability import startup as _startup

log = logging.getLogger(__name__)

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def on():
    """Whether the loop keeps its programs: JAX's persistent compilation
    cache has a directory, in a job of one process."""
    return bool(jax.config.jax_compilation_cache_dir
                and jax.config.jax_enable_compilation_cache
                and jax.process_count() == 1)


@functools.cache
def package_hash():
    """A content hash of every ``.py`` under ``horovod_tpu/``, by relative
    path, read once a process."""
    h = hashlib.sha256()
    for root, dirs, files in os.walk(_PACKAGE):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, _PACKAGE).encode())
                with open(path, "rb") as f:
                    h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


def _environment():
    """What decides a compile beside the program and its arguments."""
    import jaxlib
    from jax._src import config

    return (jax.__version__, jaxlib.__version__, jax.default_backend(),
            jax.devices()[0].client.platform_version,
            os.environ.get("XLA_FLAGS", ""),
            os.environ.get("LIBTPU_INIT_ARGS", ""),
            repr(config.trace_context()), package_hash())


def _mesh(mesh):
    if mesh is None:
        return None
    return (mesh.axis_names, mesh.devices.shape,
            [(d.id, d.device_kind) for d in mesh.devices.flat])


def _signature(args):
    """The tree of a call's arguments and each leaf's shape, dtype, weak
    type and placement: what a compiled program is specialised to."""
    leaves, tree = jax.tree_util.tree_flatten(args)

    def leaf(x):
        aval = jax.typeof(x)
        return (aval.shape, str(aval.dtype), aval.weak_type,
                str(x.sharding) if isinstance(x, jax.Array) else "host")

    return str(tree), tuple(leaf(x) for x in leaves)


def _store():
    """JAX's persistent cache object for the default backend, or None."""
    from jax._src import compilation_cache

    backend = jax.devices()[0].client
    if not compilation_cache.is_cache_used(backend):
        return None
    return compilation_cache._get_cache(backend)


@contextlib.contextmanager
def _entries(store):
    """-> the list of JAX's keys whose entries ``store`` is known to hold
    after the compiles made inside: those it read, and those it wrote (JAX
    writes none for a compile under its thresholds of seconds and bytes)."""
    held = []
    get, put = store.get, store.put

    def watched_get(key):
        value = get(key)
        if value is not None:
            held.append(key)
        return value

    def watched_put(key, value):
        put(key, value)
        held.append(key)

    store.get, store.put = watched_get, watched_put
    try:
        yield held
    finally:
        del store.get, store.put


def _save(store, key, compiled, entry):
    """The store's entry for ``compiled``: serialize_executable's pickle with
    ``("entry", JAX's key, device ids)`` where the executable's bytes
    would be."""
    from jax._src.lib import xla_client as xc
    from jax.experimental import serialize_executable as se

    unloaded = compiled._executable._unloaded_executable
    devices = [d.id for d in unloaded.device_list]

    class Pickler(se._JaxPjrtPickler):
        def persistent_id(self, obj):
            if isinstance(obj, xc.LoadedExecutable):
                return ("entry", entry, devices)
            return super().persistent_id(obj)

    flat, in_tree = jax.tree_util.tree_flatten(compiled.args_info)
    with io.BytesIO() as f:
        Pickler(f).dump((unloaded, flat, in_tree, compiled.out_tree,
                         compiled._no_kwargs))
        store.put(key, f.getvalue())


def _load(store, key):
    """-> the ``jax.stages.Compiled`` of the store's entry ``key``, from
    JAX's own entry for its executable; None where the store has none.
    Raises whatever a damaged or orphaned entry raises."""
    from jax._src import compilation_cache
    from jax._src.lib import xla_client as xc
    from jax.experimental import serialize_executable as se

    blob = store.get(key)
    if blob is None:
        return None

    class Unpickler(se._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] != "entry":
                return super().persistent_load(pid)
            _, entry, devices = pid
            held = store.get(entry)
            if held is None:
                raise LookupError(f"JAX's entry {entry} is gone")
            executable, _ = compilation_cache.extract_executable_and_time(
                compilation_cache.decompress_executable(held))
            return self.backend.deserialize_executable(
                executable, executable_devices=xc.DeviceList(
                    tuple(self.devices_by_id[i] for i in devices)))

    unloaded, flat, in_tree, out_tree, no_kwargs = Unpickler(
        io.BytesIO(blob), jax.devices()[0].client).load()
    return jax.stages.Compiled(unloaded.load(), [], in_tree.unflatten(flat),
                               out_tree, no_kwargs=no_kwargs)


def _drop(store, key):
    """Take the store's entry ``key`` out of the way of its replacement:
    JAX's cache never overwrites, so an entry that does not load would fail
    every start from then on."""
    from jax._src import lru_cache

    for suffix in (lru_cache._CACHE_SUFFIX, lru_cache._ATIME_SUFFIX):
        (store.path / (key + suffix)).unlink(missing_ok=True)


class Program:
    """One of the loop's programs behind the store: called like the ``jit``
    object it wraps (``(params, cache, ...)``, the cache donated), and every
    other attribute (``lower``, ``trace``, ``__name__``) is that object's.

    The first call, and any call the executable in hand refuses for its
    arguments, finds the executable for the call's signature: one already
    met, else the store's, else a fresh compile that it stores.
    ``described``: everything that decides the program beside its arguments
    (``cfg``, ``geo``, ``mesh``, the engine's choices, statics)."""

    def __init__(self, jitted, mesh=None, **described):
        self._jit = jitted
        self._described = repr((sorted(described.items()), _mesh(mesh)))
        self._row = "jit_" + jitted.__name__
        self._by_signature = {}
        self._compiled = None

    def __getattr__(self, name):
        if name == "_jit":          # not built yet (a copy, an unpickle)
            raise AttributeError(name)
        return getattr(self._jit, name)

    def __call__(self, *args):
        if self._compiled is not None:
            try:
                return self._compiled(*args)
            except (TypeError, ValueError):
                # Raised before anything ran or was donated: other avals,
                # tree or shardings than this executable was compiled for.
                pass
        if any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(args)):
            return self._jit(*args)     # called under a transformation
        signature = _signature(args)
        compiled = self._by_signature.get(signature)
        if compiled is None:
            compiled = self._by_signature[signature] = self._find(
                signature, args)
        self._compiled = compiled
        return compiled(*args)

    def key(self, signature):
        digest = hashlib.sha256(repr(
            (self._described, signature, _environment())).encode())
        return f"hvd_{self._row}-{digest.hexdigest()}"

    def _find(self, signature, args):
        store = key = None
        try:
            store = _store()
            if store is not None:
                key = self.key(signature)
                t0 = time.perf_counter()
                compiled = _load(store, key)
                if compiled is not None:
                    _startup.account.program_loaded(
                        self._row, time.perf_counter() - t0)
                    return compiled
        except Exception:
            # The boundary that must keep running: an entry that cannot be
            # read is a miss, never a failed start.
            log.warning("%s: the kept program did not load; compiling",
                        self._row, exc_info=True)
            with contextlib.suppress(Exception):
                _drop(store, key)
        _startup.account.program_missed()
        if key is None:
            return self._jit
        with _entries(store) as held:
            compiled = self._jit.lower(*args).compile()
        held = [entry for entry in held if entry.startswith(self._row + "-")]
        try:
            if held:
                _save(store, key, compiled, held[-1])
        except Exception:
            log.warning("%s: the compiled program was not kept", self._row,
                        exc_info=True)
        return compiled
