"""Sharded, async, crash-safe checkpointing — the state plane for an
elastic fleet (ROADMAP item 2; docs/checkpoint.md has the full spec).

Format (``hvd-sharded-v1``). Each rank writes only its own addressable
shards — no gather, no full-array host pull on any rank:

    <dir>/<step>.tmp/rank_<r>/shard_NNNN.npy   per-shard payloads
    <dir>/<step>.tmp/rank_<r>/shards.json      per-rank shard manifest
    <dir>/<step>/MANIFEST.json                 global manifest (committed)

Commit protocol: every member writes + fsyncs its shards and per-rank
manifest, then meets a named barrier (``ckpt.shards.<step>``); the set
root merges the rank manifests, validates that the shards tile every
tensor's global shape, fsyncs ``MANIFEST.json``, and atomically renames
``<step>.tmp → <step>`` — a crash at ANY point before the rename leaves
the previous checkpoint as latest (``latest_step`` never resolves a
``.tmp`` staging dir or a dir without a committed manifest). Both
barriers are core collectives, so with ``HVD_PEER_TIMEOUT_MS`` armed a
writer that dies mid-save surfaces to survivors as ``RankEvictedError``
through the PR 8 liveness/eviction path instead of wedging them.

Async: ``save(..., async_=True)`` device-to-host copies the pytree (the
only step-blocking part, measured as the ``ckpt.snapshot_stall`` span +
gauge) and hands serialization/IO/commit to a background writer thread
overlapped with compute. At most one save is in flight; a new ``save``
or ``wait()`` joins it first and re-raises its failure. Every member of
the process set must agree on ``async_`` — the commit barriers are
collectives.

Restore reshards: ``restore`` at world size M reads the global manifest
from a save at world size N, computes the index ranges each target leaf
needs, and fetches/assembles only the overlapping shard fragments —
what turns elastic spare promotion into fetch-only-your-shard. Legacy
orbax checkpoints (``_METADATA`` marker) still restore through orbax;
new saves never touch orbax. Counters: ``hvd.checkpoint_stats()``.
"""
import io
import json
import os
import shutil
import signal
import threading
import time
import zlib

import numpy as np

from .basics import basics as _basics
from .exceptions import CheckpointError
from .observability import metrics as _metrics
from .observability import spans as _spans
from .ops import collective_ops as _core

FORMAT = "hvd-sharded-v1"
MANIFEST = "MANIFEST.json"
_RANK_MANIFEST = "shards.json"


def _ckptr():
    """Orbax Checkpointer confined to this process — kept ONLY for the
    legacy read path (checkpoints written before the sharded format)."""
    import jax
    import orbax.checkpoint as ocp

    me = jax.process_index() if jax.distributed.is_initialized() else 0
    return ocp.Checkpointer(
        ocp.StandardCheckpointHandler(),
        multiprocessing_options=ocp.options.MultiprocessingOptions(
            primary_host=me, active_processes={me}))


def _resolve_set(process_set):
    """(set_id, root, member_ranks): the writer/commit root is the set's
    LOWEST member — hardcoding global rank 0 would silently commit
    nothing for a set excluding it. Non-global sets must be passed as
    ProcessSet objects (a bare id carries no membership)."""
    if hasattr(process_set, "process_set_id"):
        ranks = sorted(int(r) for r in process_set.ranks)
        return (int(process_set.process_set_id),
                (ranks[0] if ranks else 0), ranks)
    ps = int(process_set)
    if ps != 0:
        raise ValueError(
            "pass a ProcessSet object for non-global process sets: the "
            "checkpoint writer/root is the set's lowest member, which a "
            "bare id cannot name")
    return 0, 0, list(range(_basics.size()))


# ---------------------------------------------------------------------------
# Stats (hvd.checkpoint_stats()) — plain counters, always on; the CKPT_*
# metric families mirror them only under HVD_METRICS.

_stats_lock = threading.Lock()
_stats = {
    "saves": 0,              # save() calls entered
    "commits": 0,            # checkpoints durably committed (renamed)
    "aborted_commits": 0,    # saves that died before the rename
    "bytes": 0,              # shard bytes this rank wrote
    "snapshot_stall_ms": 0.0,  # last device->host snapshot stall
    "write_ms": 0.0,         # last write+commit time (off-path if async)
    "restores": 0,           # restore() calls that returned a tree
    "bytes_read": 0,         # shard-file bytes this rank fetched
    "fragments_fetched": 0,  # shard files read during reshard assembly
    "last_committed_step": -1,
}


def checkpoint_stats():
    """Snapshot of this process's checkpoint counters (see module doc)."""
    with _stats_lock:
        return dict(_stats)


def _bump(**kv):
    with _stats_lock:
        for k, v in kv.items():
            if k in ("snapshot_stall_ms", "write_ms", "last_committed_step"):
                _stats[k] = v
            else:
                _stats[k] += v


# ---------------------------------------------------------------------------
# latest_step

def _is_committed(path):
    """A step directory counts only when its commit marker is present:
    the sharded format's MANIFEST.json, or the legacy orbax _METADATA
    (possibly nested under <step>/default/ by an older revision)."""
    return (os.path.exists(os.path.join(path, MANIFEST))
            or os.path.exists(os.path.join(path, "_METADATA"))
            or os.path.exists(os.path.join(path, "default", "_METADATA")))


def latest_step(directory):
    """Newest COMMITTED checkpoint step in `directory`, or None.

    ``<step>.tmp`` staging dirs and integer-named dirs lacking a commit
    marker (a crashed writer's leftovers) are never resolved as latest —
    the crash-safety half of the commit protocol's contract.
    """
    d = str(directory)
    if not os.path.isdir(d):
        return None
    steps = [int(n) for n in os.listdir(d)
             if n.isdigit() and os.path.isdir(os.path.join(d, n))
             and _is_committed(os.path.join(d, n))]
    return max(steps) if steps else None


# ---------------------------------------------------------------------------
# Save: snapshot (step-blocking) + write/commit (inline or background)

class _InFlight:
    __slots__ = ("thread", "step", "error")

    def __init__(self, thread, step):
        self.thread = thread
        self.step = step
        self.error = None


_inflight = None


def wait():
    """Block until the in-flight async save (if any) commits; re-raises
    the writer thread's failure here, on the caller's thread."""
    global _inflight
    inf = _inflight
    if inf is None:
        return
    inf.thread.join()
    _inflight = None
    if inf.error is not None:
        raise inf.error


def _resolve_dir(directory):
    d = directory if directory is not None else os.environ.get("HVD_CKPT_DIR")
    if not d:
        raise ValueError(
            "no checkpoint directory: pass one or set HVD_CKPT_DIR")
    return str(d)


def _norm_index(index, shape):
    """Shard index -> [[start, stop], ...] with concrete bounds (a shard
    index from jax may carry None bounds on replicated dims)."""
    out = []
    for sl, dim in zip(index, shape):
        start, stop, step = sl.indices(int(dim))
        if step != 1:
            raise CheckpointError(f"non-unit shard stride {sl} unsupported")
        out.append([int(start), int(stop)])
    return out


def _is_jax_array(x):
    try:
        import jax

        return isinstance(x, jax.Array)
    except Exception:
        return False


def _flatten_named(tree):
    """[(name, leaf)] with stable pytree-path names, plus the treedef."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf in flat], treedef


def _snapshot(tree, root):
    """Device->host copy of this rank's contribution — the ONLY part of a
    save that blocks the step. jax.Array leaves contribute their
    addressable replica-0 shards (exactly one rank holds each); other
    leaves (plain numpy, scalars) are written whole by the set root,
    preserving the restore-returns-the-root's-values contract for
    unsharded state."""
    t0 = time.perf_counter()
    named, _ = _flatten_named(tree)
    me = _basics.rank()
    tensors, shards = {}, []
    for name, leaf in named:
        if _is_jax_array(leaf):
            gshape = tuple(int(s) for s in leaf.shape)
            dtype = np.dtype(leaf.dtype)
            for sh in leaf.addressable_shards:
                if sh.replica_id != 0:
                    continue
                shards.append((name, _norm_index(sh.index, gshape),
                               np.asarray(sh.data)))
        else:
            arr = np.asarray(leaf)
            gshape, dtype = arr.shape, arr.dtype
            if me == root:
                shards.append(
                    (name, [[0, int(d)] for d in gshape], arr))
        tensors[name] = {"global_shape": [int(d) for d in gshape],
                         "dtype": np.dtype(dtype).name}
    stall_ms = (time.perf_counter() - t0) * 1e3
    _bump(snapshot_stall_ms=stall_ms)
    if _metrics.enabled():
        _metrics.CKPT_SNAPSHOT_STALL_SECONDS.set(stall_ms / 1e3)
        _spans.event("ckpt.snapshot_stall",
                     time.time_ns() // 1000 - int(stall_ms * 1e3),
                     int(stall_ms * 1e3), cat="ckpt")
    return tensors, shards


def _fsync_dir(path):
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_shards(rankdir, tensors, shards, step, rank):
    """Write this rank's shard payloads + per-rank manifest, all fsynced
    before returning — the barrier that follows asserts durability."""
    if os.path.isdir(rankdir):
        shutil.rmtree(rankdir)  # stale leftovers from an aborted attempt
    os.makedirs(rankdir)
    entries, nbytes = [], 0
    for i, (name, index, arr) in enumerate(shards):
        fname = f"shard_{i:04d}.npy"
        buf = io.BytesIO()
        np.save(buf, np.ascontiguousarray(arr))
        data = buf.getvalue()
        with open(os.path.join(rankdir, fname), "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        entries.append({"name": name, "index": index, "file": fname,
                        "crc32": zlib.crc32(data) & 0xFFFFFFFF,
                        "nbytes": len(data)})
        nbytes += len(data)
    rm = {"format": FORMAT, "step": int(step), "rank": int(rank),
          "tensors": tensors, "shards": entries}
    with open(os.path.join(rankdir, _RANK_MANIFEST), "w") as f:
        json.dump(rm, f)
        f.flush()
        os.fsync(f.fileno())
    _fsync_dir(rankdir)
    _bump(bytes=nbytes)
    if _metrics.enabled():
        _metrics.CKPT_BYTES_WRITTEN.inc(nbytes)
    return nbytes


def _box_volume(index):
    v = 1
    for s, e in index:
        v *= max(0, e - s)
    return v


def _merge_and_commit(directory, staging, final, step, members):
    """Root half of the commit: merge rank manifests, validate coverage,
    fsync MANIFEST.json, atomically rename the staging dir."""
    tensors, merged = None, []
    for r in members:
        rman = os.path.join(staging, f"rank_{r}", _RANK_MANIFEST)
        try:
            with open(rman) as f:
                rm = json.load(f)
        except (OSError, ValueError) as e:
            raise CheckpointError(
                f"step {step}: rank {r} manifest {rman} unreadable: {e}")
        if tensors is None:
            tensors = rm["tensors"]
        for sh in rm["shards"]:
            merged.append(dict(sh, rank=int(r)))
    # Drop rank dirs that are not part of this commit (a crashed attempt
    # at a different world size leaves them behind in the staging dir).
    keep = {f"rank_{r}" for r in members}
    for n in os.listdir(staging):
        if n.startswith("rank_") and n not in keep:
            shutil.rmtree(os.path.join(staging, n), ignore_errors=True)
    # Coverage: the deduped shard boxes of every tensor must tile its
    # global shape exactly — else the checkpoint could restore silently
    # wrong, which is the one thing this module must never do.
    by_name = {}
    for sh in merged:
        by_name.setdefault(sh["name"], set()).add(
            tuple((s, e) for s, e in sh["index"]))
    for name, meta in tensors.items():
        vol = int(np.prod([int(d) for d in meta["global_shape"]] or [1]))
        got = sum(_box_volume(b) for b in by_name.get(name, ()))
        if got != vol:
            raise CheckpointError(
                f"step {step}: tensor {name} shards cover {got} of {vol} "
                f"elements — refusing to commit a torn checkpoint")
    manifest = {"format": FORMAT, "step": int(step),
                "world_size": len(members), "tensors": tensors,
                "shards": merged}
    mpath = os.path.join(staging, MANIFEST)
    with open(mpath, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    _fsync_dir(staging)
    if os.path.isdir(final):  # re-save of an existing step
        old = final + ".old"
        shutil.rmtree(old, ignore_errors=True)
        os.rename(final, old)
        os.rename(staging, final)
        shutil.rmtree(old, ignore_errors=True)
    else:
        os.rename(staging, final)
    _fsync_dir(directory)


def _write_and_commit(directory, step, tensors, shards, ps, root, members):
    """Serialization + IO + the two-barrier commit — everything a save
    does OFF the step path when async. Runs on the caller's thread for
    sync saves and on the background writer thread for async ones."""
    me = _basics.rank()
    t0 = time.perf_counter()
    try:
        with _spans.span("ckpt.write", cat="ckpt", step=int(step)):
            staging = os.path.join(directory, f"{int(step)}.tmp")
            final = os.path.join(directory, str(int(step)))
            rankdir = os.path.join(staging, f"rank_{me}")
            os.makedirs(staging, exist_ok=True)
            _write_shards(rankdir, tensors, shards, step, me)
            if (os.environ.get("HVD_CKPT_TEST_CRASH") == str(int(step))
                    and me == root):
                # Chaos hook (tests/test_chaos.py): the writer dies with
                # durable shards but NO commit — survivors must evict it
                # via the liveness path and restore the previous step.
                os.kill(os.getpid(), signal.SIGKILL)
            _core.barrier(process_set=ps, name=f"ckpt.shards.{int(step)}")
            with _spans.span("ckpt.commit", cat="ckpt", step=int(step)):
                if me == root:
                    _merge_and_commit(directory, staging, final, step,
                                      members)
                _core.barrier(process_set=ps,
                              name=f"ckpt.commit.{int(step)}")
    except BaseException:
        _bump(aborted_commits=1)
        if _metrics.enabled():
            _metrics.CKPT_ABORTED_COMMITS.inc()
        raise
    write_ms = (time.perf_counter() - t0) * 1e3
    _bump(commits=1, write_ms=write_ms, last_committed_step=int(step))
    if _metrics.enabled():
        _metrics.CKPT_COMMITS.inc()
        _metrics.CKPT_WRITE_SECONDS.set(write_ms / 1e3)
        _metrics.CKPT_LAST_COMMITTED_STEP.set(int(step))
    if me == root:
        _report_commit(int(step))


def _report_commit(step):
    """Tell the elastic driver the last durably committed step (it rides
    elastic_stats and each epoch's assignments, so a promoted spare can
    resolve its restore step without a collective). Best-effort."""
    try:
        from .runner.elastic import worker as _ew

        if _ew.is_elastic():
            _ew.report_ckpt_commit(step)
    except Exception:
        pass


def save(directory, step, tree, process_set=0, async_=None):
    """Write `tree` (a pytree of arrays) as checkpoint `step`.

    Every member of the process set writes its own addressable shards;
    the set root commits (global manifest + atomic rename) only after a
    named barrier confirms every rank's shards are durable. Sync saves
    return after the commit barrier; ``async_=True`` returns right after
    the device->host snapshot and commits on a background writer thread
    (:func:`wait` joins it; a prior async failure re-raises on the next
    ``save``/``wait``). ``async_=None`` reads ``HVD_CKPT_ASYNC``; the
    flag must agree across the set — the commit barriers are
    collectives. ``directory=None`` falls back to ``HVD_CKPT_DIR``.
    """
    global _inflight
    if async_ is None:
        async_ = os.environ.get("HVD_CKPT_ASYNC", "0") == "1"
    directory = _resolve_dir(directory)
    wait()  # at-most-one-in-flight; surfaces the previous save's failure
    _bump(saves=1)
    if _metrics.enabled():
        _metrics.CKPT_SAVES.inc()
    ps, root, members = _resolve_set(process_set)
    with _spans.span("ckpt.save", cat="ckpt", step=int(step),
                     mode="async" if async_ else "sync"):
        tensors, shards = _snapshot(tree, root)
        if not async_:
            _write_and_commit(directory, step, tensors, shards, ps, root,
                              members)
            return
        inf = _InFlight(None, int(step))

        def _run():
            try:
                _write_and_commit(directory, step, tensors, shards, ps,
                                  root, members)
            except BaseException as e:  # surfaced on the next save/wait
                inf.error = e

        inf.thread = threading.Thread(
            target=_run, name=f"ckpt-writer-{int(step)}", daemon=True)
        _inflight = inf
        inf.thread.start()


# ---------------------------------------------------------------------------
# Restore (with reshard)

def _load_manifest(path):
    mpath = os.path.join(path, MANIFEST)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except OSError as e:
        raise CheckpointError(f"{mpath}: unreadable: {e}")
    except ValueError as e:
        raise CheckpointError(
            f"{mpath}: torn manifest (not parseable as JSON: {e}) — the "
            f"checkpoint did not commit intact")
    if manifest.get("format") != FORMAT:
        raise CheckpointError(
            f"{mpath}: unknown format {manifest.get('format')!r} "
            f"(expected {FORMAT})")
    return manifest


class _ShardReader:
    """Reads + verifies shard files on demand, caching per restore call
    (several addressable devices of one target leaf may need fragments
    from the same shard file)."""

    def __init__(self, path, manifest):
        self.path = path
        self.by_name = {}
        for sh in manifest["shards"]:
            self.by_name.setdefault(sh["name"], []).append(sh)
        self._cache = {}

    def load(self, sh):
        key = (sh["rank"], sh["file"])
        if key in self._cache:
            return self._cache[key]
        fpath = os.path.join(self.path, f"rank_{sh['rank']}", sh["file"])
        try:
            with open(fpath, "rb") as f:
                data = f.read()
        except OSError as e:
            raise CheckpointError(
                f"missing shard rank_{sh['rank']}/{sh['file']} for tensor "
                f"{sh['name']}: {e}")
        if (zlib.crc32(data) & 0xFFFFFFFF) != sh["crc32"]:
            raise CheckpointError(
                f"checksum mismatch in shard rank_{sh['rank']}/"
                f"{sh['file']} for tensor {sh['name']}")
        arr = np.load(io.BytesIO(data), allow_pickle=False)
        self._cache[key] = arr
        _bump(bytes_read=len(data), fragments_fetched=1)
        if _metrics.enabled():
            _metrics.CKPT_BYTES_READ.inc(len(data))
            _metrics.CKPT_FRAGMENTS.inc()
        return arr

    def read_region(self, name, bounds, dtype):
        """Assemble the [start, stop) region `bounds` of tensor `name`
        from only the shard fragments that overlap it."""
        out = np.empty([e - s for s, e in bounds], dtype)
        want = _box_volume(bounds)
        covered = 0
        for sh in self.by_name.get(name, ()):
            inter = []
            for (ws, we), (ss, se) in zip(bounds, sh["index"]):
                s, e = max(ws, ss), min(we, se)
                if s >= e:
                    inter = None
                    break
                inter.append((s, e))
            if inter is None and bounds:
                continue
            arr = self.load(sh)
            if bounds:
                dst = tuple(slice(s - ws, e - ws)
                            for (s, e), (ws, we) in zip(inter, bounds))
                src = tuple(slice(s - ss, e - ss)
                            for (s, e), (ss, se) in zip(inter, sh["index"]))
                out[dst] = arr[src]
                covered += _box_volume(inter)
            else:  # scalar
                out[()] = arr[()]
                covered += 1
        if covered != want:
            raise CheckpointError(
                f"tensor {name}: region {bounds} only {covered}/{want} "
                f"elements covered by shards — refusing a partial restore")
        return out


def _restore_sharded(path, tree_like):
    """Reshard-on-read: every target leaf fetches only the index ranges
    it needs. A jax.Array leaf keeps its sharding — each addressable
    device pulls exactly its own region; other leaves assemble the full
    tensor on host."""
    manifest = _load_manifest(path)
    reader = _ShardReader(path, manifest)
    tensors = manifest["tensors"]
    named, treedef = _flatten_named(tree_like)
    out = []
    for name, leaf in named:
        if name not in tensors:
            raise CheckpointError(
                f"{os.path.join(path, MANIFEST)}: no tensor {name} in the "
                f"checkpoint (saved tree differs from tree_like)")
        meta = tensors[name]
        gshape = tuple(int(d) for d in meta["global_shape"])
        dtype = np.dtype(meta["dtype"])
        if _is_jax_array(leaf):
            import jax

            if tuple(int(s) for s in leaf.shape) != gshape:
                raise CheckpointError(
                    f"tensor {name}: tree_like shape "
                    f"{tuple(leaf.shape)} != saved shape {gshape}")

            def _cb(idx, _n=name, _g=gshape, _d=dtype):
                return reader.read_region(_n, _norm_index(idx, _g), _d)

            out.append(jax.make_array_from_callback(
                gshape, leaf.sharding, _cb))
        else:
            out.append(reader.read_region(
                name, [[0, d] for d in gshape], dtype))
    import jax

    return jax.tree_util.tree_unflatten(treedef, out)


def _restore_orbax(path, tree_like):
    """Legacy read path: checkpoints written by the pre-sharded revisions
    of this module (orbax StandardSave; an even older revision nested the
    payload under <step>/default/)."""
    import jax
    import orbax.checkpoint as ocp

    legacy = os.path.join(path, "default")
    if os.path.isdir(legacy) and not os.path.exists(
            os.path.join(path, "_METADATA")):
        path = legacy
    with _ckptr() as ck:
        return ck.restore(
            path, args=ocp.args.StandardRestore(
                jax.tree.map(np.asarray, tree_like)))


def restore(directory, tree_like, step=None, process_set=0,
            coordinate=True):
    """Restore a checkpoint into the structure (and shardings) of
    `tree_like`; returns (tree, step) or (None, None) when no committed
    checkpoint exists.

    With ``coordinate=True`` the set's root resolves which step to load
    (`step` or the latest) and broadcasts its choice so every member
    reads the SAME checkpoint even if a newer one lands mid-call.

    ``coordinate=False`` skips the broadcast and resolves locally —
    REQUIRED when ranks may reach this call with different collective
    histories (e.g. startup code before ``hvd.elastic.run``, where a
    mid-run joiner executes it while veterans sit in ``state.sync()``):
    a collective here would deadlock the job. The commit protocol writes
    atomically, so a locally visible committed step is complete; on a
    shared filesystem all ranks resolve the same latest step unless a
    save is racing — exactly the window ``coordinate=True`` exists for.
    """
    directory = _resolve_dir(directory)
    ps, root, _ = _resolve_set(process_set)
    if not coordinate:
        chosen = step if step is not None else latest_step(directory)
    else:
        if _basics.rank() == root:
            chosen = step if step is not None else latest_step(directory)
        else:
            chosen = None
        chosen = _core.broadcast_object(chosen, root_rank=root,
                                        name="ckpt.step", process_set=ps)
    if chosen is None:
        return None, None
    path = os.path.join(directory, str(int(chosen)))
    with _spans.span("ckpt.restore", cat="ckpt", step=int(chosen)):
        if os.path.exists(os.path.join(path, MANIFEST)):
            out = _restore_sharded(path, tree_like)
        elif _is_committed(path):
            out = _restore_orbax(path, tree_like)
        else:
            raise CheckpointError(
                f"{path}: no committed checkpoint ({MANIFEST} and the "
                f"legacy _METADATA marker are both absent)")
    _bump(restores=1)
    if _metrics.enabled():
        _metrics.CKPT_RESTORES.inc()
    return out, int(chosen)
