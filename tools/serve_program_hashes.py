"""sha256 of the lowered text of every program a ``ServeLoop`` builds.

A refactor of ``serving/engine.py`` or ``serving/loop.py`` that is meant to
change no program's instructions runs this on both trees and compares the
two outputs: ``jit_fn.lower(...).as_text()`` of ``decode_fn``, ``chunk_fn`` (and
``chunk_end_fn`` where it is a program of its own, ``chunk_pair_fn`` where
the loop pairs two requests' chunks), ``spec_fn`` and, where built,
``prefill_fn`` and ``bprefill_fn``, with the
arguments ``ServeLoop.warmup`` hands them. Nothing is compiled or run.
Each program gets two hashes: ``text`` of the lowered text, ``cse`` of the
same module after MLIR's common-subexpression pass, which is what stays equal
when a change only stops making one value twice (``positions[:, None]``
built once where it was built for every kind of layer). In both, the payload
of a Pallas kernel (Mosaic bytecode, which carries the file paths and line
numbers of the Python frames that traced it: a line added to ``engine.py``,
or a checkout at another path, changes it) is replaced by the kernel's
module printed without locations.

    python tools/serve_program_hashes.py --tier cpu      # JAX_PLATFORMS=cpu
    python tools/serve_program_hashes.py --tier chip     # on the chip
    python tools/serve_program_hashes.py --tier described

``cpu``: the plain tier, at the tiny configurations the tier-1 tests serve:
every entry of ``tests/served.py``'s table, with the variants the entry lists
(``tiny-spec``, ``sarvam_mla-spec``; ``granite_h`` with no snapshot rows and
``granite_h-share`` with the prefix cache that holds state and its two copy
programs). ``chip``: the kernels' tier, a ``ServeLoop`` built as each serve
cell's runner builds it (``served.cell``: the cell's configuration file,
geometry, slots and chunk; parameters by shape only).
``described``: the same with no chip, lowered for a described ``v5e:2x2``
with the engine told it sees a TPU (a rehearsal of ``chip``; its text is not
the chip's). One JSON line: ``{"tier": .., "hashes": {"<model>.<program>":
{"text": sha256, "cse": sha256}}}``.
"""
import argparse
import base64
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _sha(text):
    """sha256 of a lowered text, every kernel's payload without locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def bare(match):
        ctx = mlir.JaxIrContext()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            kernel = ir.Module.parse(base64.b64decode(match.group(2)))
            asm = kernel.operation.get_asm(enable_debug_info=False)
        return match.group(1) + hashlib.sha256(asm.encode()).hexdigest()

    text = re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)', bare, text)
    return hashlib.sha256(text.encode()).hexdigest()


def _hashes(lowered):
    from jax._src.lib.mlir import passmanager

    text = _sha(lowered.as_text())
    module = lowered.compiler_ir("stablehlo")     # the pass rewrites it
    with module.context:
        passmanager.PassManager.parse(
            "builtin.module(func.func(cse))").run(module.operation)
    return {"text": text,
            "cse": _sha(module.operation.get_asm(enable_debug_info=False))}


def _programs(loop, like):
    """{program: its two hashes} of ``loop``; ``like(shape, dtype)`` makes an
    argument."""
    import jax
    import numpy as np

    B, mb, geo = loop.max_batch, loop.geo.table_width, loop.geo
    params, cache = jax.tree.map(lambda x: like(x.shape, x.dtype),
                                 (loop.params, loop.cache))

    def slots(b, *q):
        return [like(s, d) for s, d in (((b, *q), np.int32), ((b,), np.int32),
                                        ((b, mb), np.int32), ((b,), np.bool_))]

    calls = {
        "decode": (loop.decode_fn, slots(B)),
        "chunk": (loop.chunk_fn, slots(1, loop.prefill_chunk)),
        # A program of its own only where the model's fill leaves the stack
        # (and an attribute only since the loop has such models).
        "chunk_end": (getattr(loop, "chunk_end_fn", None),
                      slots(1, loop.prefill_chunk)),
        # The fill's last few tokens, one page wide (with a cut head only).
        "chunk_tail": (getattr(loop, "chunk_tail_fn", None),
                       slots(1, geo.page_size)),
        # Two requests' chunks that end no prompt in one call (a model with
        # routed experts; an attribute only since the loop pairs them).
        "chunk_pair": (getattr(loop, "chunk_pair_fn", None),
                       slots(2, loop.prefill_chunk)),
        "spec": (loop.spec_fn, slots(B, loop.spec_tokens + 1)),
        "prefill": (loop.prefill_fn, [like((geo.max_kv,), np.int32),
                                      like((), np.int32),
                                      like((mb,), np.int32)]),
        "bprefill": (loop.bprefill_fn, slots(B, geo.max_kv)),
    }
    found = {name: _hashes(fn.lower(params, cache, *args))
             for name, (fn, args) in calls.items() if fn is not None}
    # The two copies of a prefix cache that holds state (an attribute only
    # since the loop has one).
    for name in ("snapshot", "restore"):
        fn = getattr(loop, name + "_fn", None)
        if fn is not None:
            found["state_" + name] = _hashes(fn.lower(
                cache, like((), np.int32), like((), np.int32)))
    return found


def _tiny_loops(served):
    """(name, -> ServeLoop) of the tiny configurations the tests serve."""
    for name, entry in served.ENTRIES.items():
        for suffix, kw in entry.hashed:
            yield entry.short + suffix, lambda name=name, kw=kw: served.loop(
                name, abstract=True, **kw)


def _cell_loops(served):
    """(name, -> ServeLoop) as each serve cell's runner builds it, parameters by
    shape only."""
    import jax
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving.loop import ServeLoop

    for name in served.ENTRIES:
        cell = served.cell(name)
        params = jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), cell.cfg))
        yield name, lambda cell=cell, params=params: ServeLoop(
            params, cell.cfg, geo=cell.plain, max_batch=cell.max_batch,
            **cell.loop_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", choices=("cpu", "chip", "described"),
                    required=True)
    ap.add_argument("--only", default="", help="comma-separated names")
    args = ap.parse_args()
    import jax
    from horovod_tpu.serving import kv_cache
    from tests import served

    if args.tier == "cpu":
        loops = _tiny_loops(served)
    elif args.tier == "chip":
        if jax.default_backend() != "tpu":
            raise SystemExit("--tier chip needs the chip")
        loops = _cell_loops(served)
    else:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        jax.default_backend = lambda: "tpu"
        make = kv_cache.make_cache      # no 12 GB of zeros on the host
        kv_cache.make_cache = lambda *a, **kw: jax.eval_shape(
            lambda: make(*a, **kw))
        loops = _cell_loops(served)

    def like(shape, dtype):
        if args.tier == "described":
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=SingleDeviceSharding(device))
        return jax.ShapeDtypeStruct(shape, dtype)

    only = set(filter(None, args.only.split(",")))
    hashes = {}
    for name, build in loops:
        if only and name not in only:
            continue
        for program, pair in _programs(build(), like).items():
            hashes[f"{name}.{program}"] = pair
    print(json.dumps({"tier": args.tier, "hashes": hashes}, sort_keys=True))


if __name__ == "__main__":
    main()
