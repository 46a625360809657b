"""What every runner's worker process needs, once: the compile cache, the
device check, the compilation counter, the trace capture and the record it
hands back to ``run.py``. Imported by workers only; ``run.py`` never imports
JAX and so never imports this past its top.
"""

import gc
import glob
import json
import os
import time


def load_spec(argv):
    """The worker's one argument is the spec ``run.py`` wrote: the cell, its
    configuration and traffic files' contents, ``--seed/--seconds/--trace``
    and where to leave the record."""
    if len(argv) != 3 or argv[1] != "--spec":
        raise SystemExit("worker: expected --spec <file> (started by run.py)")
    with open(argv[2]) as f:
        return json.load(f)


def setup_jax():
    """Import JAX with the compile cache where ``run.py`` put it (the
    environment carries the directory) and every program cached, however
    quickly it compiled. Returns the module."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def require_device(spec):
    """The device as JAX reports it; ends the worker, with no record, unless
    it is a TPU with the chips the cell asks for. Nothing here falls back."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU; JAX found {d.platform!r} "
                         f"({d.device_kind})")
    if len(devs) != spec["cell"]["chips"]:
        raise SystemExit(f"benchmark: cell {spec['cell']['name']} asks for "
                         f"{spec['cell']['chips']} chips; JAX found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def seed_key(seed):
    """A PRNG key from ``--seed``, which may need more than 32 signed bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def memory_peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts backend compilations (not cache look-ups that hit) while
    ``active``; a window with one is not a measurement."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.count += 1


def quiesce():
    """Before a window: collect now, so the collector does not run in it."""
    gc.collect()
    gc.freeze()


class Tracer:
    """A ``jax.profiler`` trace of a short stretch, without the Python
    tracer (it slows the host it is meant to observe). ``stop`` reduces the
    ``.xplane.pb`` to the plain form beside the record and returns its
    path."""

    def __init__(self, spec):
        rank = spec.get("rank", 0)
        self.dir = os.path.join(spec["trace_dir"], f"rank{rank}")
        self.out = spec["trace_out"].format(rank=rank)
        self.t0 = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        from benchmark import trace_reduce

        host_s = time.perf_counter() - self.t0
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            raise SystemExit("benchmark: the profiler wrote no trace")
        trace_reduce.save(trace_reduce.from_xplane(files[-1]), self.out)
        return {"file": self.out, "host_window_s": host_s}


def annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def write_record(spec, record):
    tmp = spec["record_out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, spec["record_out"])
