"""The start-up account: where a process's start went, summed in memory and
handed over once, at exit.

A start is over before anybody can switch a tracer on, so this account is
never off by default and has to cost nothing: a dozen clock reads,
``jax.monitoring`` listeners that add floats, and no I/O until the process
exits (``HVD_STARTUP_LOG=0`` registers and writes nothing).

**Phases** are disjoint stretches of the start, each opened where its work
happens by ``with startup.phase(name)``, which is one
``spans.span("startup." + name)`` (so a phase lands in the profiler and the
Chrome sink whenever those are on) that also keeps its seconds and where it
began. In order:

``launch``
    ``tpurun`` from its own start to this rank's spawn. The launcher hands
    its start over in ``HVD_LAUNCH_T0`` beside ``HVD_RANK``
    (``runner/local.py::slot_env``).
``pre_import``
    the interpreter's start to the first line of ``horovod_tpu/__init__.py``
    (``import jax`` is here where the script imports it first).
``import``
    that line to the package's last (the core's build on a first import).
``init.core``, ``init.distributed``, ``init.devices``
    inside ``hvd.init()``: the core (negotiation, rendezvous), joining the
    job's ``jax.distributed`` service, and the ``jax.devices()`` in which
    every rank of a multi-process job waits for the others.
``serve.build``, ``warmup.<program>``
    ``ServeLoop.__init__`` and each program ``ServeLoop.warmup`` runs
    (``prefill``, ``decode``, ``bprefill``, ``chunk``, ``spec``).

**Sums** are JAX's own compile events, added up as they arrive: seconds of
``trace`` (jaxpr tracing), ``lower`` (jaxpr to MLIR: a Pallas kernel is
lowered to Mosaic here, compile cache or not), ``compile`` (the backend
compile request less what it loaded) and ``load`` (reading an executable
back from the persistent cache); one row for each program whose name this
package owns (``PROGRAMS``) and one, ``other``, for everything else (weight
initialisers, eager operations). ``counts`` holds the number of events of
each kind and the persistent cache's hits and misses.

``ServeLoop`` keeps its programs across starts where JAX's persistent cache
is on (:mod:`horovod_tpu.serving.programs`): a program found there is
neither traced nor lowered, and no event of JAX's says it was read, so the
store reports it itself. ``counts.program_hits`` are the programs it loaded,
and a hit's seconds (the store's entry and JAX's own executable read back
and loaded) are in that program's ``load`` like JAX's reads;
``counts.program_misses`` are the programs it had to lower and compile
(their seconds arrive as JAX's events do). An entry is good for one
program on one installation: it misses when the configuration, the
geometry, a static argument, the mesh, an argument's shape, dtype or
sharding, the backend, ``jax`` / ``jaxlib`` / libtpu, ``XLA_FLAGS`` /
``LIBTPU_INIT_ARGS`` or ANY ``.py`` under ``horovod_tpu/`` changes, and
when JAX's cache has evicted the executable it points at.

The account closes (``closed_s``) at the program's first real work: when
``jit_step`` has been compiled, or at the first ``ServeLoop.run``. Nothing
after that is a start's (a later ``phase`` is no span either); a process
that does neither keeps it open.

``hvd.startup_stats()`` returns it; the exit hook (and ``hvd.shutdown()``)
appends it as ONE JSON line to ``$HVD_STARTUP_LOG`` (default
``<tmp>/hvd_startup.jsonl``, started anew past 1 MB). This module imports
no JAX.
"""

import atexit
import contextlib
import json
import os
import sys
import time

from . import spans as _spans

LOG_ENV = "HVD_STARTUP_LOG"
LAUNCH_ENV = "HVD_LAUNCH_T0"
LOG_MAX_BYTES = 1_000_000

PHASES = ("launch", "pre_import", "import", "init.core", "init.distributed",
          "init.devices", "serve.build", "warmup.prefill", "warmup.decode",
          "warmup.bprefill", "warmup.chunk", "warmup.spec")
SUMS = ("trace", "lower", "compile", "load")
TRACE, LOWER, COMPILE, LOAD = range(4)
# The programs this package names, as their modules are called. The trace
# event carries the function's name (``decode``), the others ``jit(decode)``.
PROGRAMS = ("jit_step", "jit_prefill", "jit_bprefill", "jit_chunk",
            "jit_decode", "jit_spec")
_ROWS = {name: row for row in PROGRAMS
         for name in (row, row[4:], f"jit({row[4:]})")}
# The events as the installed JAX spells them (jax/_src/dispatch.py,
# compiler.py, compilation_cache.py).
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_DURATIONS = {
    _TRACE_EVENT: TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
    "/jax/compilation_cache/cache_retrieval_time_sec": LOAD,
}
_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}
# What ``serving/programs.py`` reports of its store.
_PROGRAM_COUNTS = ("program_hits", "program_misses")


def log_path():
    """Where a process appends its line: ``$HVD_STARTUP_LOG``, else a file
    under the system's temporary directory."""
    path = os.environ.get(LOG_ENV)
    if path:
        return path
    import tempfile

    return os.path.join(tempfile.gettempdir(), "hvd_startup.jsonl")


class Account:
    """One process's start: its phases, JAX's compile events summed by
    program, and the counts (the module's docstring says what each is)."""

    def __init__(self):
        self.on = os.environ.get(LOG_ENV) != "0"
        self.t_first = time.perf_counter()   # the package's first line
        self.phases = []                     # (name, perf_counter, seconds)
        self.sums = {"other": [0.0] * len(SUMS)}
        self.counts = dict.fromkeys(
            (*SUMS, *_COUNTS.values(), *_PROGRAM_COUNTS), 0)
        self.rank = None
        self.closed = None                   # perf_counter at the close
        self.listening = False
        self._load = 0.0        # cache reads whose compile event is to come
        self._tracing = 0       # traces open now (an inner one is its outer's)
        self._start = None
        self._written = False

    # -- the phases -------------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name):
        """One phase of the start (as a ``with`` or a decorator): a span
        whose seconds the tally keeps. Once the account is closed it is
        neither: a loop built later in a process's life is no start."""
        if self.closed is not None:
            yield
            return
        self.listen()
        with _spans.span("startup." + name, cat="startup"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.phases.append((name, t0, time.perf_counter() - t0))

    def imported(self, t_first):
        """The last line of ``horovod_tpu/__init__.py``: ``t_first`` is its
        first line's ``perf_counter``. The exit hook is registered here."""
        self.t_first = t_first
        self.phases.append(("import", t_first,
                            time.perf_counter() - t_first))
        self.listen()
        if self.on:
            atexit.register(self.write)

    def close(self):
        if self.closed is None:
            self.closed = time.perf_counter()

    # -- JAX's compile events ---------------------------------------------

    def listen(self):
        """Register the listeners, once, as soon as JAX is loaded."""
        if self.listening or not self.on or "jax" not in sys.modules:
            return
        import jax.monitoring as monitoring

        self.listening = True
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)
        monitoring.register_scalar_listener(self.on_scalar)

    def on_scalar(self, event, value, **_):
        """JAX announces a duration event's start as a scalar: a trace that
        starts inside another (a jitted ``jnp`` function in a program's
        body) is counted in the outer one's seconds, not again."""
        if event == _TRACE_EVENT and self.closed is None:
            self._tracing += 1

    def on_duration(self, event, seconds, fun_name=None, **_):
        kind = _DURATIONS.get(event)
        if kind is None or self.closed is not None:
            return
        if kind == TRACE:
            self._tracing = max(self._tracing - 1, 0)
            if self._tracing:
                return
        self.counts[SUMS[kind]] += 1
        if kind == LOAD:
            # It carries no name: the compile event of the same program
            # follows it, and includes it.
            self._load += seconds
            return
        key = _ROWS.get(fun_name, "other")
        row = self.sums.get(key)
        if row is None:
            row = self.sums[key] = [0.0] * len(SUMS)
        if kind == COMPILE:
            row[LOAD] += self._load
            seconds -= self._load
            self._load = 0.0
            if key == "jit_step":
                self.close()        # the step is compiled: the start is over
        row[kind] += seconds

    def on_event(self, event, **_):
        name = _COUNTS.get(event)
        if name is not None and self.closed is None:
            self.counts[name] += 1

    # -- the loop's store of programs (serving/programs.py) ---------------

    def program_loaded(self, row, seconds):
        """The store had ``row``'s program (``jit_decode``, ...): it was
        read back and loaded in ``seconds``, with no trace and no lowering."""
        if self.closed is None:
            self.counts["program_hits"] += 1
            self.counts["load"] += 1
            self.sums.setdefault(row, [0.0] * len(SUMS))[LOAD] += seconds

    def program_missed(self):
        """The store had no program that loads: JAX's events of the trace,
        the lowering and the compile follow."""
        if self.closed is None:
            self.counts["program_misses"] += 1

    # -- handing it over --------------------------------------------------

    def started(self):
        """-> (wall time, ``perf_counter`` reading) of the process's start:
        its fork, to the kernel's tick, from ``/proc/self/stat``; the
        package's first line where ``/proc`` does not say. Read once, when
        first asked for (a launcher asks as it spawns, a rank at its exit)."""
        if self._start is None:
            wall, perf = time.time(), time.perf_counter()
            try:
                boot = time.clock_gettime(time.CLOCK_BOOTTIME)
                with open("/proc/self/stat") as f:
                    ticks = int(f.read().rpartition(")")[2].split()[19])
                age = boot - ticks / os.sysconf("SC_CLK_TCK")
            except (AttributeError, OSError, ValueError, IndexError):
                age = perf - self.t_first
            self._start = (wall - age, perf - age)
        return self._start

    def stats(self):
        """The account so far: ``t_start`` (the process's start, wall time),
        ``pid``, ``rank``, ``age_s``, ``closed_s``, ``phases`` (``name``,
        ``at_s`` from the process's start, ``s``) in order, ``sums`` by row
        and ``counts``."""
        t_start, p_start = self.started()
        phases = [("pre_import", p_start, self.t_first - p_start),
                  *self.phases]
        launched = os.environ.get(LAUNCH_ENV)
        if launched is not None:
            try:
                s = t_start - float(launched)
                phases.insert(0, ("launch", p_start - s, s))
            except ValueError:
                pass
        return {
            "t_start": t_start, "pid": os.getpid(), "rank": self.rank,
            "age_s": round(time.perf_counter() - p_start, 6),
            "closed_s": (None if self.closed is None
                         else round(self.closed - p_start, 6)),
            "phases": [{"name": n, "at_s": round(t0 - p_start, 6),
                        "s": round(s, 6)}
                       for n, t0, s in phases],
            "sums": {key: {k: round(v, 6) for k, v in zip(SUMS, row)}
                     for key, row in self.sums.items()},
            "counts": dict(self.counts),
        }

    def write(self):
        """Append the account as one line to the log, once a process; the
        log is started anew past ``LOG_MAX_BYTES``. Never raises: the
        account must not fail the job it describes."""
        if self._written or not self.on:
            return
        self._written = True
        try:
            path = log_path()
            line = json.dumps(self.stats()) + "\n"
            try:
                anew = os.path.getsize(path) > LOG_MAX_BYTES
            except OSError:
                anew = False
            with open(path, "w" if anew else "a") as f:
                f.write(line)
        except (OSError, ValueError):
            pass


# Process-wide account + module-level conveniences.
account = Account()
phase = account.phase
imported = account.imported
close = account.close
stats = account.stats
write = account.write
