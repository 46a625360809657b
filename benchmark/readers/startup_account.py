"""One part (``params.part``) of the program's own account of this run's
start: the line every process of the program appends to its start-up log at
exit (``horovod_tpu/observability/startup.py``). A program without that
module, or a log without a line of this run: no metric.

The lines of this run are those of processes that started at or after the
command (``spec.t_command``; ``run.py``'s own process started before it).
Where they carry ranks (a ``tpurun`` job) rank 0's line gives the phases and
``tpurun``'s own line is left out; else the one worker's line does.

Parts, in seconds unless said otherwise:

``launch``, ``pre_import``, ``import``
    the phases of those names.
``init``
    ``init.core`` + ``init.distributed`` + ``init.devices``, slowest rank.
``warmup``
    ``serve.build`` + every ``warmup.*``.
``trace_lower``, ``compile``, ``cache_load``
    JAX's compile events summed over the whole start, every program and
    everything else, on the rank that spent most.
``program_lower``
    trace + lower of the programs the package names alone, likewise: what a
    start pays for them although every executable is in the compile cache.
``cache_misses``
    the persistent cache's misses, the most of any rank (a count).
``runner_gap``
    what is left of the process from its start to the window's
    (``t_command + setup_seconds``) once the phases inside it and the
    compile events of ``jit_step``, which no phase covers, are taken off:
    the runner's own work (the backend where the runner brings it up,
    weights from the seed, the offer, warm-up segments).
``unaccounted_share``
    percent of ``setup_seconds`` that ``launch``, those phases, those
    events and the gap leave over: the command's own time before the
    program's first process, and whatever the two clocks disagree by.
"""

import json

INIT = ("init.core", "init.distributed", "init.devices")
SUMS = {"trace_lower": ("trace", "lower"), "compile": ("compile",),
        "cache_load": ("load",)}


def lines_since(path, t_command):
    """The accounts in the log at ``path`` of processes that started at or
    after ``t_command``, by start."""
    found = []
    try:
        with open(path) as f:
            for text in f:
                try:
                    line = json.loads(text)
                except ValueError:
                    continue            # a torn line is no account
                if line.get("t_start", 0) >= t_command:
                    found.append(line)
    except OSError:
        return []
    return sorted(found, key=lambda line: line["t_start"])


def _seconds(line, *names, prefix=None):
    return sum(p["s"] for p in line["phases"]
               if p["name"] in names
               or (prefix is not None and p["name"].startswith(prefix)))


def _events(line, kinds, rows=None):
    """Seconds of the events of ``kinds`` in the line's rows: every row, the
    programs' rows alone (``"owned"``), or the one named."""
    return sum(row[k] for name, row in line["sums"].items()
               if rows is None or name == rows
               or (rows == "owned" and name != "other") for k in kinds)


def account(lines, t_command, setup_seconds):
    """-> {part: value} from this run's lines; parts with nothing to read
    are left out."""
    ranked = [line for line in lines if line.get("rank") is not None]
    ranks = ranked or lines[:1]
    if not ranks:
        return {}
    main = next((r for r in ranks if r["rank"] == 0), ranks[0])
    names = {p["name"] for r in ranks for p in r["phases"]}
    parts = {"pre_import": _seconds(main, "pre_import"),
             "import": _seconds(main, "import"),
             "cache_misses": max(r["counts"]["cache_misses"] for r in ranks),
             "program_lower": max(_events(r, ("trace", "lower"), "owned")
                                  for r in ranks)}
    for part, kinds in SUMS.items():
        parts[part] = max(_events(r, kinds) for r in ranks)
    if "launch" in names:
        parts["launch"] = _seconds(main, "launch")
    if names & set(INIT):
        parts["init"] = max(_seconds(r, *INIT) for r in ranks)
    if any(n == "serve.build" or n.startswith("warmup.") for n in names):
        parts["warmup"] = _seconds(main, "serve.build", prefix="warmup.")
    launch = parts.get("launch", 0.0)
    inside = sum(p["s"] for p in main["phases"] if p["name"] != "launch")
    step = _events(main, ("trace", "lower", "compile", "load"), "jit_step")
    gap = t_command + setup_seconds - main["t_start"] - inside - step
    parts["runner_gap"] = gap
    parts["unaccounted_share"] = 100.0 * (
        setup_seconds - launch - inside - step - gap) / setup_seconds
    return parts


def read(ctx, params):
    if "startup_account" not in ctx:
        ctx["startup_account"] = {}
        try:
            from horovod_tpu.observability import startup
        except ImportError:
            return None                 # a program that keeps no account
        t_command = ctx["spec"]["t_command"]
        setup = ctx["fields"].get("setup_seconds")
        if setup:
            try:
                ctx["startup_account"] = account(
                    lines_since(startup.log_path(), t_command), t_command,
                    setup)
            except (KeyError, TypeError):
                pass                    # lines of another format: no metric
    return ctx["startup_account"].get(params["part"])
