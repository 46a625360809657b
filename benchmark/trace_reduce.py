"""From a profiler trace to numbers. The benchmark's own arithmetic: every
PR's traced run is reduced by this file and no other.

A trace here is plain data (``load``/``save``; JSON, gzip): planes, their
lines, and events as (name, start_ns, dur_ns). :func:`from_xplane` makes it
from the ``.xplane.pb`` that ``jax.profiler`` writes, and is the one function
that imports JAX (the worker calls it; ``run.py`` reads the plain form).
``recorded_trace.json.gz`` (two steps on one chip) and
``recorded_trace_dp4.json.gz`` (one step of one rank of four, operands cut
from the names) beside this file are cuts of real v5e traces of this
benchmark, which ``benchmark/tests`` reduce to known numbers.

On a TPU each chip is one plane ``/device:TPU:<n>``. Its line ``XLA Ops``
holds one event per executed HLO operation, named by the instruction's whole
text (``%fusion.12 = (f32[50257,1024]{..}, ..) fusion(..operands..)``);
``Async XLA Ops`` holds the asynchronous ones from their start to their done
(copies, and collectives where the compiler made them asynchronous); ``XLA
Modules`` holds one event per executed program
(``jit_<name>(<fingerprint>)``). A pattern is matched against the
instruction's NAME (``fusion.12``), never its operands, unless the caller
asks for the text. A name need not say what the operation is (the all-reduce
of a ``psum`` is called ``%psum.7``), so collectives are matched in the text by
their opcode, which stands between a space and ``(``: :data:`COLLECTIVES`. Host threads are lines of
the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans and the
runtime's own (``PjitFunction(..)``, transfers) land there, on the same clock.

Definitions used by every reader:

- busy: the union of the ``XLA Ops`` intervals of a chip (nested or
  overlapping events count once);
- window: from the first operation's start to the last one's end, over all
  chips of the trace;
- idle share: 1 - busy / window, averaged over the chips;
- a gap: a stretch of the window with no operation on a chip; it is named by
  the innermost host span that covers its middle.
"""

import gzip
import json
import re

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = r"^/host:CPU$"
MIN_GAP_NS = 10_000          # shorter gaps are summed as "between ops"
COLLECTIVES = (r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
               r"collective-permute)(-start|-done)?\(")


# ---- plain form -----------------------------------------------------------

def from_xplane(path):
    """Read ``.xplane.pb`` into the plain form: the device planes and the
    host plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    names, index, planes = [], {}, []
    for plane in data.planes:
        if not (re.match(DEVICE_PLANE, plane.name)
                or re.match(HOST_PLANE, plane.name)):
            continue
        lines = []
        for line in plane.lines:
            n, s, d = [], [], []
            for ev in line.events:
                name = ev.name
                if name not in index:
                    index[name] = len(names)
                    names.append(name)
                n.append(index[name])
                s.append(int(ev.start_ns))
                d.append(int(ev.duration_ns))
            if n:
                lines.append({"name": line.name, "n": n, "s": s, "d": d})
        planes.append({"name": plane.name, "lines": lines})
    return {"names": names, "planes": planes}


def save(trace, path):
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def merge(traces):
    """Several processes' traces (one rank per chip) as one."""
    names, index, planes = [], {}, []
    for t in traces:
        remap = []
        for name in t["names"]:
            if name not in index:
                index[name] = len(names)
                names.append(name)
            remap.append(index[name])
        for p in t["planes"]:
            planes.append({"name": p["name"], "lines": [
                {"name": ln["name"], "n": [remap[i] for i in ln["n"]],
                 "s": ln["s"], "d": ln["d"]} for ln in p["lines"]]})
    return {"names": names, "planes": planes}


# ---- intervals ------------------------------------------------------------

def union(intervals):
    """Sorted, disjoint cover of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the disjoint sorted cover ``a`` not inside cover ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def intersect(a, b):
    return subtract(a, subtract(a, b))


# ---- what a trace holds ---------------------------------------------------

def device_planes(trace):
    return [p for p in trace["planes"] if re.match(DEVICE_PLANE, p["name"])]


def line_events(plane, line_name):
    """-> list of (name_index, start_ns, end_ns) of the named line."""
    out = []
    for ln in plane["lines"]:
        if ln["name"] == line_name:
            out.extend((n, s, s + d)
                       for n, s, d in zip(ln["n"], ln["s"], ln["d"]))
    return out


def instr(name):
    """``%fusion.12 = ... fusion(...)`` -> ``fusion.12``; other names whole."""
    return name.split(" = ", 1)[0].lstrip("%")


def kind(name):
    """What :func:`top_ops` groups by: the instruction's name without its
    number, and its result type without layouts (``fusion (f32[4096,1024],
    f32[4096,1024], f32[4096,1024])``), so the same operation of every layer
    is one row."""
    if " = " not in name:
        return name[:96]
    head, rest = name.split(" = ", 1)
    rest = re.sub(r"\{[^}]*\}", "", rest)
    m = re.match(r"(\([^()]*\)|\S+) [\w\-]+\(", rest)
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    return (base + " " + (m.group(1) if m else rest))[:96].rstrip()


def _matching(trace, pattern, text=False):
    rx = re.compile(pattern)
    return {i for i, name in enumerate(trace["names"])
            if rx.search(name if text else instr(name))}


def busy_and_window(trace):
    """-> (busy seconds averaged over the chips, window seconds, chips).
    ``(0.0, 0.0, 0)`` when no operation ran on a device."""
    covers = [union((s, e) for _, s, e in line_events(p, OPS_LINE))
              for p in device_planes(trace)]
    covers = [c for c in covers if c]
    if not covers:
        return 0.0, 0.0, 0
    start = min(c[0][0] for c in covers)
    end = max(c[-1][1] for c in covers)
    busy = sum(total(c) for c in covers) / len(covers)
    return busy * 1e-9, (end - start) * 1e-9, len(covers)


def op_seconds(trace, pattern=None, lines=(OPS_LINE,), text=False):
    """Seconds per chip (mean over the chips) in events of ``lines`` whose
    name matches ``pattern`` (all if None); overlaps count once."""
    planes = device_planes(trace)
    if not planes:
        return None
    keep = None if pattern is None else _matching(trace, pattern, text)
    t = 0
    for p in planes:
        t += total(union((s, e) for ln in lines
                         for n, s, e in line_events(p, ln)
                         if keep is None or n in keep))
    return t / len(planes) * 1e-9


def exposed_seconds(trace, pattern, lines=(OPS_LINE, ASYNC_LINE),
                    text=False):
    """Seconds per chip in which an operation matching ``pattern`` (on any
    of ``lines``) runs and no other operation of ``XLA Ops`` does."""
    planes = device_planes(trace)
    if not planes:
        return None
    keep = _matching(trace, pattern, text)
    t = 0
    for p in planes:
        mine = union((s, e) for ln in lines
                     for n, s, e in line_events(p, ln) if n in keep)
        rest = union((s, e) for n, s, e in line_events(p, OPS_LINE)
                     if n not in keep)
        t += total(subtract(mine, rest))
    return t / len(planes) * 1e-9


def program_durations(trace, pattern):
    """Seconds of every execution of a program whose name matches
    ``pattern``, all chips together."""
    keep = _matching(trace, pattern)
    return [(e - s) * 1e-9 for p in device_planes(trace)
            for n, s, e in line_events(p, MODULES_LINE) if n in keep]


def busy_share_in_programs(trace, pattern):
    """Share of the chips' busy time that lies inside executions of the
    programs matching ``pattern``."""
    keep = _matching(trace, pattern)
    inside = busy = 0
    for p in device_planes(trace):
        ops = union((s, e) for _, s, e in line_events(p, OPS_LINE))
        progs = union((s, e) for n, s, e in line_events(p, MODULES_LINE)
                      if n in keep)
        busy += total(ops)
        inside += total(intersect(ops, progs))
    return inside / busy if busy else None


def top_ops(trace, k=10):
    """The ``k`` kinds of operation (see :func:`kind`) with most device
    time: [[kind, seconds], ...], seconds per chip."""
    planes = device_planes(trace)
    if not planes:
        return []
    kinds = [kind(name) for name in trace["names"]]
    acc = {}
    for p in planes:
        for n, s, e in line_events(p, OPS_LINE):
            acc[kinds[n]] = acc.get(kinds[n], 0) + (e - s)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t / len(planes) * 1e-9] for name, t in top]


def idle_gaps(trace, k=10):
    """The idle time of the chips by what the host was doing:
    [[host span name, seconds], ...], largest first, seconds per chip."""
    planes = device_planes(trace)
    if not planes:
        return []
    host = []
    for p in trace["planes"]:
        if re.match(HOST_PLANE, p["name"]):
            for ln in p["lines"]:
                host.extend(zip(ln["s"],
                                (s + d for s, d in zip(ln["s"], ln["d"])),
                                ln["n"]))
    host.sort()
    acc = {}
    for p in planes:
        cover = union((s, e) for _, s, e in line_events(p, OPS_LINE))
        for (_, a), (b, _) in zip(cover, cover[1:]):
            if b - a < MIN_GAP_NS:
                key = "between_ops_under_10us_each"
            else:
                mid = (a + b) // 2
                inner = None
                for s, e, n in host:
                    if s > mid:
                        break
                    if e >= mid and (inner is None
                                     or e - s < inner[1] - inner[0]):
                        inner = (s, e, n)
                key = trace["names"][inner[2]] if inner else "host_no_span"
            acc[key] = acc.get(key, 0) + (b - a)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t / len(planes) * 1e-9] for name, t in top]
