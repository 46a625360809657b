"""Device milliseconds a step by named scope: the train step's account.

The program opens ``jax.named_scope`` around the phases of its train step
and the parts of its model (``horovod_tpu/observability/scopes.py`` is the
list, and owns what a scope path means: ``scopes.parse``). A scope is
metadata: it reaches every HLO instruction as its ``op_name``, and the
profiler writes that path into the ``.xplane.pb`` as the stat ``tf_op`` of
the instruction's event METADATA on the device plane.
``trace_reduce.from_xplane`` keeps an event's name, start and duration, and
``jax.profiler.ProfileData`` shows an event's own stats only (PR 39 looked on
the chip: ``device_offset_ps``, ``device_duration_ps``, no ``tf_op``), so
:func:`table` reads the metadata from the file itself. The file is a
protobuf (``XSpace`` of tsl's ``xplane.proto``); jaxlib ships no Python
class for it, and the few fields wanted are read here from the wire format,
so neither JAX nor TensorFlow is imported and nothing can touch a chip.
``python3 -m benchmark.readers.xplane_scopes <file>`` prints the table.

:func:`read` takes all its TIMES from the reduced trace (``ctx["trace"]``,
``trace_reduce``'s helpers) and from the table only which scope an
instruction's text belongs to. An event's time is its SELF time: its
interval less the ``XLA Ops`` events nested in it, so a ``while`` and the
operations of its body are not counted twice and a ``while`` with no scope
does not swallow its scoped children. Summed over every event the self times
are the chip's busy time, so the account closes by construction: every
(phase, scope, direction) plus the unattributed rest is ``step_dev_ms``.

An instruction the compiler made carries no ``op_name`` (the copies between
memory spaces: ``copy-start`` / ``copy-done``, ``slice-start`` /
``slice-done``), and a copy of an argument into another layout carries the
argument's name: no scope of the program holds either. Their time is the
wait for data that another operation needs, so an event with no path that
``scopes.parse`` knows is charged to the FIRST operation that takes its
result as an operand (read from the instructions' texts, which list their
operands; followed through at most ``MOVES_DEPTH`` such steps). What then
still has no scope is unattributed (a copy whose result only the program's
output takes, an operation of another program), and :func:`account` hands
it back by name for ``PERF.md``.

``params``: ``phase`` and ``scope`` are patterns matched against the whole
name (absent: any, none included); ``backward`` true or false (absent:
both); ``unattributed`` true asks for the check instead. The result is
milliseconds a step a chip (``fields.trace_steps``), or for the check
percent of the busy time. ``None`` where there is nothing to read: no
``trace_steps`` (a serving cell), no raw ``.xplane.pb`` under
``spec.trace_dir`` and no table in ``ctx``, a table without one ``tf_op``
(a program that opens no scope), or a program without ``scopes.py`` (the
parent of PR 39). It never raises for what a run lacks.

``ctx["scope_table"]`` (the table) and ``ctx["scope_account"]`` are made
once a run, from rank 0's file (every rank runs the same program), and kept
in ``ctx`` for the other metrics of the line.
"""

import glob
import json
import os
import re
import sys

from benchmark import trace_reduce

MOVES_DEPTH = 4
_OPCODE = re.compile(r"\s[\w\-]+\(")     # between a space and ``(``
_OPERAND = re.compile(r"%([\w.\-]+)")

try:
    from horovod_tpu.observability import scopes
except ImportError:          # a program from before PR 39: nothing to read
    scopes = None


# ---- the protobuf wire format, as far as xplane.proto needs it --------------

def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf, i, end):
    """-> (field number, value) of one message: an int for a varint, a
    ``(start, end)`` pair for a length-delimited field; fixed-width fields
    are passed over."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, spans):
    """The value message (field 2) of each entry of a protobuf map."""
    for a, e in spans:
        for f, v in _fields(buf, a, e):
            if f == 2:
                yield v


def table(xplane_path):
    """-> {instruction text: scope path} from the event metadata of the
    first device plane of an ``.xplane.pb`` (the stat ``tf_op``). An
    instruction text that two metadata give different paths maps to ``""``."""
    with open(xplane_path, "rb") as f:
        buf = memoryview(f.read())
    for f, plane in _fields(buf, 0, len(buf)):            # XSpace.planes
        if f != 1:
            continue
        name, events, stats = "", [], []
        for f2, v in _fields(buf, *plane):                # XPlane
            if f2 == 2:
                name = _text(buf, v)
            elif f2 == 4:
                events.append(v)
            elif f2 == 5:
                stats.append(v)
        if not re.match(trace_reduce.DEVICE_PLANE, name):
            continue
        stat_names = {}
        for a, e in _map_values(buf, stats):              # XStatMetadata
            m = dict(_fields(buf, a, e))
            if 2 in m:
                stat_names[m.get(1, 0)] = _text(buf, m[2])
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        out = {}
        for a, e in _map_values(buf, events):             # XEventMetadata
            text = path = None
            for f3, v in _fields(buf, a, e):
                if f3 == 2:
                    text = _text(buf, v)
                elif f3 == 5:                             # XStat
                    st = dict(_fields(buf, *v))
                    if st.get(1) in tf_op:
                        path = (_text(buf, st[5]) if 5 in st
                                else stat_names.get(st.get(7), ""))
            if text is not None and path is not None:
                out[text] = path if out.get(text, path) == path else ""
        return out
    return {}


def find_xplane(spec):
    """Rank 0's raw profile of this run, or None."""
    files = sorted(glob.glob(os.path.join(
        spec.get("trace_dir") or "", "rank0", "plugins", "profile", "*",
        "*.xplane.pb")), key=os.path.getmtime)
    return files[-1] if files else None


# ---- the account -----------------------------------------------------------

def self_times(events):
    """``[(name, start, end)]`` of one chip's ``XLA Ops`` -> {name: ns} of
    self time: each event's length less the events that start inside it."""
    out, stack = {}, []          # stack: [end, name, covered until]
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[n] = out.get(n, 0) + (e - s)
        if stack:
            top = stack[-1]
            inside = min(e, top[0]) - max(s, top[2])
            if inside > 0:
                out[top[1]] -= inside
                top[2] = min(e, top[0])
        stack.append([e, n, s])
    return out


def _first_users(trace):
    """-> {instruction: index of the name of the first operation (of the
    first chip; every chip runs the same program) that takes the
    instruction's result as an operand}."""
    first = {}
    for n, s, _ in trace_reduce.line_events(
            trace_reduce.device_planes(trace)[0], trace_reduce.OPS_LINE):
        if s < first.get(n, s + 1):
            first[n] = s
    users = {}
    for n in sorted(first, key=first.get):
        text = trace["names"][n].partition(" = ")[2]
        m = _OPCODE.search(text)
        for operand in _OPERAND.findall(text[m.end():] if m else ""):
            users.setdefault(operand, n)
    return users


def _parsed(trace, tbl):
    """What ``scopes.parse`` makes of every name's path; for a name without
    one, of its first user's."""
    names, users = trace["names"], _first_users(trace)

    def found(n, depth=0):
        got = scopes.parse(tbl.get(names[n]))
        if got or depth == MOVES_DEPTH:
            return got
        user = users.get(trace_reduce.instr(names[n]))
        return None if user is None else found(user, depth + 1)

    return [found(n) for n in range(len(names))]


def account(trace, tbl):
    """-> ({(phase, scope, backward) or None: ns a chip}, {instruction text:
    ns a chip} of the unattributed events)."""
    planes = trace_reduce.device_planes(trace)
    parsed = _parsed(trace, tbl)
    acc, rest = {}, {}
    for p in planes:
        times = self_times(trace_reduce.line_events(p, trace_reduce.OPS_LINE))
        for n, t in times.items():
            key = parsed[n]
            acc[key] = acc.get(key, 0) + t / len(planes)
            if key is None:
                name = trace["names"][n]
                rest[name] = rest.get(name, 0) + t / len(planes)
    return acc, rest


def _account_of(ctx):
    if "scope_account" not in ctx:
        ctx["scope_account"] = None
        if scopes is None:
            return None
        if "scope_table" not in ctx:
            path = find_xplane(ctx.get("spec") or {})
            try:
                ctx["scope_table"] = table(path) if path else None
            except (OSError, ValueError, IndexError):
                ctx["scope_table"] = None
        tbl = ctx["scope_table"]
        if tbl and any(tbl.values()) and trace_reduce.device_planes(
                ctx["trace"]):
            ctx["scope_account"] = account(ctx["trace"], tbl)
    return ctx["scope_account"]


def _wanted(pattern, value):
    return pattern is None or (value is not None
                               and re.fullmatch(pattern, value) is not None)


def read(ctx, params):
    steps = (ctx.get("fields") or {}).get("trace_steps")
    if not steps:
        return None
    found = _account_of(ctx)
    if not found:
        return None
    acc = found[0]
    if params.get("unattributed"):
        busy = sum(acc.values())
        return 100.0 * acc.get(None, 0) / busy if busy else None
    backward = params.get("backward")
    ns = sum(t for key, t in acc.items() if key is not None
             and _wanted(params.get("phase"), key[0])
             and _wanted(params.get("scope"), key[1])
             and backward in (None, key[2]))
    return ns / steps * 1e-6


if __name__ == "__main__":
    json.dump(table(sys.argv[1]), sys.stdout, indent=0)
