#!/usr/bin/env python3
"""hvdlint — repo-custom static consistency checker for horovod_tpu.

The tuning surface spans four layers that are supposed to mirror each
other — `HVD_*` env knobs read in C++ and Python, `tpurun` CLI flags,
YAML config keys, and the docs — plus two in-core contracts worth
pinning as pattern checks. Drift between them is invisible to the type
system and to pytest, so this lint parses the sources and enforces:

  knob-docs      every HVD_* knob READ anywhere (csrc Env*/getenv, Python
                 os.environ/os.getenv) is documented in
                 docs/perf_tuning.md or docs/running.md
  arm-stats      every autotune categorical arm (`int8_t tuned_X` in
                 csrc/common.h) has a matching `X_stats()` introspection
                 in basics.py, a column named X in autotune.cc's CSV
                 header, and `init_X`/`can_toggle_X` fields on
                 AutotuneConfig (autotune.h) — the three places a new
                 arm must be threaded through or the search silently
                 never walks it; additionally the C++ CSV header literal
                 must equal the shared schema table
                 (horovod_tpu/observability/autotune_csv.py COLUMNS) so
                 the writer and every Python consumer split rows the
                 same way
  config-parity  config_parser.ARG_TO_ENV attrs <-> launch.py CLI flags
                 <-> _FILE_SECTIONS YAML keys stay in sync (both ways
                 for YAML, env->CLI for flags)
  raw-getenv     no raw std::getenv in csrc outside logging.h — EnvRaw
                 is the one designated knob-reading site (it owns the
                 HVD_ -> HOROVOD_ compat fallback)
  counter-order  in core.cc's ExecAllreduce, every zerocopy/staging
                 counter bump precedes the first CompleteHandle of its
                 return-delimited path segment (the PR-3 contract: a
                 caller polling stats the instant its op resolves never
                 sees the op uncounted)
  blocking-syscall
                 every wait-class syscall site in csrc (poll/ppoll,
                 accept, connect, epoll_wait, io_uring_enter — calls
                 that can park the thread indefinitely) arms BOTH the
                 fault-injection hook (fault::Check) and the lockdep
                 blocking-IO hook (lockdep::OnBlockingSyscall) within
                 the preceding few lines, so chaos tests can interpose
                 on every place the data/control plane can wedge and
                 debug builds flag locks held across the wait

Run standalone (`python tools/hvdlint.py`, or `make check` from csrc/)
or via pytest (tests/test_hvdlint.py, tier-1). Zero suppressions: a
violation is fixed, not ignored. docs/static_analysis.md documents the
rules and how to extend them.
"""
import argparse
import ast
import os
import re
import sys

# --- knob read patterns ----------------------------------------------------

# C++: the Env* helpers (core.cc/logging.h) and any raw getenv, called with
# a literal HVD_ name. Literal arrays (logging.h kNoCompat) don't match the
# call form.
CXX_READ = re.compile(
    r'\b(?:EnvStr|EnvInt|EnvDouble|EnvRaw|getenv)\(\s*"(HVD_[A-Z0-9_]+)"')

# Python: os.environ.get / os.getenv / os.environ[...] reads, tolerating the
# `import os as _os` idiom. Dict-copy plumbing (env.get(...) on a child-env
# dict) is out of scope on purpose: it forwards knobs, it doesn't consume
# them.
PY_READ = re.compile(
    r'\b_?os\s*\.\s*(?:environ\.get|getenv)\(\s*["\'](HVD_[A-Z0-9_]+)')
PY_SUBSCRIPT = re.compile(
    r'\b_?os\s*\.\s*environ\[\s*["\'](HVD_[A-Z0-9_]+)["\']\s*\]')
DOC_KNOB = re.compile(r"HVD_[A-Z0-9_]+")

# Docs that count as knob documentation (the ISSUE fixes this set: the
# perf-tuning reference and the running/config reference).
KNOB_DOCS = ("docs/perf_tuning.md", "docs/running.md")

# The one csrc file allowed to call getenv: EnvRaw lives there.
GETENV_OK = {"logging.h"}


class Violation:
    def __init__(self, rule, path, line, symbol, message):
        self.rule = rule
        self.path = path
        self.line = line
        self.symbol = symbol
        self.message = message

    def __str__(self):
        return "%s:%d: [%s] %s: %s" % (
            self.path, self.line, self.rule, self.symbol, self.message)


def _read(path):
    with open(path, encoding="utf-8", errors="replace") as f:
        return f.read()


def _iter_files(root, rel_dir, exts):
    base = os.path.join(root, rel_dir)
    if not os.path.isdir(base):
        return
    for dirpath, _dirnames, filenames in os.walk(base):
        for name in sorted(filenames):
            if name.endswith(exts):
                yield os.path.join(dirpath, name)


def _rel(root, path):
    return os.path.relpath(path, root)


# --- rule: knob-docs -------------------------------------------------------

def collect_knob_reads(root):
    """[(knob, relpath, lineno)] for every literal HVD_* read in the
    package sources (csrc C++ + horovod_tpu Python)."""
    reads = []
    for path in _iter_files(root, "horovod_tpu/csrc", (".cc", ".h")):
        for i, line in enumerate(_read(path).splitlines(), 1):
            for m in CXX_READ.finditer(line):
                reads.append((m.group(1), _rel(root, path), i))
    for path in _iter_files(root, "horovod_tpu", (".py",)):
        for i, line in enumerate(_read(path).splitlines(), 1):
            for m in PY_READ.finditer(line):
                reads.append((m.group(1), _rel(root, path), i))
            for m in PY_SUBSCRIPT.finditer(line):
                rest = line[m.end():]
                # `os.environ["X"] = v` assigns and `del os.environ["X"]`
                # clears — neither consumes the knob's value.
                if re.match(r"\s*=(?!=)", rest):
                    continue
                if re.search(r"\bdel\s+$", line[:m.start()]):
                    continue
                reads.append((m.group(1), _rel(root, path), i))
    return reads


def check_knob_docs(root):
    documented = set()
    for doc in KNOB_DOCS:
        path = os.path.join(root, doc)
        if os.path.exists(path):
            documented |= set(DOC_KNOB.findall(_read(path)))
    out = []
    seen = set()
    for knob, relpath, line in collect_knob_reads(root):
        if knob in documented or knob in seen:
            continue
        seen.add(knob)
        out.append(Violation(
            "knob-docs", relpath, line, knob,
            "knob is read here but documented in neither %s"
            % " nor ".join(KNOB_DOCS)))
    return out


# --- rule: arm-stats -------------------------------------------------------

def _autotune_csv_columns(src):
    """Column names of the autotune CSV header fprintf in autotune.cc,
    or None if the anchor string moved. The header literal may span
    several adjacent C string pieces."""
    m = re.search(r'"sample,[^;]*?score_mbps\\n"', src, re.S)
    if not m:
        return None
    joined = "".join(re.findall(r'"([^"]*)"', m.group(0)))
    return joined.replace("\\n", "").split(",")


def _schema_columns(root):
    """COLUMNS from horovod_tpu/observability/autotune_csv.py (the shared
    schema table), parsed via ast so linting never imports the package, or
    None when the module/table is absent."""
    path = os.path.join(root, "horovod_tpu", "observability",
                        "autotune_csv.py")
    if not os.path.exists(path):
        return None, path
    for node in ast.walk(ast.parse(_read(path))):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "COLUMNS"
                and isinstance(node.value, (ast.Tuple, ast.List))):
            cols = [e.value for e in node.value.elts
                    if isinstance(e, ast.Constant)]
            return cols, path
    return None, path


def check_arm_stats(root):
    common = os.path.join(root, "horovod_tpu", "csrc", "common.h")
    basics = os.path.join(root, "horovod_tpu", "basics.py")
    at_h = os.path.join(root, "horovod_tpu", "csrc", "autotune.h")
    at_cc = os.path.join(root, "horovod_tpu", "csrc", "autotune.cc")
    if not (os.path.exists(common) and os.path.exists(basics)):
        return []
    basics_src = _read(basics)
    at_h_src = _read(at_h) if os.path.exists(at_h) else ""
    csv_cols = None
    if os.path.exists(at_cc):
        csv_cols = _autotune_csv_columns(_read(at_cc))
    out = []
    # The C++ writer's header literal and the shared Python schema table
    # must be the SAME row layout, or every consumer slicing columns by
    # name (worker asserts, operator tooling) reads
    # skewed fields.
    schema_cols, schema_path = _schema_columns(root)
    if csv_cols is not None and schema_cols is not None \
            and csv_cols != schema_cols:
        out.append(Violation(
            "arm-stats", _rel(root, schema_path), 1, "COLUMNS",
            "autotune_csv.COLUMNS (%s) != the CSV header literal in "
            "autotune.cc (%s)" % (",".join(schema_cols),
                                  ",".join(csv_cols))))
    for i, line in enumerate(_read(common).splitlines(), 1):
        for m in re.finditer(r"\bint8_t\s+tuned_([a-z0-9_]+)", line):
            arm = m.group(1)
            if not re.search(r"\bdef\s+%s_stats\s*\(" % arm, basics_src):
                out.append(Violation(
                    "arm-stats", _rel(root, common), i, "tuned_" + arm,
                    "autotune arm has no %s_stats() introspection in "
                    "basics.py" % arm))
            if csv_cols is not None and arm not in csv_cols:
                out.append(Violation(
                    "arm-stats", _rel(root, common), i, "tuned_" + arm,
                    "autotune arm missing from the CSV header columns in "
                    "autotune.cc (%s)" % ",".join(csv_cols)))
            for param in ("init_%s" % arm, "can_toggle_%s" % arm):
                if at_h_src and not re.search(
                        r"\b%s\b" % param, at_h_src):
                    out.append(Violation(
                        "arm-stats", _rel(root, common), i, "tuned_" + arm,
                        "Autotuner::Configure (autotune.h) has no %s "
                        "parameter — the arm can never be seeded or "
                        "swept" % param))
    return out


# --- rule: config-parity ---------------------------------------------------

def _parse_config_parser(path):
    """(arg_to_env {attr: (env, lineno)}, file_attrs {attr: lineno})."""
    tree = ast.parse(_read(path))
    arg_to_env, file_attrs = {}, {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name):
            continue
        if target.id == "ARG_TO_ENV" and isinstance(node.value, ast.Dict):
            for k, v in zip(node.value.keys, node.value.values):
                if not isinstance(k, ast.Constant):
                    continue
                env = None
                if isinstance(v, ast.Tuple) and v.elts and \
                        isinstance(v.elts[0], ast.Constant):
                    env = v.elts[0].value
                arg_to_env[k.value] = (env, k.lineno)
        if target.id == "_FILE_SECTIONS" and isinstance(node.value, ast.Dict):
            for section in node.value.values:
                if not isinstance(section, ast.Dict):
                    continue
                for v in section.values:
                    if isinstance(v, ast.Constant):
                        file_attrs[v.value] = v.lineno
    return arg_to_env, file_attrs


def _parse_cli_dests(path):
    """{dest: lineno} for every add_argument in launch.py's parser."""
    tree = ast.parse(_read(path))
    dests = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            continue
        dest = None
        for kw in node.keywords:
            if kw.arg == "dest" and isinstance(kw.value, ast.Constant):
                dest = kw.value.value
        if dest is None:
            flags = [a.value for a in node.args
                     if isinstance(a, ast.Constant)
                     and isinstance(a.value, str)]
            longs = [f for f in flags if f.startswith("--")]
            if longs:
                dest = longs[0].lstrip("-").replace("-", "_")
            elif flags and not flags[0].startswith("-"):
                dest = flags[0]  # positional
        if dest:
            dests[dest] = node.lineno
    return dests


def check_config_parity(root):
    cp = os.path.join(root, "horovod_tpu", "runner", "config_parser.py")
    lp = os.path.join(root, "horovod_tpu", "runner", "launch.py")
    if not (os.path.exists(cp) and os.path.exists(lp)):
        return []
    arg_to_env, file_attrs = _parse_config_parser(cp)
    dests = _parse_cli_dests(lp)
    out = []
    for attr, (env, lineno) in sorted(arg_to_env.items()):
        if attr not in dests:
            out.append(Violation(
                "config-parity", _rel(root, cp), lineno, attr,
                "maps to %s but launch.py has no CLI flag with this dest"
                % env))
        if attr not in file_attrs:
            out.append(Violation(
                "config-parity", _rel(root, cp), lineno, attr,
                "maps to %s but _FILE_SECTIONS has no YAML key for it"
                % env))
    for attr, lineno in sorted(file_attrs.items()):
        if attr not in arg_to_env:
            out.append(Violation(
                "config-parity", _rel(root, cp), lineno, attr,
                "YAML key maps to an attr missing from ARG_TO_ENV "
                "(no env spelling)"))
    return out


# --- rule: raw-getenv ------------------------------------------------------

def check_raw_getenv(root):
    out = []
    for path in _iter_files(root, "horovod_tpu/csrc", (".cc", ".h")):
        if os.path.basename(path) in GETENV_OK:
            continue
        for i, line in enumerate(_read(path).splitlines(), 1):
            m = re.search(r"\bgetenv\s*\(", line)
            if m:
                out.append(Violation(
                    "raw-getenv", _rel(root, path), i,
                    line.strip()[:60],
                    "raw getenv outside logging.h — use EnvRaw/EnvStr/"
                    "EnvInt/EnvDouble (they own the HOROVOD_ compat "
                    "fallback)"))
    return out


# --- rule: counter-order ---------------------------------------------------

COUNTER = re.compile(r"ps\.Publish\(\)|g->\w+_total\s*(?:\+\+|\+=)")
COMPLETE = re.compile(r"\bCompleteHandle\s*\(")


def _function_body(src, signature):
    """(start_lineno, lines) of the brace-matched body of `signature`."""
    idx = src.find(signature)
    if idx < 0:
        return None, []
    start_line = src.count("\n", 0, idx) + 1
    depth = 0
    seen_open = False
    end = idx
    for end in range(idx, len(src)):
        c = src[end]
        if c == "{":
            depth += 1
            seen_open = True
        elif c == "}":
            depth -= 1
            if seen_open and depth == 0:
                break
    return start_line, src[idx:end + 1].splitlines()


def check_counter_order(root):
    core = os.path.join(root, "horovod_tpu", "csrc", "core.cc")
    if not os.path.exists(core):
        return []
    start, body = _function_body(_read(core), "void ExecAllreduce(")
    if not body:
        return [Violation("counter-order",
                          _rel(root, core), 1, "ExecAllreduce",
                          "ExecAllreduce not found — update hvdlint's "
                          "anchor if it was renamed")]
    out = []
    seg_counter, seg_complete = [], []  # (lineno, text) within segment
    for off, line in enumerate(body):
        lineno = start + off
        if COUNTER.search(line):
            seg_counter.append((lineno, line.strip()))
        if COMPLETE.search(line):
            seg_complete.append((lineno, line.strip()))
        if re.search(r"\breturn\s*;", line) or off == len(body) - 1:
            # Segment boundary: grade this completion path.
            if seg_complete and seg_counter:
                first_complete = min(ln for ln, _ in seg_complete)
                for ln, text in seg_counter:
                    if ln > first_complete:
                        out.append(Violation(
                            "counter-order", _rel(root, core), ln,
                            text[:60],
                            "counter bumped AFTER CompleteHandle (line %d) "
                            "on the same path — a caller polling stats "
                            "when its op resolves races this bump"
                            % first_complete))
            seg_counter, seg_complete = [], []
    return out


# --- rule: blocking-syscall ------------------------------------------------

# Wait-class syscalls: the calls that can park the thread until a peer (or
# the kernel) acts. Byte-moving syscalls (sendmsg/recv/readv) are out of
# scope — on the hot path they run only after poll reported readiness (or
# inside io_uring, which has its own hook at the enter site). The
# io_uring_enter pattern matches the raw-syscall invocation, not the
# __NR_* feature-detection #ifdefs.
WAIT_SYSCALL = re.compile(
    r"::poll\s*\(|::ppoll\s*\(|::accept4?\s*\(|::connect\s*\(|"
    r"::epoll_wait\s*\(|\bsyscall\s*\(\s*__NR_io_uring_enter\b")
SYSCALL_HOOKS = ("fault::Check", "lockdep::OnBlockingSyscall")
HOOK_WINDOW = 8  # lines above the syscall both hooks must appear within


def check_blocking_syscall(root):
    out = []
    for path in _iter_files(root, "horovod_tpu/csrc", (".cc", ".h")):
        lines = _read(path).splitlines()
        for i, line in enumerate(lines, 1):
            code = line.split("//")[0]
            if not WAIT_SYSCALL.search(code):
                continue
            window = "\n".join(lines[max(0, i - 1 - HOOK_WINDOW):i])
            for hook in SYSCALL_HOOKS:
                if hook not in window:
                    out.append(Violation(
                        "blocking-syscall", _rel(root, path), i,
                        code.strip()[:60],
                        "wait-class syscall without %s() in the %d "
                        "preceding lines — chaos tests cannot interpose "
                        "here and debug builds cannot flag locks held "
                        "across the wait" % (hook, HOOK_WINDOW)))
    return out


# --- driver ----------------------------------------------------------------

CHECKS = [
    check_knob_docs,
    check_arm_stats,
    check_config_parity,
    check_raw_getenv,
    check_counter_order,
    check_blocking_syscall,
]


def run(root):
    violations = []
    for check in CHECKS:
        violations += check(root)
    return violations


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    default_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--repo", default=default_root,
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--list-knobs", action="store_true",
                    help="dump every HVD_* knob read and where, then exit")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.repo)
    if args.list_knobs:
        for knob, path, line in sorted(set(collect_knob_reads(root))):
            print("%-36s %s:%d" % (knob, path, line))
        return 0
    violations = run(root)
    for v in violations:
        print(v)
    if violations:
        print("hvdlint: %d violation(s)" % len(violations))
        return 1
    print("hvdlint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
