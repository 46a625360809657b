"""Launcher utilities: HMAC secrets, safe process execution, host hashing.

Reference parity: `horovod/runner/common/util/secret.py` (HMAC tokens),
`safe_shell_exec.py` (process-group-safe spawn/terminate),
`host_hash.py`.
"""

import hashlib
import hmac
import os
import secrets as _secrets
import signal
import socket
import subprocess
import time

GRACEFUL_TERMINATION_TIME_S = 5.0

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir():
    """Where the repo's own drivers (chip_smoke.py, benchmark/run.py) tell the
    processes they start to keep JAX's persistent compilation cache: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names when the machine sets
    one, otherwise ``.jax_cache`` at the root of the checkout. The path is
    part of JAX's cache key, so it is fixed: never the temp dir, a uid, a
    pid or a time."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def make_secret_key() -> bytes:
    return _secrets.token_bytes(32)


def sign(key: bytes, payload: bytes) -> str:
    return hmac.new(key, payload, hashlib.sha256).hexdigest()


def check_signature(key: bytes, payload: bytes, signature: str) -> bool:
    return hmac.compare_digest(sign(key, payload), signature)


def host_hash(salt=None):
    """Stable identifier for this host (reference: host_hash.py; used to
    group ranks into local sets)."""
    h = socket.gethostname()
    if salt:
        h = f"{h}-{salt}"
    return hashlib.md5(h.encode()).hexdigest()


def safe_exec(command, env=None, stdout=None, stderr=None, stdin=None):
    """Spawn `command` in its own process group so the whole tree can be
    terminated (reference: safe_shell_exec.py)."""
    return subprocess.Popen(command, env=env, stdout=stdout, stderr=stderr,
                            stdin=stdin, preexec_fn=os.setsid)


def send_stdin_line(proc, data: bytes):
    """Write one line to `proc`'s stdin and close it, tolerating the process
    having already died (ssh missing, instant connection refused) — the
    caller learns the story from its exit code, not a BrokenPipeError.
    Used to pass the HMAC secret to remote workers off the command line."""
    try:
        proc.stdin.write(data + b"\n")
        proc.stdin.flush()
        proc.stdin.close()
    except (BrokenPipeError, OSError):
        pass


def terminate(proc, timeout=GRACEFUL_TERMINATION_TIME_S):
    """SIGTERM the process group, escalate to SIGKILL after `timeout`."""
    if proc.poll() is not None:
        return
    try:
        pgid = os.getpgid(proc.pid)
    except OSError:
        return
    try:
        os.killpg(pgid, signal.SIGTERM)
    except OSError:
        pass
    deadline = time.time() + timeout
    while proc.poll() is None and time.time() < deadline:
        time.sleep(0.05)
    if proc.poll() is None:
        try:
            os.killpg(pgid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
