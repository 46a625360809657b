"""Core-polish coverage (VERDICT r1 item #9 + ADVICE #1): NEGOTIATE timeline
phase, stall-inspector disable semantics, bounded single-rank shutdown,
negotiation frame-size sanity cap, and HVD_LOG_LEVEL consumption."""

import json
import os
import socket
import struct
import subprocess
import sys
import time

from .util import WORKERS, _REPO


def _run_job(np_, worker, extra_env=None, timeout=90, controller_port=None):
    """run_local with captured combined output (for stderr assertions)."""
    from horovod_tpu.runner.local import run_local

    env = {"PYTHONPATH": _REPO, "JAX_PLATFORMS": "cpu"}
    env.update(extra_env or {})
    out_path = os.path.join("/tmp", f"job_out_{os.getpid()}_{worker}.log")
    with open(out_path, "w") as f:
        codes = run_local(np_, [sys.executable, os.path.join(WORKERS, worker)],
                          env=env, timeout=timeout, stdout=f,
                          controller_port=controller_port)
    with open(out_path) as f:
        output = f.read()
    os.unlink(out_path)
    return codes, output


def test_timeline_negotiate_phase(tmp_path):
    """The timeline records the QUEUE -> NEGOTIATE_* -> TCP_* lifecycle
    (reference: NEGOTIATE_ALLREDUCE / WAIT_FOR_OTHER_TENSOR_DATA phases in
    docs/timeline.rst)."""
    tl = tmp_path / "tl.json"
    codes, out = _run_job(2, "stall_worker.py",
                          extra_env={"HVD_TIMELINE": str(tl)})
    assert codes == [0, 0], out
    events = json.loads(tl.read_text())
    phases = {e["name"] for e in events if e.get("ph") == "X"}
    assert "QUEUE" in phases, phases
    assert "NEGOTIATE_ALLREDUCE" in phases, phases
    assert "TCP_ALLREDUCE" in phases, phases
    # rank 1 announced ~2.5s late; the coordinator's NEGOTIATE phase for the
    # early rank must span that wait.
    neg = [e for e in events if e["name"] == "NEGOTIATE_ALLREDUCE"]
    assert max(e["dur"] for e in neg) > 1_000_000, neg


def test_stall_warning_fires():
    codes, out = _run_job(2, "stall_worker.py",
                          extra_env={"HVD_STALL_CHECK_TIME_SECONDS": "1"})
    assert codes == [0, 0], out
    assert "potential stall" in out, out
    assert "NOT by ranks [ 1 ]" in out, out


def test_stall_check_disabled():
    """--no-stall-check maps to HVD_STALL_CHECK_TIME_SECONDS=0, which now
    disables the inspector instead of warning every cycle (ADVICE r1 #1)."""
    codes, out = _run_job(2, "stall_worker.py",
                          extra_env={"HVD_STALL_CHECK_TIME_SECONDS": "0"})
    assert codes == [0, 0], out
    assert "potential stall" not in out, out


def test_stall_shutdown_fires_even_with_warnings_disabled():
    """HVD_STALL_SHUTDOWN_TIME_SECONDS aborts a stalled job with
    HorovodInternalError, and silencing warnings with
    HVD_STALL_CHECK_TIME_SECONDS=0 does NOT disable the explicitly
    configured shutdown threshold (ADVICE r2 #3)."""
    codes, out = _run_job(2, "stall_shutdown_worker.py",
                          extra_env={"HVD_STALL_CHECK_TIME_SECONDS": "0",
                                     "HVD_STALL_SHUTDOWN_TIME_SECONDS": "1"},
                          timeout=60)
    assert codes == [0, 0], out
    assert "HorovodInternalError as expected" in out, out
    assert "potential stall" not in out, out  # warnings stayed silenced


def test_cache_capacity_mismatch_reconciled():
    """Per-rank HVD_CACHE_CAPACITY disagreement is reconciled during the
    mesh handshake (rank 0 authoritative) instead of silently
    desynchronizing replica bit positions once eviction starts
    (ADVICE r2 #5)."""
    codes, out = _run_job(2, "cache_mismatch_worker.py", timeout=60)
    assert codes == [0, 0], out
    assert "HVD_CACHE_CAPACITY mismatch" in out, out


def test_single_rank_shutdown_does_not_hang():
    codes, out = _run_job(2, "early_shutdown_worker.py",
                          extra_env={"HVD_SHUTDOWN_TIMEOUT": "2"},
                          timeout=60)
    assert codes == [0, 0], out
    assert "HorovodInternalError as expected" in out, out


def test_profiler_op_ranges_and_trace_window(tmp_path):
    """Profiler parity (reference: nvtx_op_range.h → TPU xplane mapping,
    SURVEY §5): the open trace window is the one switch. Inside
    ``hvd.profiler.start/stop`` every collective call lands in the xplane
    artifact as an ``hvd.<op>`` range (the worker reads them back); the
    same calls outside the window leave none, and no environment
    variable is involved."""
    codes, out = _run_job(2, "profiler_worker.py",
                          extra_env={"PROFILE_DIR": str(tmp_path)})
    assert codes == [0, 0], out
    assert out.count("OK") == 2, out


def test_log_level_consumed():
    """HVD_LOG_LEVEL=info surfaces core init/shutdown logs; the default
    (warn) keeps them silent (reference: logging.cc HOROVOD_LOG_LEVEL)."""
    codes, out = _run_job(2, "stall_worker.py",
                          extra_env={"HVD_LOG_LEVEL": "info"})
    assert codes == [0, 0], out
    assert "[hvd info]" in out and "init: size=2" in out, out

    codes, out = _run_job(2, "stall_worker.py", extra_env={})
    assert codes == [0, 0], out
    assert "[hvd info]" not in out, out


def test_frame_size_sanity_cap():
    """A hostile/corrupt peer announcing a huge frame length must not OOM
    the coordinator. Since the resilient-rendezvous change (VERDICT r4
    weak #6) the hostile connection is DROPPED (CheckFrameLen throws, the
    accept loop closes the socket and keeps going) and the real job
    completes — previously the cap surfaced as an init failure."""
    port = _free_port()
    rogue_done = {}

    def rogue():
        # Dial the controller like a worker would, then claim a 3 GiB
        # frame. The coordinator must close the connection on us.
        deadline = time.time() + 10
        s = None
        while time.time() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=1)
                break
            except OSError:
                time.sleep(0.05)
        assert s is not None, "controller never listened"
        s.sendall(struct.pack("<I", 3 << 30))
        s.settimeout(20)
        try:
            rogue_done["closed"] = s.recv(1) == b""
        except OSError:
            rogue_done["closed"] = True  # reset also proves the drop
        s.close()

    import threading
    t = threading.Thread(target=rogue)
    t.start()
    # Explicit empty secret: auth off, so the rogue's frame-length claim
    # reaches RecvFrame (the cap under test) rather than the auth gate.
    codes, out = _run_job(2, "auth_worker.py",
                          extra_env={"AUTH_RANK1_DELAY": "4",
                                     "HVD_RENDEZVOUS_SECRET": ""},
                          timeout=90, controller_port=port)
    t.join(timeout=30)
    assert codes == [0, 0], out
    assert rogue_done.get("closed"), "coordinator never dropped the rogue"


def test_unauthenticated_connect_refused():
    """csrc/auth.cc (VERDICT r4 weak #7): with a job secret in the
    environment, every negotiated socket demands an HMAC-SHA256
    challenge-response on connect. A connector without the secret is
    refused (socket closed after a bad MAC) and the job completes
    undisturbed. This exceeds the reference: its Gloo pairs accept raw
    connects."""
    import secrets as pysecrets
    import threading

    port = _free_port()
    secret = pysecrets.token_hex(16)
    rogue_state = {}

    def rogue():
        deadline = time.time() + 10
        s = None
        while time.time() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=1)
                break
            except OSError:
                time.sleep(0.05)
        assert s is not None, "controller never listened"
        s.settimeout(20)
        try:
            challenge = b""
            while len(challenge) < 16:
                chunk = s.recv(16 - len(challenge))
                if not chunk:
                    break
                challenge += chunk
            rogue_state["challenged"] = len(challenge) == 16
            s.sendall(b"\x00" * 32)  # a MAC we cannot compute
            rogue_state["refused"] = s.recv(1) == b""
        except OSError:
            rogue_state["refused"] = True
        s.close()

    t = threading.Thread(target=rogue)
    t.start()
    codes, out = _run_job(
        2, "auth_worker.py",
        extra_env={"HVD_RENDEZVOUS_SECRET": secret,
                   "AUTH_RANK1_DELAY": "4"},
        timeout=90, controller_port=port)
    t.join(timeout=30)
    assert codes == [0, 0], out
    assert rogue_state.get("challenged"), "no challenge was issued"
    assert rogue_state.get("refused"), \
        "coordinator accepted an unauthenticated peer"


def test_silent_rogue_does_not_wedge_rendezvous():
    """A half-open connection that never sends a byte must not wedge the
    single-threaded accept loop: the handshake recv is bounded
    (Socket::SetRecvTimeout in EstablishMesh), after which the rogue is
    dropped and the real worker registers."""
    import threading

    import secrets as pysecrets

    port = _free_port()
    state = {}

    def rogue():
        deadline = time.time() + 10
        s = None
        while time.time() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=1)
                break
            except OSError:
                time.sleep(0.05)
        assert s is not None, "controller never listened"
        # Say nothing. The coordinator must give up on us by itself.
        s.settimeout(30)
        try:
            while s.recv(64):
                pass  # drain the challenge; still never answer
            state["dropped"] = True
        except OSError:
            state["dropped"] = True
        s.close()

    t = threading.Thread(target=rogue)
    t.start()
    codes, out = _run_job(
        2, "auth_worker.py",
        extra_env={"HVD_RENDEZVOUS_SECRET": pysecrets.token_hex(16),
                   "AUTH_RANK1_DELAY": "4"},
        timeout=90, controller_port=port)
    t.join(timeout=40)
    assert codes == [0, 0], out
    assert state.get("dropped"), "coordinator never dropped the silent peer"


def test_hmac_matches_hashlib():
    """Known-answer check of the core's hand-rolled HMAC-SHA256
    (csrc/auth.cc) against Python's hashlib — a SHA that merely
    self-agrees across ranks would still pass the handshake tests."""
    import ctypes
    import hashlib
    import hmac as pyhmac

    lib = ctypes.CDLL(os.path.join(_REPO, "horovod_tpu", "lib",
                                   "libhvd_tpu.so"))
    fn = lib.hvd_hmac_sha256
    fn.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
                   ctypes.c_int, ctypes.c_char_p]
    cases = [(b"k", b"m"), (b"x" * 65, b"data" * 100), (b"", b""),
             (bytes(range(32)), bytes(range(256)) * 3),
             (b"secret", b"a" * 55), (b"secret", b"a" * 56),
             (b"secret", b"a" * 64)]
    for key, msg in cases:
        out = ctypes.create_string_buffer(32)
        fn(key, len(key), msg, len(msg), out)
        want = pyhmac.new(key, msg, hashlib.sha256).digest()
        assert out.raw == want, (key, msg)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_dynamic_timeline(tmp_path):
    """start_timeline/stop_timeline at runtime (reference:
    horovod_start_timeline): traced window captured with cycle marks,
    untraced ops absent, restartable into a second file, error on double
    start / stop-before-start."""
    from .util import run_worker_job

    run_worker_job(2, "timeline_worker.py",
                   extra_env={"TL_PATH": str(tmp_path / "tl.json")})
