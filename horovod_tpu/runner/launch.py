"""`tpurun` — the launcher CLI (reference: `horovodrun`,
`horovod/runner/launch.py` `run_commandline`/`parse_args`/`_run`).

Static launch: parse hosts → assign ranks → export slot env (HVD_RANK...,
HVD_CONTROLLER_ADDR pointing at rank 0's host) → spawn one process per slot
(local fork or ssh), kill all on any failure. Elastic launch (min-np/max-np
+ discovery) lives in `horovod_tpu.runner.elastic` and is selected the same
way the reference does it: presence of --min-np/--max-np/
--host-discovery-script.

Usage:
    python -m horovod_tpu.runner.launch -np 4 python train.py
    tpurun -np 8 -H host1:4,host2:4 --timeline-filename /tmp/tl.json \
        python train.py
"""

import argparse
import os
import shlex
import sys

from . import config_parser, hosts as hosts_mod, util
from .local import find_free_port, maybe_bind_tpu_chip, slot_env
from .util import safe_exec, terminate


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="tpurun",
        description="Launch a horovod_tpu job: one process per slot/chip.")
    p.add_argument("-np", "--num-proc", dest="np", type=int,
                   help="total number of processes (default: all slots)")
    p.add_argument("-H", "--hosts", dest="hosts",
                   help='host list, e.g. "host1:4,host2:4" (default '
                        'localhost with -np slots)')
    p.add_argument("--hostfile", dest="hostfile",
                   help="file with one 'host slots=N' per line")
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("--remote-shell", dest="remote_shell",
                   choices=["ssh", "blaunch"], default=None,
                   help="remote spawn tool (default: ssh; blaunch "
                        "auto-selected inside an LSF allocation)")
    p.add_argument("--start-timeout", type=int, default=None,
                   help="seconds to wait for ranks to register")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--config-file", dest="config_file")
    p.add_argument("--disable-cache", action="store_true",
                   help="sets HVD_CACHE_CAPACITY=0")
    # tunables (config_parser maps these to HVD_* env)
    p.add_argument("--fusion-threshold-mb", dest="fusion_threshold_mb",
                   type=float, default=None)
    p.add_argument("--cycle-time-ms", dest="cycle_time_ms", type=float,
                   default=None)
    p.add_argument("--cache-capacity", dest="cache_capacity", type=int,
                   default=None)
    p.add_argument("--zerocopy-threshold-mb", dest="zerocopy_threshold_mb",
                   type=float, default=None,
                   help="min payload MB routed onto the scatter-gather "
                        "zero-copy ring (HVD_ZEROCOPY_THRESHOLD)")
    p.add_argument("--ring-pipeline", dest="ring_pipeline", type=int,
                   default=None,
                   help="ring reduce-scatter streaming depth "
                        "(HVD_RING_PIPELINE): 0 auto-sizes sub-chunks per "
                        "ring step, 1 forces the serial recv-then-reduce "
                        "path, N>1 splits each chunk into N sub-blocks")
    p.add_argument("--shm-threshold-mb", dest="shm_threshold_mb",
                   type=float, default=None,
                   help="min payload MB routed over the intra-host "
                        "shared-memory plane (HVD_SHM_THRESHOLD); smaller "
                        "same-host messages stay on TCP")
    p.add_argument("--bucket", dest="bucket", type=int, choices=[0, 1],
                   default=None,
                   help="backprop-ordered gradient bucketing (HVD_BUCKET): "
                        "1 forces it live from init, 0 disables it and "
                        "removes the autotune arm; unset leaves it off but "
                        "sweepable by autotune")
    p.add_argument("--bucket-bytes", dest="bucket_bytes", type=int,
                   default=None,
                   help="gradient bucket size bound in bytes "
                        "(HVD_BUCKET_BYTES, default 32 MiB): allreduces "
                        "are grouped into buckets of at most this many "
                        "payload bytes in backward-completion order")
    p.add_argument("--bucket-flush-ms", dest="bucket_flush_ms", type=int,
                   default=None,
                   help="ms an incomplete gradient bucket may hold its "
                        "members before flushing ungrouped "
                        "(HVD_BUCKET_FLUSH_MS, default 250)")
    p.add_argument("--compression", dest="compression",
                   choices=["int8", "topk", "0"], default=None,
                   help="lossy wire codec for f32 Sum/Average allreduces "
                        "(HVD_COMPRESS): int8 = error-feedback quantized "
                        "ring (~4x fewer wire bytes), topk = top-k "
                        "sparsified allgather (see --topk-frac), 0 = off "
                        "(the default; kill switch — wire byte-identical "
                        "to builds without the codecs). Setting a codec "
                        "also enables the autotune `compress` arm")
    p.add_argument("--topk-frac", dest="topk_frac", type=float,
                   default=None,
                   help="fraction of elements top-k compression keeps, in "
                        "(0, 1] (HVD_COMPRESS_TOPK_FRAC, default 0.01): "
                        "wire bytes scale with k = max(1, round(frac*n)) "
                        "per rank; only meaningful with --compression topk")
    p.add_argument("--alltoall", dest="alltoall",
                   choices=["auto", "basic"], default=None,
                   help="alltoallv routing (HVD_ALLTOALL): auto (the "
                        "default) rides the intra-host shm plane for "
                        "same-host members and the io_uring SG linked-wave "
                        "path for pairwise chunks above the zero-copy "
                        "threshold; basic is the kill switch — pairwise "
                        "full-duplex TCP only, both tier counters stay 0")
    p.add_argument("--alltoall-compress", dest="alltoall_compress",
                   type=int, choices=[0, 1], default=None,
                   help="int8 expert-dispatch wire for f32 alltoallv "
                        "(HVD_ALLTOALL_COMPRESS): 1 ships each per-peer "
                        "chunk as a 4-byte f32 scale + int8 payload "
                        "(>= 3.5x fewer wire bytes) when the int8 codec "
                        "is live (--compression int8); inert without it. "
                        "0 (the default) keeps alltoallv bit-exact")
    p.add_argument("--ep-capacity-factor", dest="ep_capacity_factor",
                   type=float, default=None,
                   help="expert-parallel router capacity factor "
                        "(HVD_EP_CAPACITY_FACTOR, default 1.25): "
                        "per-expert buffer slots = factor * tokens / "
                        "experts for moe_dispatch_combine when no "
                        "explicit capacity is passed; overflow tokens "
                        "are dropped and counted in hvd.ep_stats()")
    p.add_argument("--pipeline-schedule", dest="pipeline_schedule",
                   default=None,
                   help="pipeline-parallel microbatch schedule for the "
                        "JAX pipeline layer (HVD_PIPE_SCHEDULE): gpipe "
                        "(the default), 1f1b (fused forward/backward "
                        "scan, O(S) activation residency), "
                        "interleaved[:V] (V virtual stage slices per "
                        "device), or zb (best-effort ZB-H1 backward "
                        "split; counted fallback to 1f1b). See "
                        "docs/perf_tuning.md section 'Pipeline "
                        "schedules'")
    p.add_argument("--reduce-threads", dest="reduce_threads", type=int,
                   default=None,
                   help="reduce worker-pool lanes (HVD_REDUCE_THREADS): 1 "
                        "runs reductions inline, N>1 shards large "
                        "reductions across N-1 workers plus the caller")
    p.add_argument("--wire", dest="wire",
                   choices=["auto", "uring", "zerocopy", "basic"],
                   default=None,
                   help="cross-host wire tier (HVD_WIRE): auto probes the "
                        "best supported one at init (uring > zerocopy > "
                        "basic) and the mesh agrees on the minimum across "
                        "ranks; uring batches the hot path through "
                        "io_uring, zerocopy sends large buffers with "
                        "MSG_ZEROCOPY, basic is the legacy "
                        "poll/sendmsg/readv path")
    p.add_argument("--wire-zc-threshold", dest="wire_zc_threshold",
                   type=int, default=None,
                   help="min payload bytes sent with MSG_ZEROCOPY on the "
                        "zerocopy tier (HVD_WIRE_ZC_THRESHOLD, default "
                        "16384): page pinning beats copying only for "
                        "large buffers")
    p.add_argument("--numa", dest="numa", type=int, choices=[0, 1],
                   default=None,
                   help="NUMA placement (HVD_NUMA): 1 pins reduce-pool "
                        "lanes round-robin across nodes and mbinds shm "
                        "segments to their owner's node, 0 leaves "
                        "placement to the scheduler; unset auto-enables "
                        "on multi-node boxes")
    p.add_argument("--timeline-filename", dest="timeline_filename")
    p.add_argument("--timeline-mark-cycles", dest="timeline_mark_cycles",
                   action="store_true", default=None)
    p.add_argument("--no-stall-check", dest="no_stall_check",
                   action="store_true")
    p.add_argument("--stall-check-warning-time-seconds",
                   dest="stall_check_warning_time_seconds", type=int,
                   default=None)
    p.add_argument("--stall-check-shutdown-time-seconds",
                   dest="stall_check_shutdown_time_seconds", type=int,
                   default=None)
    p.add_argument("--autotune", action="store_true", default=None)
    p.add_argument("--autotune-log-file", dest="autotune_log_file")
    p.add_argument("--autotune-profile-dir", dest="autotune_profile_dir",
                   help="directory for persisted workload-keyed tuning "
                        "profiles (HVD_AUTOTUNE_PROFILE_DIR): on "
                        "convergence the coordinator writes the winning "
                        "configuration keyed by workload signature; a "
                        "later identical job adopts it with zero sweep "
                        "samples, a near-miss seeds the search priors. "
                        "Unset = profiles off (v1-identical search, no "
                        "filesystem access)")
    p.add_argument("--log-level", dest="log_level",
                   choices=["trace", "debug", "info", "warn", "error"])
    p.add_argument("--metrics", dest="metrics", action="store_true",
                   default=None,
                   help="enable the observability metrics registry "
                        "(HVD_METRICS; docs/observability.md)")
    p.add_argument("--metrics-port", dest="metrics_port", type=int,
                   default=None,
                   help="serve per-worker Prometheus /metrics on this port "
                        "(HVD_METRICS_PORT; rank-offset per local rank)")
    # elastic
    p.add_argument("--min-np", dest="min_np", type=int, default=None)
    p.add_argument("--max-np", dest="max_np", type=int, default=None)
    p.add_argument("--host-discovery-script",
                   dest="host_discovery_script", default=None)
    p.add_argument("--blacklist-cooldown-range", nargs=2, type=float,
                   default=None, help="elastic host blacklist cooldown "
                   "min/max seconds")
    p.add_argument("--hot-spares", dest="hot_spares", type=int,
                   default=None,
                   help="elastic: keep N pre-warmed rankless workers "
                        "parked so an eviction is repaired by promotion "
                        "instead of a cold spawn (docs/elastic.md)")
    p.add_argument("--peer-timeout-ms", dest="peer_timeout_ms", type=int,
                   default=None,
                   help="control-plane liveness heartbeat deadline in ms "
                        "(HVD_PEER_TIMEOUT_MS; 0 disables eviction — "
                        "docs/elastic.md)")
    # serving plane (docs/serving.md)
    p.add_argument("--serve-page-size", dest="serve_page_size", type=int,
                   default=None,
                   help="serving: KV-cache page size in token slots "
                        "(HVD_SERVE_PAGE_SIZE; docs/serving.md)")
    p.add_argument("--serve-kv-pages", dest="serve_kv_pages", type=int,
                   default=None,
                   help="serving: total KV-cache pages per replica, page 0 "
                        "reserved (HVD_SERVE_KV_PAGES)")
    p.add_argument("--serve-max-batch", dest="serve_max_batch", type=int,
                   default=None,
                   help="serving: decode-batch slots per replica "
                        "(HVD_SERVE_MAX_BATCH)")
    p.add_argument("--serve-mode", dest="serve_mode", default=None,
                   choices=["continuous", "static"],
                   help="serving: continuous batching, or the static "
                        "baseline that drains the whole batch before "
                        "admitting (HVD_SERVE_MODE)")
    p.add_argument("--serve-autoscale", dest="serve_autoscale",
                   action="store_true", default=None,
                   help="serving: let the elastic driver resize the "
                        "active set from /ctl/serve_load queue-depth "
                        "reports (HVD_SERVE_AUTOSCALE; scale-up promotes "
                        "hot spares, scale-down parks them)")
    p.add_argument("--serve-autoscale-high", dest="serve_autoscale_high",
                   type=int, default=None,
                   help="serving: queue depth above which the autoscaler "
                        "wants another rank (HVD_SERVE_AUTOSCALE_HIGH; "
                        "hysteresis band bottom is fixed at depth<=1)")
    p.add_argument("--serve-prefix-cache", dest="serve_prefix_cache",
                   type=int, choices=[0, 1], default=None,
                   help="serving: radix-tree shared-prefix KV reuse — "
                        "identical page-aligned prompt prefixes share "
                        "physical pages and skip their prefill "
                        "(HVD_SERVE_PREFIX_CACHE; default 1, 0 restores "
                        "the uncached path — docs/serving.md)")
    p.add_argument("--serve-spec-tokens", dest="serve_spec_tokens",
                   type=int, default=None,
                   help="serving: speculative-decoding draft length k — "
                        "each step drafts k tokens and scores them in one "
                        "batched target pass, emitting 1..k+1 tokens "
                        "bit-identical to greedy (HVD_SERVE_SPEC_TOKENS; "
                        "default 0 = off — docs/serving.md)")
    # state plane (docs/checkpoint.md)
    p.add_argument("--ckpt-dir", dest="ckpt_dir", default=None,
                   help="checkpoint: default directory for "
                        "hvd.checkpoint.save/restore when the call "
                        "passes none (HVD_CKPT_DIR; docs/checkpoint.md)")
    p.add_argument("--ckpt-async", dest="ckpt_async",
                   action="store_true", default=None,
                   help="checkpoint: commit saves on the background "
                        "writer thread — the step only pays the "
                        "device-to-host snapshot stall (HVD_CKPT_ASYNC; "
                        "must agree across ranks)")
    p.add_argument("--check-build", action="store_true",
                   help="print framework/native-layer availability and "
                        "exit (reference: horovodrun --check-build)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command")
    args = p.parse_args(argv)
    if args.config_file:
        config_parser.apply_config_file(args, args.config_file)
    if args.no_stall_check:
        args.stall_check_warning_time_seconds = 0
        args.stall_check_shutdown_time_seconds = 0
    if args.disable_cache:
        args.cache_capacity = 0
    if not args.command and not args.check_build:
        p.error("no training command given")
    return args


def check_build():
    """`tpurun --check-build` (reference: horovodrun --check-build):
    which frameworks import, which native layers are present."""
    import importlib.util

    def have(mod):
        return importlib.util.find_spec(mod) is not None

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib = os.path.join(pkg, "lib")
    mark = lambda b: "[X]" if b else "[ ]"  # noqa: E731
    print("horovod_tpu build:")
    print("  Frameworks:")
    for label, mod in (("JAX", "jax"), ("TensorFlow", "tensorflow"),
                       ("PyTorch", "torch"), ("Keras", "tensorflow"),
                       ("MXNet", "mxnet")):
        print(f"    {mark(have(mod))} {label}")
    print("  Native layers:")
    print(f"    {mark(os.path.exists(os.path.join(lib, 'libhvd_tpu.so')))}"
          f" core runtime (libhvd_tpu.so)")
    print(f"    {mark(os.path.exists(os.path.join(lib, 'libhvd_tf_ops.so')))}"
          f" TF custom ops (libhvd_tf_ops.so)")
    print(f"    {mark(os.path.exists(os.path.join(lib, 'libhvd_tf_xla_ops.so')))}"
          f" TF in-XLA-graph ops (libhvd_tf_xla_ops.so)")
    # Cheap artifact probe only — calling native_ext.lib() here would
    # JIT-compile the extension (minutes, under the exclusive build
    # lock) just to print a checkmark.
    import glob
    import importlib.util

    # Load native_ext.py by file path: its top level is os/sys-only, and
    # going through the `horovod_tpu.torch` package would import torch
    # itself just to print a checkmark. The path format still has exactly
    # one definition (native_ext.jit_build_dir — ADVICE r4).
    _ne_spec = importlib.util.spec_from_file_location(
        "_hvd_native_ext_paths",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "torch", "native_ext.py"))
    _ne = importlib.util.module_from_spec(_ne_spec)
    _ne_spec.loader.exec_module(_ne)
    torch_ext = bool(glob.glob(os.path.join(_ne.jit_build_dir(),
                                            "hvd_torch_ops*")))
    print(f"    {mark(torch_ext)} torch extension (hvd_torch_ops; "
          f"JIT-built on first use when unmarked)")
    print("  Data planes:")
    print("    [X] in-jit XLA collectives over the device mesh (ICI)")
    print("    [X] fused TCP ring (host/DCN) + hierarchical compose")
    print("    [ ] MPI / NCCL / Gloo — not used by design "
          "(docs/migrating.md)")
    return 0


def _resolve_hosts(args):
    if args.hosts and args.hostfile:
        raise ValueError("use either -H or --hostfile, not both")
    if args.hostfile:
        hs = hosts_mod.parse_hostfile(args.hostfile)
    elif args.hosts:
        hs = hosts_mod.parse_hosts(args.hosts)
    else:
        from . import lsf

        if lsf.in_lsf():
            # bsub allocation: hosts/slots come from the scheduler env
            # (reference: horovodrun's LSF auto-detection, runner/util/
            # lsf.py).
            hs = lsf.host_slots()
            if args.verbose:
                print(f"tpurun: LSF allocation detected: "
                      f"{','.join(f'{h.hostname}:{h.slots}' for h in hs)}",
                      file=sys.stderr)
        else:
            hs = [hosts_mod.HostInfo("localhost", args.np or 1)]
    return hs


def get_remote_command(slot, command, env, ssh_port=None, stdin_env=(),
                       remote_shell=None):
    """Assemble the per-slot remote command (reference: gloo_run.py
    `get_remote_command` — env exported inline, command exec'd on host).

    Variables named in ``stdin_env`` are NOT placed on the command line
    (argv is world-readable via ps on both hosts — secrets must never ride
    it); the remote shell reads one line per variable from stdin instead,
    and the spawner writes the values there (see ElasticDriver._spawn).

    ``remote_shell="blaunch"`` uses LSF's in-allocation remote-execution
    tool instead of ssh (reference: the LSF/jsrun launch path). blaunch
    gives the remote task the CALLER's environment (LSF's res propagates
    it, like lsrun) but no stdin forwarding guarantee — so the
    ``stdin_env`` variables still stay off argv, and the spawner exports
    them into its own environment instead of writing stdin (see
    _run_static / ElasticDriver._spawn).
    """
    env = {k: v for k, v in env.items() if k not in stdin_env}
    exports = " ".join(f"{k}={shlex.quote(str(v))}"
                       for k, v in sorted(env.items()))
    reads = "" if remote_shell == "blaunch" else \
        "".join(f"read -r {k} && export {k} && "
                for k in sorted(stdin_env))
    inner = f"cd {shlex.quote(os.getcwd())} > /dev/null 2>&1 ; " \
            f"{reads}env {exports} " \
            f"{' '.join(shlex.quote(c) for c in command)}"
    if remote_shell == "blaunch":
        # blaunch offers no port option; it rides LSF's own daemons.
        return f"blaunch {slot.hostname} {shlex.quote(inner)}"
    port = f"-p {ssh_port} " if ssh_port else ""
    return f"ssh -o PasswordAuthentication=no -o StrictHostKeyChecking=no " \
           f"{port}{slot.hostname} {shlex.quote(inner)}"


def spawn_remote(cmd, secret, remote_shell=None):
    """Spawn an assembled remote command with the secret-delivery protocol
    matching the shell: ssh reads HVD_RENDEZVOUS_SECRET from stdin (the
    command carries a `read -r` prefix); blaunch propagates the caller's
    environment to the remote task (no stdin guarantee), so the secret
    rides the spawn env. Either way it never touches argv. One
    implementation shared by the static launcher and ElasticDriver."""
    import subprocess

    spawn_env = dict(os.environ)
    if remote_shell == "blaunch":
        spawn_env["HVD_RENDEZVOUS_SECRET"] = secret
        return safe_exec(["/bin/sh", "-c", cmd], env=spawn_env)
    p = safe_exec(["/bin/sh", "-c", cmd], env=spawn_env,
                  stdin=subprocess.PIPE)
    util.send_stdin_line(p, secret.encode())
    return p


def _slot_extra_env(args):
    env = config_parser.args_to_env(args)
    if args.verbose:
        env.setdefault("HVD_LOG_LEVEL", "debug")
    return env


def _run_static(args):
    hs = _resolve_hosts(args)
    np_ = args.np or sum(h.slots for h in hs)
    slots = hosts_mod.get_host_assignments(hs, np_)
    extra = _slot_extra_env(args)

    any_remote = any(not hosts_mod.is_local(s.hostname) for s in slots)
    rdv = None
    if any_remote:
        # Driver/task services (reference: runner/driver/driver_service.py
        # + task_service.py): the launcher hosts an HMAC-signed KV store;
        # the job's rank 0 probes real free ports ON ITS OWN HOST for the
        # controller and jax coordinator and registers them; every rank
        # reads the registrations (runner/network.py). No port on a remote
        # host is ever guessed from here.
        from .network import NEGOTIATE
        from .program import host_negotiation_kv

        remote = [s.hostname for s in slots
                  if not hosts_mod.is_local(s.hostname)]
        rdv, extra = host_negotiation_kv(
            "svc", remote, extra_env=extra,
            probe_port=args.ssh_port or 22)
        ctrl = jax_coord = NEGOTIATE
    else:
        # Single-host job: the launcher IS rank 0's host, so probing here
        # is probing the right machine.
        ctrl = f"127.0.0.1:{find_free_port()}"
        jax_coord = f"127.0.0.1:{find_free_port()}"

    procs = []
    try:
        for s in slots:
            env = slot_env(s.rank, s.size, s.local_rank, s.local_size,
                           s.cross_rank, s.cross_size,
                           controller_addr=ctrl, jax_coord_addr=jax_coord,
                           extra_env=extra)
            # Pin the chip BEFORE libtpu initializes; harmless off-TPU.
            maybe_bind_tpu_chip(env, s.local_rank, s.local_size)
            if hosts_mod.is_local(s.hostname):
                procs.append(safe_exec(list(args.command), env=env))
            else:
                cmd = get_remote_command(s, list(args.command), {
                    k: v for k, v in env.items()
                    if k.startswith(("HVD_", "PYTHONPATH", "PATH", "TPU_",
                                     "CLOUD_TPU_"))
                }, args.ssh_port, stdin_env=("HVD_RENDEZVOUS_SECRET",),
                    remote_shell=args.remote_shell)
                procs.append(spawn_remote(
                    cmd, env["HVD_RENDEZVOUS_SECRET"],
                    remote_shell=args.remote_shell))
        return _wait_all(procs, verbose=args.verbose)
    finally:
        for p in procs:
            terminate(p)
        if rdv is not None:
            rdv.stop()


def _wait_all(procs, verbose=False):
    import time
    codes = [None] * len(procs)
    while any(c is None for c in codes):
        for i, p in enumerate(procs):
            if codes[i] is None:
                codes[i] = p.poll()
                if codes[i] not in (None, 0):
                    if verbose:
                        print(f"rank process {i} exited with {codes[i]}; "
                              f"terminating job", file=sys.stderr)
                    for q in procs:
                        terminate(q)
        time.sleep(0.05)
    bad = [c for c in codes if c != 0]
    return 0 if not bad else (bad[0] if bad[0] > 0 else 1)


def run_commandline(argv=None):
    args = parse_args(argv)
    if args.check_build:
        return check_build()
    from . import lsf

    if args.remote_shell is None and lsf.in_lsf():
        # In-allocation remote shell, regardless of whether hosts come
        # from the scheduler env or an explicit -H/--hostfile subset
        # (allocation nodes commonly refuse direct ssh).
        args.remote_shell = "blaunch"
    if args.min_np is not None or args.max_np is not None \
            or args.host_discovery_script:
        from .elastic.driver import run_elastic
        return run_elastic(args)
    return _run_static(args)


def run(fn=None, np=1, hosts=None, command=None, **kwargs):
    """Programmatic API (reference: horovod.run()). Either a shell
    `command` list, or via tpurun CLI args."""
    argv = ["-np", str(np)]
    if hosts:
        argv += ["-H", hosts]
    for k, v in kwargs.items():
        argv.append("--" + k.replace("_", "-"))
        if v is not True:
            argv.append(str(v))
    argv += list(command)
    return run_commandline(argv)


def main():
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
