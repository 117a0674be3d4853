"""``hvdrun`` — the launcher CLI (reference ``horovodrun``).

Reference: ``horovod/runner/launch.py`` (``parse_args:212``,
``_run_static:484``, ``run_commandline:715``).  Maps the same surface
onto the TPU runtime: host/hostfile parsing, config-file → env plumbing,
per-slot env contract (``gloo_context.cc:47-55``), process fan-out with
fail-fast teardown, and the ``jax.distributed`` coordinator address in
place of the gloo rendezvous server.

Usage::

    python -m horovod_tpu.runner.launch -np 4 python train.py
    python -m horovod_tpu.runner.launch -np 4 -H h1:2,h2:2 python train.py
    python -m horovod_tpu.runner.launch -np 2 --min-np 2 --max-np 4 \
        --host-discovery-script ./discover.sh python train.py
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import socket
import sys
import threading
from typing import Dict, List, Optional

from horovod_tpu.runner import config_parser, safe_shell_exec
from horovod_tpu.runner.hosts import (
    HostInfo,
    SlotInfo,
    get_host_assignments,
    parse_hostfile,
    parse_hosts,
)

_LOCAL_NAMES = ("localhost", "127.0.0.1", "::1")


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu distributed job.")
    p.add_argument("-v", "--version", action="store_true")
    p.add_argument("-np", "--num-proc", type=int, dest="np",
                   help="total number of worker processes")
    p.add_argument("-H", "--hosts", dest="hosts",
                   help='host list "h1:slots,h2:slots"; default localhost')
    p.add_argument("--hostfile", dest="hostfile",
                   help="file with one 'host slots=N' per line")
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("-i", "--ssh-identity-file", dest="ssh_identity_file",
                   help="ssh private key for remote worker launch")
    p.add_argument("--gloo", action="store_true", dest="use_gloo",
                   help="use the built-in launcher fan-out (the default; "
                        "accepted for reference CLI compatibility)")
    p.add_argument("--mpi", action="store_true", dest="use_mpi",
                   help="launch through mpirun (workers read identity "
                        "from the OMPI/PMIx env)")
    p.add_argument("--jsrun", action="store_true",
                   help="launch through jsrun with an ERF rankfile "
                        "(LSF clusters)")
    p.add_argument("--mpi-args", dest="mpi_args",
                   help="extra arguments appended to mpirun")
    p.add_argument("--network-interface", dest="nics",
                   help="comma-separated interfaces to restrict control "
                        "and data traffic to (narrows NIC discovery and "
                        "pins GLOO_SOCKET_IFNAME)")
    p.add_argument("--start-timeout", type=int, default=30)
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--output-filename", dest="output_filename",
                   help="per-rank stdout/stderr directory")
    p.add_argument("--config-file", dest="config_file")
    p.add_argument("--check-build", action="store_true",
                   help="print capability report and exit")

    # elastic (reference --min-np/--max-np/--host-discovery-script)
    p.add_argument("--min-np", type=int, dest="min_np")
    p.add_argument("--max-np", type=int, dest="max_np")
    p.add_argument("--slots-per-host", type=int, dest="slots",
                   help="default slot count for discovered hosts")
    p.add_argument("--host-discovery-script", dest="host_discovery_script")
    p.add_argument("--elastic-timeout", type=int, default=600)
    p.add_argument("--reset-limit", type=int, dest="reset_limit",
                   help="stop after this many elastic resets (reference "
                        "--reset-limit)")

    # knobs → env (reference config_parser flag set)
    p.add_argument("--fusion-threshold-mb", type=int,
                   dest="fusion_threshold_mb")
    p.add_argument("--cycle-time-ms", type=float, dest="cycle_time_ms")
    p.add_argument("--cache-capacity", type=int, dest="cache_capacity")
    p.add_argument("--disable-cache", action="store_const", const=True,
                   dest="disable_cache",
                   help="re-run launch-time discovery (NIC ring probe) "
                        "instead of using cached results (reference "
                        "--disable-cache), and disable the "
                        "response-cache analogue "
                        "(sets HOROVOD_CACHE_CAPACITY=0)")
    p.add_argument("--autotune", action="store_const", const=True,
                   dest="autotune")
    p.add_argument("--autotune-log-file", dest="autotune_log_file")
    p.add_argument("--autotune-warmup-samples", type=int,
                   dest="autotune_warmup_samples")
    p.add_argument("--autotune-steps-per-sample", type=int,
                   dest="autotune_steps_per_sample")
    p.add_argument("--autotune-bayes-opt-max-samples", type=int,
                   dest="autotune_bayes_opt_max_samples")
    p.add_argument("--autotune-gaussian-process-noise", type=float,
                   dest="autotune_gaussian_process_noise")
    p.add_argument("--log-level", dest="log_level",
                   choices=["trace", "debug", "info", "warning", "error",
                            "fatal"])
    p.add_argument("--log-hide-timestamp", action="store_const", const=True,
                   dest="log_hide_timestamp")
    p.add_argument("--timeline-filename", dest="timeline_filename")
    p.add_argument("--timeline-mark-cycles", action="store_const", const=True,
                   dest="timeline_mark_cycles")
    p.add_argument("--no-stall-check", action="store_const", const=True,
                   dest="no_stall_check")
    p.add_argument("--stall-warning-time-seconds", type=float,
                   dest="stall_warning_time_seconds")
    p.add_argument("--stall-shutdown-time-seconds", type=float,
                   dest="stall_shutdown_time_seconds")
    p.add_argument("--mesh-shape", dest="mesh_shape",
                   help='TPU mesh override "dcn,ici"')
    p.add_argument("--tpu-operations", dest="tpu_operations")

    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command")
    return p.parse_args(argv)


def _resolve_hosts(args) -> List[HostInfo]:
    if args.hosts and args.hostfile:
        raise ValueError("specify --hosts or --hostfile, not both")
    if args.hostfile:
        return parse_hostfile(args.hostfile)
    if args.hosts:
        return parse_hosts(args.hosts)
    from horovod_tpu.runner.cluster_env import detect_cluster_hosts

    detected = detect_cluster_hosts()
    if detected:   # LSF / TPU pod: host list with zero flags
        return detected
    return [HostInfo("localhost", args.np)]


def _is_local(hostname: str) -> bool:
    return hostname in _LOCAL_NAMES or hostname == socket.gethostname()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _coordinator_addr(hosts: List[HostInfo]) -> str:
    """jax.distributed coordinator on rank 0's host (the rendezvous-server
    analogue, reference ``gloo_run.py:213``)."""
    head = hosts[0].hostname
    if _is_local(head):
        head = "127.0.0.1"
    return f"{head}:{_free_port()}"


def _discover_coordinator_addr(hosts: List[HostInfo], args) -> str:
    """Multi-host coordinator addressing via the NIC ring probe: start a
    probe task on every host (ssh for remote ones), compute the
    interfaces every consecutive pair can route over, and address the
    coordinator by rank-0's IP on a common interface (reference
    ``get_common_interfaces`` + driver/task services,
    ``driver_service.py:124-193``) — instead of hoping ``hosts[0]``'s
    name resolves identically from every worker."""
    import subprocess

    from horovod_tpu.runner.driver_service import probe_common_and_rank0
    from horovod_tpu.runner.network import make_secret_key

    hostnames = [h.hostname for h in hosts]
    if all(_is_local(h) for h in hostnames):
        return _coordinator_addr(hosts)
    key = make_secret_key()
    requested_nics = set(args.nics.split(",")) if args.nics else None
    procs = []

    def spawn(host: str, index: int, driver_addrs: str) -> None:
        # the key rides the command line, not the env — ssh does not
        # forward env vars (the reference ships settings incl. the key
        # base64-encoded in the remote command, driver_service.py:49-84)
        cmd = [sys.executable, "-m", "horovod_tpu.runner.probe_task",
               driver_addrs, str(index), key]
        slot = SlotInfo(hostname=host, rank=index, local_rank=0,
                        cross_rank=0, size=len(hostnames), local_size=1,
                        cross_size=len(hostnames))
        full = build_worker_command(slot, cmd, args.ssh_port,
                                    args.ssh_identity_file)
        procs.append(subprocess.Popen(full,
                                      stdout=subprocess.DEVNULL,
                                      stderr=subprocess.DEVNULL))

    try:
        # repeated launches against one host set skip the ssh+probe
        # round trip via the on-disk TTL cache (reference
        # runner/util/cache.py; --disable-cache forces a fresh probe)
        cache = None
        if not getattr(args, "disable_cache", None):
            from horovod_tpu.runner.cache import DiscoveryCache

            cache = DiscoveryCache()
        common, rank0_ips = probe_common_and_rank0(
            hostnames, spawn, key, cache=cache,
            validate_port=args.ssh_port or 22)
        if requested_nics is not None:
            # --network-interface: the user's list wins, but the probe
            # still supplies rank-0's IP on that interface (the launcher
            # cannot know it otherwise) and fails loudly if the requested
            # interface is not mutually routable
            narrowed = [i for i in common if i in requested_nics]
            if not narrowed:
                raise RuntimeError(
                    f"--network-interface {args.nics} matches none of "
                    f"the mutually-routable interfaces {common}")
            common = narrowed
        iface = next(i for i in common if i in rank0_ips)
        ip = rank0_ips[iface]
        if args.verbose:
            print(f"[launcher] common interfaces: {common}; coordinator "
                  f"on {ip}", file=sys.stderr)
        return f"{ip}:{_free_port()}"
    finally:
        # reap without masking the primary error: stragglers get
        # terminated, then killed — never re-raise from cleanup
        for p in procs:
            try:
                p.wait(timeout=5)
            except Exception:
                p.terminate()
                try:
                    p.wait(timeout=2)
                except Exception:
                    p.kill()


def build_worker_command(slot: SlotInfo, command: List[str],
                         ssh_port: Optional[int] = None,
                         ssh_identity_file: Optional[str] = None
                         ) -> List[str]:
    """Local slots exec directly; remote slots go through ssh (reference
    ``gloo_run.py:113-180`` ssh/exec split).  Remote args are
    ``shlex.quote``d — naive single-quoting corrupts any argument that
    itself contains a quote."""
    import shlex

    if _is_local(slot.hostname):
        return list(command)
    ssh = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if ssh_identity_file:
        ssh += ["-i", ssh_identity_file]
    if ssh_port:
        # options must precede the destination — ssh stops parsing at it
        ssh += ["-p", str(ssh_port)]
    ssh.append(slot.hostname)
    return ssh + [" ".join(shlex.quote(c) for c in command)]


SSH_CHECK_TIMEOUT_S = 30


def check_all_hosts_ssh_successful(hostnames: List[str],
                                   ssh_port: Optional[int] = None,
                                   ssh_identity_file: Optional[str] = None,
                                   runner=None) -> None:
    """Verify every remote host is ssh-reachable before fan-out
    (reference ``_check_all_hosts_ssh_successful``, ``launch.py:55-104``)
    — one bad host should fail the launch immediately with a named
    culprit, not hang N-1 healthy workers.  ``runner`` is injectable for
    tests; defaults to running the composed ssh command."""
    import shlex
    import subprocess

    def default_runner(cmd: List[str]) -> int:
        try:
            return subprocess.run(
                cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                timeout=SSH_CHECK_TIMEOUT_S).returncode
        except subprocess.TimeoutExpired:
            return 255

    run = runner or default_runner
    remote = [h for h in hostnames if not _is_local(h)]
    results: Dict[str, int] = {}
    lock = threading.Lock()

    def check(host: str) -> None:
        cmd = ["ssh", "-o", "BatchMode=yes",
               "-o", "StrictHostKeyChecking=no"]
        if ssh_identity_file:
            cmd += ["-i", ssh_identity_file]
        if ssh_port:
            cmd += ["-p", str(ssh_port)]
        cmd += [host, shlex.quote("true")]
        rc = run(cmd)
        with lock:
            results[host] = rc

    threads = [threading.Thread(target=check, args=(h,), daemon=True)
               for h in remote]
    for t in threads:
        t.start()
    for t in threads:
        t.join(SSH_CHECK_TIMEOUT_S + 5)
    failed = sorted(h for h, rc in results.items() if rc != 0)
    failed += sorted(h for h in remote if h not in results)
    if failed:
        raise RuntimeError(
            "SSH was unable to connect to hosts: {}\n"
            "Check that every host is reachable, accepts passwordless "
            "ssh, and that --ssh-port matches.".format(", ".join(failed)))


#: PCI ids of TPU chips (vendor Google; device v3, plc, v4, v5p, v5e,
#: v6e, 7x) — the table JAX itself tells a TPU host by
#: (``jax/_src/hardware_utils.py``).  The vendor alone will not do: a
#: cloud VM's virtual NIC carries it too.
_TPU_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset(
    ("0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"))


def host_has_tpu(sysfs: str = "/sys/bus/pci/devices") -> bool:
    """Whether a TPU chip sits on this host's PCI bus, read from sysfs —
    the launcher parent must not ask JAX, which would take the chips it
    is about to hand to a worker.  Presence, not a count: a machine
    that is given one chip of a four-chip board still lists all four
    functions, and a device node (``/dev/vfio/<n>``) names no vendor."""
    def read(path):
        try:
            with open(path) as f:
                return f.read().strip()
        except OSError:
            return ""

    return any(
        read(vendor) == _TPU_PCI_VENDOR
        and read(os.path.join(os.path.dirname(vendor), "device"))
        in _TPU_PCI_DEVICES
        for vendor in glob.glob(os.path.join(sysfs, "*", "vendor")))


def check_one_process_per_tpu_host(local_slots: int,
                                   env: Dict[str, str]) -> None:
    """Refuse, before any worker starts, to put several workers on a
    host that holds TPU chips.

    A chip belongs to one process at a time and a JAX process opens
    every chip of its host, so ``-np 4`` on a four-chip host is four
    processes contending for the same four chips — a clash or a hang
    inside libtpu.  One process drives all local chips; nothing in a
    worker's environment confines it to one.  Workers held off the TPU
    (``JAX_PLATFORMS`` without ``tpu``) are not affected."""
    platforms = env.get("JAX_PLATFORMS", "")
    if local_slots <= 1 or (platforms and "tpu" not in platforms.split(",")):
        return
    if host_has_tpu():
        raise SystemExit(
            f"hvdrun: refusing to start {local_slots} worker processes on "
            f"this host, which holds TPU chips: a chip belongs to one "
            f"process at a time and one process drives all local chips "
            f"(hvd.size() counts them), so start one process per host — "
            f"`hvdrun -np 1 ...` here, `-H h1:1,h2:1` across hosts.  For "
            f"CPU-only workers set JAX_PLATFORMS=cpu.")


def build_worker_env(slot: SlotInfo, base_env: Dict[str, str],
                     coordinator_addr: str) -> Dict[str, str]:
    env = dict(base_env)
    env.update(slot.to_env())
    env["HOROVOD_COORDINATOR_ADDR"] = coordinator_addr
    # HOROVOD_RANK/SIZE name the *process* world for jax.distributed
    env["HOROVOD_CONTROLLER"] = "jax"
    return env


def _run_jsrun(args, hosts: List[HostInfo]) -> int:
    """LSF/jsrun launch: one jsrun command with an ERF rankfile places
    every rank; workers read identity from the PMIx env (reference
    ``run_controller`` jsrun branch, ``launch.py:632`` + ``js_run.py``)."""
    from horovod_tpu.runner import js_run

    env = config_parser.set_env_from_args(dict(os.environ), args)
    env["HOROVOD_COORDINATOR_ADDR"] = _coordinator_addr(hosts)
    env["HOROVOD_SIZE"] = str(args.np)
    return js_run.js_run(args, hosts, env)


def _run_mpi(args, hosts: List[HostInfo]) -> int:
    """mpirun launch: mpirun places the ranks; workers read identity
    from the OMPI/PMIx env (reference ``mpi_run.py``)."""
    from horovod_tpu.runner import mpi_run

    env = config_parser.set_env_from_args(dict(os.environ), args)
    env["HOROVOD_COORDINATOR_ADDR"] = _coordinator_addr(hosts)
    env["HOROVOD_SIZE"] = str(args.np)
    return mpi_run.mpi_run(args, hosts, env)


def _run_static(args) -> int:
    hosts = _resolve_hosts(args)
    if args.jsrun:
        return _run_jsrun(args, hosts)
    if args.use_mpi:
        return _run_mpi(args, hosts)
    check_all_hosts_ssh_successful([h.hostname for h in hosts],
                                   args.ssh_port, args.ssh_identity_file)
    assignments = get_host_assignments(hosts, args.np, args.np)
    base_env = config_parser.set_env_from_args(dict(os.environ), args)
    check_one_process_per_tpu_host(
        max((s.local_size for s in assignments if _is_local(s.hostname)),
            default=0), base_env)
    coordinator = _discover_coordinator_addr(hosts, args)

    if args.verbose:
        for s in assignments:
            print(f"[launcher] rank {s.rank} -> {s.hostname} "
                  f"(local {s.local_rank}/{s.local_size})", file=sys.stderr)

    failures: List[int] = []
    abort = threading.Event()
    threads = []
    out_dir = args.output_filename
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)

    def run_slot(slot: SlotInfo):
        cmd = build_worker_command(slot, args.command, args.ssh_port,
                                   args.ssh_identity_file)
        env = build_worker_env(slot, base_env, coordinator)
        stdout = stderr = None
        if out_dir:
            stdout = open(os.path.join(out_dir, f"rank.{slot.rank}.out"), "wb")
            stderr = open(os.path.join(out_dir, f"rank.{slot.rank}.err"), "wb")
        try:
            rc = safe_shell_exec.execute(cmd, env=env, stdout=stdout,
                                         stderr=stderr, events=[abort])
        finally:
            for f in (stdout, stderr):
                if f:
                    f.close()
        if rc != 0:
            failures.append(rc)
            abort.set()   # fail fast: kill the whole job (reference
            #               gloo_run kills all on any failure)

    for slot in assignments:
        t = threading.Thread(target=run_slot, args=(slot,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    return failures[0] if failures else 0


def _check_build() -> int:
    import horovod_tpu as hvd

    print("horovod_tpu v" + hvd.__version__)
    print("Available backends:")
    print(f"    [{'X' if hvd.xla_built() else ' '}] XLA")
    print(f"    [{'X' if hvd.tpu_available() else ' '}] TPU")
    print(f"    [{'X' if hvd.mpi_built() else ' '}] MPI")
    print(f"    [{'X' if hvd.gloo_built() else ' '}] Gloo")
    print(f"    [{'X' if hvd.nccl_built() else ' '}] NCCL")
    print(f"Eager data plane (HOROVOD_TPU_OPERATIONS): "
          f"{hvd.current_operations()}")
    return 0


def run_commandline(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.version:
        import horovod_tpu as hvd

        print(hvd.__version__)
        return 0
    if args.check_build:
        return _check_build()
    if args.config_file:
        config_parser.apply_config_defaults(
            args, config_parser.load_config_file(args.config_file))
    if not args.command:
        raise SystemExit("no training command given")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if args.np is None and not args.host_discovery_script:
        raise SystemExit("-np is required")

    elastic = bool(args.host_discovery_script or args.min_np or args.max_np)
    if elastic:
        from horovod_tpu.elastic.launch import run_elastic

        return run_elastic(args)
    return _run_static(args)


def main() -> None:
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
