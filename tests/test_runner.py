"""Launcher unit tests (reference ``test/test_run.py`` style: arg
parsing, env propagation, command construction asserted as strings,
single-process with no cluster) plus a real localhost ``run(fn)``
end-to-end (reference ``test_interactiverun.py``)."""

import os
import sys
import textwrap

import pytest

from horovod_tpu.runner import config_parser
from horovod_tpu.runner.hosts import (
    HostInfo,
    get_host_assignments,
    parse_hostfile,
    parse_hosts,
)
from horovod_tpu.runner.launch import (
    build_worker_command,
    build_worker_env,
    parse_args,
)


class TestHosts:
    def test_parse_hosts(self):
        hosts = parse_hosts("h1:2, h2:4,h3")
        assert [(h.hostname, h.slots) for h in hosts] == \
            [("h1", 2), ("h2", 4), ("h3", 1)]

    def test_parse_hostfile(self, tmp_path):
        f = tmp_path / "hostfile"
        f.write_text(textwrap.dedent("""\
            # comment
            h1 slots=2
            h2:4

            h3
        """))
        hosts = parse_hostfile(str(f))
        assert [(h.hostname, h.slots) for h in hosts] == \
            [("h1", 2), ("h2", 4), ("h3", 1)]

    def test_assignments_round_robin(self):
        slots = get_host_assignments(
            [HostInfo("h1", 2), HostInfo("h2", 2)], 4)
        assert [(s.hostname, s.rank, s.local_rank, s.cross_rank)
                for s in slots] == \
            [("h1", 0, 0, 0), ("h1", 1, 1, 0),
             ("h2", 2, 0, 1), ("h2", 3, 1, 1)]
        assert all(s.size == 4 and s.local_size == 2 and s.cross_size == 2
                   for s in slots)

    def test_assignments_insufficient(self):
        with pytest.raises(ValueError, match="slots"):
            get_host_assignments([HostInfo("h1", 1)], 4)

    def test_env_contract(self):
        slot = get_host_assignments([HostInfo("h1", 2)], 2)[1]
        env = slot.to_env()
        assert env["HOROVOD_RANK"] == "1"
        assert env["HOROVOD_SIZE"] == "2"
        assert env["HOROVOD_LOCAL_RANK"] == "1"
        assert env["HOROVOD_CROSS_SIZE"] == "1"


class TestLaunchCommand:
    def test_local_command_direct(self):
        slot = get_host_assignments([HostInfo("localhost", 1)], 1)[0]
        cmd = build_worker_command(slot, ["python", "train.py"])
        assert cmd == ["python", "train.py"]

    def test_remote_command_ssh(self):
        slot = get_host_assignments([HostInfo("worker-7", 1)], 1)[0]
        cmd = build_worker_command(slot, ["python", "train.py"],
                                   ssh_port=2222)
        assert cmd[0] == "ssh"
        assert "worker-7" in cmd
        assert "-p" in cmd and "2222" in cmd
        assert cmd[-1] == "python train.py"

    def test_remote_command_quotes_special_chars(self):
        """shlex-quoted remote args: embedded quotes and spaces must
        survive the ssh hop intact (reference uses shlex.quote in every
        remote command composition; round-1 naive single-quoting
        corrupted args containing quotes)."""
        import shlex

        slot = get_host_assignments([HostInfo("worker-7", 1)], 1)[0]
        tricky = ["python", "-c", "print('hello world')", "--flag=a b"]
        cmd = build_worker_command(slot, tricky)
        assert shlex.split(cmd[-1]) == tricky

    def test_ssh_reachability_check_names_bad_host(self):
        """Pre-fan-out reachability check fails fast, naming the culprit
        (reference _check_all_hosts_ssh_successful, launch.py:55-104)."""
        from horovod_tpu.runner.launch import check_all_hosts_ssh_successful

        calls = []

        def fake_runner(cmd):
            calls.append(cmd)
            return 255 if "badhost" in cmd else 0

        with pytest.raises(RuntimeError, match="badhost"):
            check_all_hosts_ssh_successful(
                ["localhost", "goodhost", "badhost"], runner=fake_runner)
        # localhost is skipped; both remote hosts probed over BatchMode ssh
        assert len(calls) == 2
        assert all(c[0] == "ssh" and "BatchMode=yes" in c[2] for c in calls)

    def test_ssh_reachability_all_good(self):
        from horovod_tpu.runner.launch import check_all_hosts_ssh_successful

        check_all_hosts_ssh_successful(["h1", "h2"], runner=lambda c: 0)

    def test_worker_env(self):
        slot = get_host_assignments([HostInfo("localhost", 2)], 2)[0]
        env = build_worker_env(slot, {"PATH": "/bin"}, "10.0.0.1:1234")
        assert env["HOROVOD_COORDINATOR_ADDR"] == "10.0.0.1:1234"
        assert env["HOROVOD_RANK"] == "0"
        assert env["PATH"] == "/bin"

    def test_several_workers_on_a_tpu_host_are_refused(self, monkeypatch):
        """A chip belongs to one process and a JAX process opens every
        chip of its host: more than one TPU worker a host is refused
        before any worker starts, not left to clash inside libtpu.  The
        host is faked — the sandbox has no chip."""
        from horovod_tpu.runner import launch

        monkeypatch.setattr(launch, "host_has_tpu", lambda: True)
        for env in ({}, {"JAX_PLATFORMS": "tpu,cpu"}):
            with pytest.raises(SystemExit, match="one process drives all"):
                launch.check_one_process_per_tpu_host(4, env)
        # one worker, or workers held off the TPU, are fine
        launch.check_one_process_per_tpu_host(1, {})
        launch.check_one_process_per_tpu_host(4, {"JAX_PLATFORMS": "cpu"})
        # and so is any slot count on a host without chips
        monkeypatch.setattr(launch, "host_has_tpu", lambda: False)
        launch.check_one_process_per_tpu_host(4, {})

    def test_a_tpu_host_is_told_by_pci_id_not_by_device_node(
            self, tmp_path):
        """Only a TPU's PCI id makes a TPU host: one whose vfio groups
        pass a NIC or GPU through, or whose virtual NIC carries
        Google's vendor id, holds no chip and is not refused."""
        from horovod_tpu.runner import launch

        def pci(slot, vendor, device):
            d = tmp_path / slot
            d.mkdir()
            (d / "vendor").write_text(vendor + "\n")
            (d / "device").write_text(device + "\n")

        pci("0000:00:04.0", "0x1ae0", "0x0042")     # virtual NIC
        pci("0000:00:05.0", "0x10de", "0x2330")     # a GPU behind vfio
        assert not launch.host_has_tpu(str(tmp_path))
        pci("0000:00:06.0", "0x1ae0", "0x0063")     # a v5e chip
        assert launch.host_has_tpu(str(tmp_path))
        assert not launch.host_has_tpu(str(tmp_path / "absent"))

    def test_refusal_happens_before_any_worker_starts(self, monkeypatch):
        from horovod_tpu.runner import launch

        started = []
        monkeypatch.setattr(launch, "host_has_tpu", lambda: True)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(launch.safe_shell_exec, "execute",
                            lambda *a, **k: started.append(a) or 0)
        with pytest.raises(SystemExit, match="refusing to start 4"):
            launch.run_commandline(["-np", "4", "python", "train.py"])
        assert started == []

    def test_launcher_parent_stays_off_the_backend(self):
        """Importing the launcher (static and elastic paths) must not
        initialise a JAX backend: the parent would hold the chips its
        worker needs."""
        import subprocess

        code = ("import horovod_tpu.runner.launch, "
                "horovod_tpu.elastic.launch; "
                "from jax._src import xla_bridge as xb; "
                "assert not xb._backends, list(xb._backends)")
        subprocess.run([sys.executable, "-c", code], check=True,
                       timeout=120)

    def test_parse_args_knobs(self):
        args = parse_args([
            "-np", "4", "-H", "h1:4", "--fusion-threshold-mb", "32",
            "--autotune", "--timeline-filename", "/tmp/t.json",
            "--", "python", "train.py"])
        assert args.np == 4 and args.hosts == "h1:4"
        env = config_parser.set_env_from_args({}, args)
        assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
        assert env["HOROVOD_AUTOTUNE"] == "1"
        assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"

    def test_config_file_defaults_cli_wins(self, tmp_path):
        cfg = tmp_path / "cfg.yaml"
        cfg.write_text(textwrap.dedent("""\
            fusion:
              threshold_mb: 16
              cycle_time_ms: 2.5
            timeline:
              filename: /tmp/from_config.json
        """))
        args = parse_args(["-np", "1", "--fusion-threshold-mb", "64",
                           "--config-file", str(cfg), "--", "true"])
        config_parser.apply_config_defaults(
            args, config_parser.load_config_file(str(cfg)))
        # CLI value survives; unset values filled from config
        assert args.fusion_threshold_mb == 64
        assert args.cycle_time_ms == 2.5
        assert args.timeline_filename == "/tmp/from_config.json"


class TestClusterEnv:
    def test_lsf_hosts(self, monkeypatch):
        from horovod_tpu.runner.cluster_env import LSFUtils, detect_cluster_hosts

        monkeypatch.setenv("LSB_JOBID", "1234")
        monkeypatch.setenv("LSB_MCPU_HOSTS", "batch1 1 node1 4 node2 4")
        assert LSFUtils.using_lsf()
        hosts = detect_cluster_hosts()
        assert [(h.hostname, h.slots) for h in hosts] == \
            [("node1", 4), ("node2", 4)]

    def test_tpu_pod_hosts(self, monkeypatch):
        from horovod_tpu.runner.cluster_env import detect_cluster_hosts

        monkeypatch.delenv("LSB_JOBID", raising=False)
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t0,t1,t2,t3")
        hosts = detect_cluster_hosts()
        assert [h.hostname for h in hosts] == ["t0", "t1", "t2", "t3"]

    def test_no_cluster(self, monkeypatch):
        from horovod_tpu.runner.cluster_env import detect_cluster_hosts

        monkeypatch.delenv("LSB_JOBID", raising=False)
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
        assert detect_cluster_hosts() is None

    def test_jsm_identity_pmix(self, monkeypatch):
        from horovod_tpu.runner.cluster_env import jsm_identity

        for v in ("PMIX_RANK", "PMIX_SIZE", "OMPI_COMM_WORLD_RANK",
                  "OMPI_COMM_WORLD_SIZE"):
            monkeypatch.delenv(v, raising=False)
        assert jsm_identity() is None
        monkeypatch.setenv("PMIX_RANK", "3")
        monkeypatch.setenv("PMIX_SIZE", "8")
        monkeypatch.setenv("PMIX_LOCAL_RANK", "1")
        monkeypatch.setenv("PMIX_LOCAL_SIZE", "4")
        assert jsm_identity() == {"rank": 3, "size": 8,
                                  "local_rank": 1, "local_size": 4}

    def test_jsm_identity_feeds_config(self, monkeypatch):
        from horovod_tpu.runtime.config import Config

        monkeypatch.delenv("HOROVOD_RANK", raising=False)
        monkeypatch.delenv("HOROVOD_SIZE", raising=False)
        monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "2")
        monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
        cfg = Config.from_env()
        assert cfg.rank == 2 and cfg.size == 4

    def test_exchange_env_knobs(self, monkeypatch):
        """HOROVOD_EXCHANGE_BUCKET_BYTES / HOROVOD_EXCHANGE_HIERARCHY
        feed the sharded-exchange defaults and count as user-fixed
        knobs (never autotuned over)."""
        from horovod_tpu.runtime.config import Config

        cfg = Config.from_env()
        assert cfg.exchange_bucket_bytes is None
        assert cfg.exchange_hierarchy == "auto"
        monkeypatch.setenv("HOROVOD_EXCHANGE_BUCKET_BYTES",
                           str(4 * 1024 * 1024))
        monkeypatch.setenv("HOROVOD_EXCHANGE_HIERARCHY", "two_level")
        cfg = Config.from_env()
        assert cfg.exchange_bucket_bytes == 4 * 1024 * 1024
        assert cfg.exchange_hierarchy == "two_level"
        assert "exchange_bucket_bytes" in cfg.fixed_knobs
        assert "exchange_hierarchy" in cfg.fixed_knobs


class TestJsRun:
    """jsrun command + ERF rankfile composed as strings, no LSF needed
    (reference test_run.py mpirun-command string assertions)."""

    def test_rankfile_format(self, tmp_path):
        from horovod_tpu.runner.js_run import generate_jsrun_rankfile

        rf = tmp_path / "rf.erf"
        generate_jsrun_rankfile(
            [HostInfo("host1", 2), HostInfo("host2", 2)], np=3,
            path=str(rf), cores_per_node=4, threads_per_core=2,
            accelerators_per_node=2)
        text = rf.read_text()
        assert "overlapping_rs: allow" in text
        assert "cpu_index_using: logical" in text
        # 4 cores x 2 threads / 2 accels = 4 cpus per slot
        assert "rank: 0: { hostname: host1; cpu: {0-3} ; gpu: * ; mem: * }" \
            in text
        assert "rank: 1: { hostname: host1; cpu: {4-7} ; gpu: * ; mem: * }" \
            in text
        # np=3 truncates host2 to one slot
        assert "rank: 2: { hostname: host2; cpu: {0-3} ; gpu: * ; mem: * }" \
            in text
        assert "rank: 3" not in text

    def test_rankfile_rejects_oversubscription(self, tmp_path):
        from horovod_tpu.runner.js_run import generate_jsrun_rankfile

        with pytest.raises(ValueError, match="exposes only"):
            generate_jsrun_rankfile(
                [HostInfo("h", 8)], np=8, path=str(tmp_path / "rf"),
                cores_per_node=4, threads_per_core=1,
                accelerators_per_node=4)

    def test_rankfile_rejects_too_few_slots(self, tmp_path):
        from horovod_tpu.runner.js_run import generate_jsrun_rankfile

        with pytest.raises(ValueError, match="too few slots"):
            generate_jsrun_rankfile(
                [HostInfo("h", 2)], np=4, path=str(tmp_path / "rf"),
                cores_per_node=4, threads_per_core=1,
                accelerators_per_node=2)

    def test_command_composition(self):
        from horovod_tpu.runner.js_run import js_run_command

        cmd = js_run_command(["python", "train.py"], "/tmp/rf.erf",
                             output_filename="/tmp/out")
        assert cmd == ["jsrun", "--erf_input", "/tmp/rf.erf",
                       "--stdio_stderr", "/tmp/out",
                       "--stdio_stdout", "/tmp/out",
                       "python", "train.py"]

    def test_jsrun_flag_parses(self):
        args = parse_args(["-np", "2", "--jsrun", "--", "python", "t.py"])
        assert args.jsrun


class TestMpiRun:
    """mpirun command composed as strings, no MPI needed (reference
    test_run.py mpirun-command string assertions)."""

    def test_command_composition(self):
        from horovod_tpu.runner.mpi_run import mpi_run_command

        env = {"HOROVOD_COORDINATOR_ADDR": "10.0.0.1:1234",
               "PYTHONPATH": "/x", "HOME": "/root", "GLOO_SOCKET_IFNAME":
               "eth0"}
        cmd = mpi_run_command(
            4, [HostInfo("h1", 2), HostInfo("h2", 2)],
            ["python", "train.py"], env,
            impl_flags=["-bind-to", "none", "-map-by", "slot"],
            nics="eth0", extra_mpi_args="--oversubscribe")
        s = " ".join(cmd)
        assert s.startswith("mpirun -bind-to none -map-by slot")
        assert "-np 4" in s and "-H h1:2,h2:2" in s
        assert "-mca btl_tcp_if_include eth0" in s
        assert "-x GLOO_SOCKET_IFNAME" in s
        assert "-x HOROVOD_COORDINATOR_ADDR" in s
        assert "-x PYTHONPATH" in s
        assert "-x HOME" not in s       # only the forwarding allowlist
        assert "--oversubscribe" in s
        assert s.endswith("python train.py")

    def test_mpich_command_composition(self):
        from horovod_tpu.runner.mpi_run import (
            mpi_implementation_flags,
            mpi_run_command,
        )

        env = {"HOROVOD_COORDINATOR_ADDR": "10.0.0.1:1234",
               "PYTHONPATH": "/x", "HOME": "/root"}
        cmd = mpi_run_command(
            4, [HostInfo("h1", 2), HostInfo("h2", 2)],
            ["python", "train.py"], env,
            impl_flags=mpi_implementation_flags(impl="mpich"),
            nics="eth0,eth1", impl="mpich")
        s = " ".join(cmd)
        # hydra spellings only: no OpenMPI MCA/-x/--tag-output args
        assert s.startswith("mpirun -bind-to none -map-by slot")
        assert "-mca" not in s and "--tag-output" not in s
        assert "-iface eth0" in s
        assert "-genvlist HOROVOD_COORDINATOR_ADDR,PYTHONPATH" in s
        assert "-x" not in s.split()
        assert s.endswith("python train.py")
        # hydra has no per-arg rsh passthrough: ssh options must fail
        # loudly, not silently dial default ssh settings
        import pytest as _pytest
        with _pytest.raises(ValueError, match="hydra"):
            mpi_run_command(
                4, [HostInfo("h1", 2), HostInfo("h2", 2)],
                ["python", "train.py"], env,
                impl_flags=mpi_implementation_flags(impl="mpich"),
                ssh_port=2222, impl="mpich")

    def test_implementation_detection(self, monkeypatch):
        import subprocess as sp

        from horovod_tpu.runner import mpi_run

        outputs = {
            "openmpi": "mpirun (Open MPI) 4.1.4",
            "spectrum": "mpirun (IBM Spectrum MPI) 10.3",
            "mpich": "HYDRA build details:\n    Version: 4.1",
        }
        for expect, version_text in outputs.items():
            monkeypatch.setattr(
                mpi_run.subprocess, "run",
                lambda *a, _out=version_text, **k: sp.CompletedProcess(
                    a, 0, stdout=_out, stderr=""))
            assert mpi_run.detect_mpi_implementation() == expect

    def test_unknown_implementation_rejected(self):
        from horovod_tpu.runner.mpi_run import mpi_implementation_flags

        with pytest.raises(RuntimeError, match="Unsupported MPI"):
            mpi_implementation_flags(impl="unknown")

    def test_mpich_identity_env(self, monkeypatch):
        from horovod_tpu.runner.cluster_env import jsm_identity

        for var in ("PMIX_RANK", "OMPI_COMM_WORLD_RANK", "PMI_RANK"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("PMI_RANK", "3")
        monkeypatch.setenv("PMI_SIZE", "8")
        monkeypatch.setenv("MPI_LOCALRANKID", "1")
        monkeypatch.setenv("MPI_LOCALNRANKS", "4")
        assert jsm_identity() == {
            "rank": 3, "size": 8, "local_rank": 1, "local_size": 4}

    def test_mpi_flag_without_mpirun_errors(self, monkeypatch):
        from horovod_tpu.runner import mpi_run
        from horovod_tpu.runner.launch import run_commandline

        monkeypatch.setattr(mpi_run.shutil, "which", lambda _: None)
        with pytest.raises(RuntimeError, match="does not find an installed"):
            run_commandline(["-np", "2", "--mpi", "--", "python", "t.py"])


class TestFlagParity:
    def test_reference_flags_accepted(self):
        args = parse_args([
            "-np", "2", "--disable-cache", "--network-interface", "eth0,lo",
            "-i", "/root/.ssh/key", "--slots-per-host", "4",
            "--reset-limit", "3", "--log-level", "debug",
            "--log-hide-timestamp", "--autotune-warmup-samples", "5",
            "--autotune-steps-per-sample", "20",
            "--autotune-bayes-opt-max-samples", "30",
            "--autotune-gaussian-process-noise", "0.5",
            "--gloo", "--", "python", "t.py"])
        assert args.disable_cache and args.nics == "eth0,lo"
        assert args.ssh_identity_file == "/root/.ssh/key"
        assert args.slots == 4 and args.reset_limit == 3
        env = config_parser.set_env_from_args({}, args)
        assert env["HOROVOD_CACHE_CAPACITY"] == "0"   # --disable-cache
        assert env["GLOO_SOCKET_IFNAME"] == "eth0,lo"
        assert env["HOROVOD_LOG_LEVEL"] == "debug"
        assert env["HOROVOD_LOG_HIDE_TIME"] == "1"
        assert env["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] == "5"
        assert env["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] == "30"
        assert env["HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"] == "0.5"

    def test_ssh_identity_in_commands(self):
        from horovod_tpu.runner.launch import (
            build_worker_command,
            check_all_hosts_ssh_successful,
        )

        slot = get_host_assignments([HostInfo("w1", 1)], 1)[0]
        cmd = build_worker_command(slot, ["true"],
                                   ssh_identity_file="/k.pem")
        assert "-i" in cmd and "/k.pem" in cmd
        seen = []
        check_all_hosts_ssh_successful(
            ["w1"], ssh_identity_file="/k.pem",
            runner=lambda c: seen.append(c) or 0)
        assert "-i" in seen[0] and "/k.pem" in seen[0]


class TestNicDiscovery:
    """Ring-probe NIC discovery exercised for real on localhost
    (reference driver/task services, driver_service.py:124-193)."""

    def test_local_interfaces_nonempty(self):
        from horovod_tpu.runner.driver_service import (
            local_interface_addresses,
        )

        ifaces = local_interface_addresses()
        assert ifaces, "at least loopback must be discoverable"
        assert any(ip.startswith("127.") for ip in ifaces.values())

    def test_ring_probe_finds_common_interfaces(self):
        import threading

        from horovod_tpu.runner.driver_service import (
            discover_common_interfaces,
            run_probe_task,
        )

        def spawn(host, index, driver_addr):
            threading.Thread(target=run_probe_task,
                             args=(driver_addr, index, "k"),
                             daemon=True).start()

        common, driver = discover_common_interfaces(
            ["localhost", "localhost", "localhost"], spawn,
            secret_key="k", timeout_s=30)
        try:
            assert common, "localhost tasks must share an interface"
            rank0 = driver.task_address(0)
            assert any(i in rank0 for i in common)
        finally:
            driver.shutdown()

    def test_probe_cache_warm_hit_skips_probe(self, tmp_path):
        """TTL-cached discovery (reference runner/util/cache.py): the
        second launch against the same host set consults the on-disk
        cache and spawns NO probe tasks; an expired entry re-probes."""
        import threading

        from horovod_tpu.runner.cache import DiscoveryCache
        from horovod_tpu.runner.driver_service import (
            probe_common_and_rank0,
            run_probe_task,
        )

        spawns = []

        def spawn(host, index, driver_addr):
            spawns.append(index)
            threading.Thread(target=run_probe_task,
                             args=(driver_addr, index, "k"),
                             daemon=True).start()

        cache = DiscoveryCache(path=str(tmp_path / "cache.json"),
                               ttl_s=3600)
        hosts = ["localhost", "localhost"]
        common, rank0 = probe_common_and_rank0(hosts, spawn, "k",
                                               timeout_s=30, cache=cache)
        assert common and rank0
        assert len(spawns) == 2
        # warm: same hosts, zero probe spawns, identical answer
        common2, rank02 = probe_common_and_rank0(hosts, spawn, "k",
                                                 timeout_s=30, cache=cache)
        assert (common2, rank02) == (common, rank0)
        assert len(spawns) == 2
        # a different host set is a different key — probes again
        probe_common_and_rank0(["localhost"], spawn, "k",
                               timeout_s=30, cache=cache)
        assert len(spawns) == 3
        # expired: TTL 0 forces a fresh probe
        expired = DiscoveryCache(path=str(tmp_path / "cache.json"),
                                 ttl_s=0)
        probe_common_and_rank0(hosts, spawn, "k", timeout_s=30,
                               cache=expired)
        assert len(spawns) == 5

    def test_tcp_reachable_semantics(self):
        """Listening and connection-refused both prove the host is
        alive and routable; only timeouts/route errors mark it stale."""
        import socket

        from horovod_tpu.runner.cache import tcp_reachable

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        s.listen(1)
        port = s.getsockname()[1]
        try:
            assert tcp_reachable("127.0.0.1", port)
        finally:
            s.close()
        # closed port: RST still comes from the host — alive
        assert tcp_reachable("127.0.0.1", port)

    def test_stale_cached_ip_falls_through_to_probe(self, tmp_path,
                                                    monkeypatch):
        """A warm hit whose rank-0 IP fails the TCP liveness check must
        re-probe instead of handing the launcher a dead coordinator
        address (ADVICE round 5)."""
        import threading

        import horovod_tpu.runner.cache as cache_mod
        from horovod_tpu.runner.cache import DiscoveryCache
        from horovod_tpu.runner.driver_service import (
            probe_common_and_rank0,
            run_probe_task,
        )

        hosts = ["localhost", "localhost"]
        cache = DiscoveryCache(path=str(tmp_path / "cache.json"),
                               ttl_s=3600)
        cache.put({"probe": hosts},
                  {"common": ["eth9"], "rank0": {"eth9": "192.0.2.1"}})

        checked = []
        monkeypatch.setattr(
            cache_mod, "tcp_reachable",
            lambda ip, port=22, timeout_s=1.0:
            checked.append((ip, port)) or False)

        spawns = []

        def spawn(host, index, driver_addr):
            spawns.append(index)
            threading.Thread(target=run_probe_task,
                             args=(driver_addr, index, "k"),
                             daemon=True).start()

        common, rank0 = probe_common_and_rank0(
            hosts, spawn, "k", timeout_s=30, cache=cache,
            validate_port=2222)
        assert checked == [("192.0.2.1", 2222)]
        assert len(spawns) == 2               # fell through to a probe
        assert rank0 and "192.0.2.1" not in rank0.values()
        # and the fresh (validatable) result replaced the stale entry
        assert cache.get({"probe": hosts})["rank0"] == rank0

    def test_probe_timeout_mentions_cache(self):
        from horovod_tpu.runner.driver_service import ProbeDriver

        driver = ProbeDriver(1, "k")
        try:
            with pytest.raises(TimeoutError, match="disable-cache"):
                driver.wait_common_interfaces(timeout_s=0.05)
        finally:
            driver.shutdown()

    def test_discovery_cache_roundtrip_and_expiry(self, tmp_path):
        import time as _time

        from horovod_tpu.runner.cache import DiscoveryCache

        path = str(tmp_path / "c.json")
        c = DiscoveryCache(path=path, ttl_s=3600)
        assert c.get({"probe": ["a"]}) is None
        c.put({"probe": ["a"]}, {"common": ["lo"], "rank0": {"lo": "1.1"}})
        assert c.get({"probe": ["a"]})["common"] == ["lo"]
        # key order must not matter
        c.put({"b": 1, "a": 2}, "v")
        assert DiscoveryCache(path=path, ttl_s=3600).get(
            {"a": 2, "b": 1}) == "v"
        # expiry honors the entry timestamp
        short = DiscoveryCache(path=path, ttl_s=0.05)
        short.put({"probe": ["x"]}, "soon-stale")
        _time.sleep(0.1)
        assert short.get({"probe": ["x"]}) is None
        # corrupt file degrades to a miss, never a crash
        with open(path, "w") as f:
            f.write("{not json")
        assert DiscoveryCache(path=path).get({"probe": ["a"]}) is None

    def test_probe_timeout_names_missing_tasks(self):
        from horovod_tpu.runner.driver_service import ProbeDriver

        driver = ProbeDriver(2, "k")
        try:
            with pytest.raises(TimeoutError, match=r"task\(s\) \[0, 1\]"):
                driver.wait_common_interfaces(timeout_s=0.5)
        finally:
            driver.shutdown()


class TestRunApi:
    def test_run_fn_collects_per_rank_results(self):
        """Real localhost 2-process launch through the full CLI path
        (reference ``test_interactiverun.py``)."""
        from horovod_tpu.runner import run

        def fn(factor):
            # worker processes: no jax needed — this validates the
            # launcher/env/result plumbing
            rank = int(os.environ["HOROVOD_RANK"])
            size = int(os.environ["HOROVOD_SIZE"])
            return {"rank": rank, "size": size, "value": rank * factor}

        results = run(fn, args=(10,), np=2)
        assert results == [
            {"rank": 0, "size": 2, "value": 0},
            {"rank": 1, "size": 2, "value": 10},
        ]

    def test_run_fn_failure_propagates(self):
        from horovod_tpu.runner import run

        def boom():
            raise RuntimeError("worker exploded")

        with pytest.raises(RuntimeError, match="exit code"):
            run(boom, np=2)


class TestCheckBuild:
    def test_check_build_output(self, capsys):
        from horovod_tpu.runner.launch import run_commandline

        rc = run_commandline(["--check-build"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "XLA" in out and "horovod_tpu" in out
