"""benchmark/run.py off the chip: it must refuse, and its rehearsal must
run every cell's path end to end at the tiny preset — one device and
four virtual ones — and print no result line either way."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _run(*args, cache_dir, devices=1, cwd=REPO):
    # as the driver starts it: none of conftest's HOROVOD_* settings
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("HOROVOD_")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], *args], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=600)


# every cell untraced; traced, the four-device cell and the host-fed one
RUNS = [(name, 0) for name in sorted(CELLS)] + \
    [("lm871m-s1024-b6-dp4", 1), ("resnet50-b256-hostfed", 1)]


@pytest.mark.parametrize("name,traced", RUNS)
def test_rehearsal_runs_the_cell_end_to_end_and_prints_no_result(
        name, traced, tmp_path):
    proc = _run("--workload", name, "--seed", "3", "--seconds", "1",
                "--trace", str(traced), "--rehearse", cache_dir=tmp_path,
                devices=CELLS[name]["chips"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert "CPU rehearsal of the tiny preset finished" in proc.stdout
    assert "correct True" in proc.stdout
    assert "'parity_with_plain_reference': True" in proc.stdout
    assert "'nothing_compiled_in_the_window': True" in proc.stdout
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout
    named = proc.stdout.rsplit("metrics it would name:", 1)[1]
    assert ("dispatch_ms" in named) if traced else ("setup_s" in named)


def test_without_a_tpu_it_fails_and_prints_no_result(tmp_path):
    proc = _run("--workload", "resnet50-b256", "--seed", "0", "--seconds",
                "1", "--trace", "0", cache_dir=tmp_path)
    assert proc.returncode != 0
    assert "needs 1 tpu device(s)" in proc.stderr
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout


def test_a_rehearsal_on_the_wrong_number_of_devices_is_refused(tmp_path):
    proc = _run("--workload", "lm871m-s1024-b6-dp4", "--seconds", "1",
                "--rehearse", cache_dir=tmp_path, devices=2)
    assert proc.returncode != 0
    assert "rehearses on 4 cpu device(s)" in proc.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the system
    under test is missing, so there is nothing to measure."""
    tree = tmp_path / "tree"
    tree.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", tree)
    for path in BENCH["paths"]:
        shutil.copytree(REPO / path, tree / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run("--workload", "resnet50-b256", "--seconds", "1",
                "--rehearse", cache_dir=tmp_path / "cache", cwd=tree)
    assert proc.returncode != 0
    assert "horovod_tpu" in proc.stderr
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout
