"""chip_smoke.py off the chip: it must refuse, and its rehearsal must run.

The script's real work needs a TPU (the chip tool runs it); what tier-1
can hold is the contract around it — no accelerator, no result — and,
through ``--cpu-rehearsal``, that every phase still executes against the
current package at a tiny size with interpreter-mode kernels.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(*args, cache_dir, devices=1):
    # the script runs as a user would start it: none of conftest's
    # HOROVOD_* settings (mesh shape, cache root) in its environment
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("HOROVOD_")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=600)


def test_without_a_tpu_it_fails_and_prints_no_result(tmp_path):
    proc = _run(cache_dir=str(tmp_path))
    assert proc.returncode != 0
    assert "platform cpu" in proc.stdout.splitlines()[0]
    assert "does not train on the CPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_cpu_rehearsal_runs_every_phase_and_prints_no_result(tmp_path):
    proc = _run("--cpu-rehearsal", cache_dir=str(tmp_path), devices=4)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert "CPU rehearsal" in proc.stdout
    for phase in ("[train_pjit]", "[train_zero]", "[ring_flash_sp4]",
                  "[fused_tp4]"):
        assert phase in proc.stdout, proc.stdout[-2000:]
    assert '"ok"' not in proc.stdout


def test_result_line_has_exactly_the_keys_the_checker_takes(monkeypatch):
    # the checker refuses a last line with any key besides these (it
    # refused this script once, for carrying the phases in it)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    # its dataclass resolves annotations through sys.modules
    monkeypatch.setitem(sys.modules, "chip_smoke", chip_smoke)
    spec.loader.exec_module(chip_smoke)
    line = chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
