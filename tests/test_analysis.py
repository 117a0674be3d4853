"""hvdlint: per-rule positive/negative fixture pairs, suppression and
baseline round-trips, the offline HLO rule pack, and the package
self-run that makes the analyzer a tier-1 gate.

Every rule gets a known-bad snippet that MUST fire and a repaired twin
that MUST NOT — the pair is the rule's contract: the positive proves
the bug class is detected, the negative proves the idiomatic fix (or
the common benign look-alike) doesn't drown the tool in noise.
"""

import json
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

from horovod_tpu.analysis import Severity, run_analysis, write_baseline
from horovod_tpu.analysis import hlo_lint
from horovod_tpu.analysis.__main__ import main as cli_main
from horovod_tpu.analysis.engine import (
    Project,
    changed_files,
    collect_files,
    load_modules,
)

REPO = Path(__file__).resolve().parent.parent
BASELINE = REPO / "analysis_baseline.json"


def lint(src: str, tmp_path, select=None, name="mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return run_analysis([str(p)], select=select, root=str(tmp_path))


def rules_fired(report):
    return {f.rule for f in report.findings}


# -- HVD001: collective divergence -----------------------------------------

BAD_DIVERGENT = """
    import jax
    from horovod_tpu.ops import collectives as C

    def sync(x):
        if jax.process_index() == 0:
            return C.allreduce(x)
        return x

    def bcast(x, rank):
        if rank != 0:
            return None
        return C.broadcast(x, root_rank=0)
"""

GOOD_DIVERGENT = """
    import jax
    from horovod_tpu.ops import collectives as C

    def sync(x):
        return C.allreduce(x)

    def maybe(x, size):
        # branching on a world-uniform value is SPMD-safe: every rank
        # takes the same side
        if size > 1:
            return C.allreduce(x)
        return x

    def root_reads(x, rank):
        # rank branch WITHOUT a collective inside/after is fine
        val = read_disk() if rank == 0 else None
        return C.broadcast(val, root_rank=0)
"""


class TestCollectiveDivergence:
    def test_bad_fires(self, tmp_path):
        r = lint(BAD_DIVERGENT, tmp_path, select={"HVD001"})
        assert len(r.findings) == 2, [f.format() for f in r.findings]
        assert all(f.rule == "HVD001" and f.severity == Severity.P0
                   for f in r.findings)
        # one guarded-branch form, one early-exit form
        msgs = " ".join(f.message for f in r.findings)
        assert "rank-dependent control flow" in msgs
        assert "early exit" in msgs

    def test_repaired_twin_is_clean(self, tmp_path):
        r = lint(GOOD_DIVERGENT, tmp_path, select={"HVD001"})
        assert r.findings == [], [f.format() for f in r.findings]


# -- HVD002: host sync in hot path -----------------------------------------

BAD_HOTPATH = """
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        v = float(x)
        h = np.asarray(x)
        x.block_until_ready()
        i = x.item()
        return x * v

    def train(x):
        # jit(f)-wrapped defs count too
        def body(y):
            return float(y) + 1
        return jax.jit(body)(x)
"""

GOOD_HOTPATH = """
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        limit = float("inf")      # float of a constant is static Python
        return x + limit

    def host_side(x):
        # the same calls OUTSIDE the compiled region are the fix
        v = float(x)
        h = np.asarray(x)
        x.block_until_ready()
        return v, h
"""


class TestHostSync:
    def test_bad_fires(self, tmp_path):
        r = lint(BAD_HOTPATH, tmp_path, select={"HVD002"})
        kinds = sorted(f.message.split("'")[1] for f in r.findings)
        assert len(r.findings) == 5, [f.format() for f in r.findings]
        assert ".block_until_ready()" in kinds and ".item()" in kinds
        assert "np.asarray" in kinds and kinds.count("float()") == 2

    def test_repaired_twin_is_clean(self, tmp_path):
        r = lint(GOOD_HOTPATH, tmp_path, select={"HVD002"})
        assert r.findings == [], [f.format() for f in r.findings]


# -- HVD003: retrace hazard -------------------------------------------------

BAD_RETRACE = """
    import functools
    import hashlib
    import json
    import jax

    @jax.jit
    def branchy(x, n):
        if n > 3:             # tracer branch
            return x
        while x > 0:          # tracer loop
            x = x - 1
        return x

    def cache_key(obj, extras):
        h = hash(obj)                              # PYTHONHASHSEED-salted
        i = id(obj)                                # address reuse
        blob = json.dumps(extras, default=repr)    # embeds 0x... addrs
        return hashlib.sha256(f"{h}{i}{blob}".encode()).hexdigest()
"""

GOOD_RETRACE = """
    import functools
    import hashlib
    import json
    import jax

    @functools.partial(jax.jit, static_argnames=("n",))
    def branchy(x, n):
        if n > 3:             # static arg: free to branch
            return x
        return x * 2

    @jax.jit
    def optionals(x, y=None):
        if y is None:         # trace-time Python dispatch, not a tracer
            return x
        return x + y

    def cache_key(lowered_text, extras):
        payload = {"extras": extras or {},
                   "sha": hashlib.sha256(lowered_text.encode()).hexdigest()}
        return hashlib.sha256(
            json.dumps(payload, sort_keys=True).encode()).hexdigest()
"""


class TestRetraceHazard:
    def test_bad_fires(self, tmp_path):
        r = lint(BAD_RETRACE, tmp_path, select={"HVD003"})
        msgs = [f.message for f in r.findings]
        assert len(r.findings) == 5, [f.format() for f in r.findings]
        assert sum("traced parameter" in m for m in msgs) == 2
        assert any("hash()" in m for m in msgs)
        assert any("id()" in m for m in msgs)
        assert any("default=repr" in m for m in msgs)

    def test_repaired_twin_is_clean(self, tmp_path):
        r = lint(GOOD_RETRACE, tmp_path, select={"HVD003"})
        assert r.findings == [], [f.format() for f in r.findings]


# -- HVD004: thread/lock discipline ----------------------------------------

BAD_THREADS = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            self._t = threading.Thread(target=self._run, daemon=True)

        def _run(self):
            while True:
                self._count += 1          # thread side, no lock

        def reset(self):
            self._count = 0               # main side, no lock
"""

GOOD_THREADS = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            self._scratch = 0
            self._t = threading.Thread(target=self._run, daemon=True)

        def _run(self):
            while True:
                with self._lock:
                    self._count += 1
                self._scratch = 1         # thread-ONLY state: fine

        def reset(self):
            with self._lock:
                self._count = 0
"""

BAD_LOCK_ORDER = """
    import threading

    class Registry:
        def __init__(self, driver):
            self._lock = threading.Lock()
            self._driver = driver

        def purge(self):
            with self._lock:
                pass

        def fail(self):
            with self._lock:
                self._driver.stop()       # registry -> driver

    class Driver:
        def __init__(self):
            self._lock = threading.Lock()
            self._registry = Registry(self)

        def stop(self):
            with self._lock:
                pass

        def assign(self):
            with self._lock:
                self._registry.purge()    # driver -> registry
"""

GOOD_LOCK_ORDER = """
    import threading

    class Registry:
        def __init__(self, driver):
            self._lock = threading.Lock()
            self._driver = driver

        def purge(self):
            with self._lock:
                pass

        def fail(self):
            with self._lock:
                stop = True
            if stop:
                self._driver.stop()       # called OUTSIDE our lock

    class Driver:
        def __init__(self):
            self._lock = threading.Lock()
            self._registry = Registry(self)

        def stop(self):
            with self._lock:
                pass

        def assign(self):
            with self._lock:
                self._registry.purge()
"""


class TestThreadLockDiscipline:
    def test_bad_fires(self, tmp_path):
        r = lint(BAD_THREADS, tmp_path, select={"HVD004"})
        assert len(r.findings) == 1, [f.format() for f in r.findings]
        assert "Worker._count" in r.findings[0].message

    def test_repaired_twin_is_clean(self, tmp_path):
        r = lint(GOOD_THREADS, tmp_path, select={"HVD004"})
        assert r.findings == [], [f.format() for f in r.findings]

    def test_lock_order_cycle_fires(self, tmp_path):
        """The constructor-argument back-reference pattern that hid the
        real elastic registry<->driver inversion this PR fixed."""
        r = lint(BAD_LOCK_ORDER, tmp_path, select={"HVD004"})
        cycles = [f for f in r.findings
                  if "lock-acquisition-order cycle" in f.message]
        assert cycles, [f.format() for f in r.findings]
        assert "Registry._lock" in cycles[0].message
        assert "Driver._lock" in cycles[0].message

    def test_lock_order_repaired_twin_is_clean(self, tmp_path):
        r = lint(GOOD_LOCK_ORDER, tmp_path, select={"HVD004"})
        cycles = [f for f in r.findings
                  if "lock-acquisition-order cycle" in f.message]
        assert cycles == [], [f.format() for f in cycles]

    def test_real_inversion_is_detected_when_reintroduced(self, tmp_path):
        """Regression pin for the fixed elastic deadlock: re-create the
        pre-fix _maybe_resume shape against the real driver/registry
        pair and assert the rule still catches it."""
        driver_src = (REPO / "horovod_tpu/elastic/driver.py").read_text()
        reg_src = (REPO / "horovod_tpu/elastic/registration.py").read_text()
        # un-fix: put the stop() call back under the registry lock
        broken = reg_src.replace(
            "        with self._lock:\n"
            "            stop = bool(self._reset_limit\n"
            "                        and self._reset_count >= "
            "self._reset_limit)\n"
            "            if not stop:\n"
            "                self._reset_count += 1\n"
            "        if stop:",
            "        with self._lock:\n"
            "            stop = bool(self._reset_limit\n"
            "                        and self._reset_count >= "
            "self._reset_limit)\n"
            "            if not stop:\n"
            "                self._reset_count += 1\n"
            "            if stop:\n"
            "                self._driver.stop()\n"
            "                return\n"
            "        if stop:")
        assert broken != reg_src, "un-fix patch no longer applies"
        (tmp_path / "driver.py").write_text(driver_src)
        (tmp_path / "registration.py").write_text(broken)
        r = run_analysis([str(tmp_path)], select={"HVD004"},
                         root=str(tmp_path))
        cycles = [f for f in r.findings
                  if "lock-acquisition-order cycle" in f.message]
        assert cycles, [f.format() for f in r.findings]


# -- HVD005: env-knob registry ----------------------------------------------

def _mini_repo(tmp_path, module_src: str, knobs=("HOROVOD_GOOD_KNOB",),
               docs="HOROVOD_GOOD_KNOB documented here"):
    tmp_path.mkdir(parents=True, exist_ok=True)
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "horovod_tpu"
    (pkg / "runtime").mkdir(parents=True)
    (pkg / "runtime" / "config.py").write_text(
        "KNOWN_KNOBS = frozenset({"
        + ", ".join(repr(k) for k in knobs) + "})\n")
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "running.md").write_text(docs)
    (pkg / "mod.py").write_text(textwrap.dedent(module_src))
    return run_analysis([str(pkg)], select={"HVD005"}, root=str(tmp_path))


class TestEnvKnobRegistry:
    def test_unregistered_read_and_undocumented_fire(self, tmp_path):
        r = _mini_repo(tmp_path, """
            import os
            def f():
                return os.environ.get("HOROVOD_ROGUE_KNOB", "1")
        """)
        msgs = [f.message for f in r.findings]
        assert any("not declared" in m and "HOROVOD_ROGUE_KNOB" in m
                   for m in msgs), msgs
        assert any("undocumented" in m and "HOROVOD_ROGUE_KNOB" in m
                   for m in msgs), msgs

    def test_registered_documented_is_clean(self, tmp_path):
        r = _mini_repo(tmp_path, """
            import os
            def f():
                return os.environ.get("HOROVOD_GOOD_KNOB", "1")
        """)
        assert r.findings == [], [f.format() for f in r.findings]

    def test_stale_registration_flagged(self, tmp_path):
        r = _mini_repo(tmp_path, """
            def f():
                return 1
        """, knobs=("HOROVOD_GOOD_KNOB",))
        stale = [f for f in r.findings if "stale registration" in f.message]
        assert stale and stale[0].severity == Severity.P3

    def test_package_registry_is_complete(self):
        """Every knob the real package references is registered —
        HVD005's half of what test_env_knob_docs pins for docs."""
        from horovod_tpu.analysis.rules_runtime import (
            parse_known_knobs,
            referenced_knobs,
        )

        files = collect_files([str(REPO / "horovod_tpu")])
        project = Project(load_modules(files, str(REPO)), root=str(REPO))
        knobs = parse_known_knobs(project.module("runtime/config.py"))
        assert knobs, "KNOWN_KNOBS missing from runtime/config.py"
        missing = sorted(set(referenced_knobs(project)) - knobs)
        assert missing == [], f"unregistered knobs: {missing}"


# -- HVD006: fault-hook coverage --------------------------------------------

BAD_FAULTS = """
    import threading

    class Poller:
        def __init__(self):
            self._t = threading.Thread(target=self._loop)

        def _loop(self):
            while True:
                pass

    def connect_backend(addr):
        return open_socket(addr)
"""

GOOD_FAULTS = """
    import threading
    from horovod_tpu import faults

    class Poller:
        def __init__(self):
            self._t = threading.Thread(target=self._loop)

        def _loop(self):
            while True:
                faults.inject("poller.loop")

    def connect_backend(addr):
        faults.inject("backend.connect")
        return open_socket(addr)

    class OneShot:
        def __init__(self):
            self._t = threading.Thread(target=self._work)

        def _work(self):
            for _ in range(3):     # worklist, not a run-loop
                pass
"""


class TestFaultHookCoverage:
    def test_bad_fires(self, tmp_path):
        r = lint(BAD_FAULTS, tmp_path, select={"HVD006"})
        msgs = [f.message for f in r.findings]
        assert len(r.findings) == 2, [f.format() for f in r.findings]
        assert any("thread run-loop 'Poller._loop'" in m for m in msgs)
        assert any("connect path 'connect_backend'" in m for m in msgs)

    def test_repaired_twin_is_clean(self, tmp_path):
        r = lint(GOOD_FAULTS, tmp_path, select={"HVD006"})
        assert r.findings == [], [f.format() for f in r.findings]

    def test_one_call_hop_counts(self, tmp_path):
        r = lint("""
            import threading
            from horovod_tpu import faults

            def _pass():
                faults.inject("x.pass")

            class M:
                def __init__(self):
                    self._t = threading.Thread(target=self._loop)

                def _loop(self):
                    while True:
                        _pass()
        """, tmp_path, select={"HVD006"})
        assert r.findings == [], [f.format() for f in r.findings]


# -- suppressions + baseline ------------------------------------------------

class TestSuppressionAndBaseline:
    SRC = """
        import jax
        from horovod_tpu.ops import collectives as C

        def f(x):
            if jax.process_index() == 0:
                return C.allreduce(x)   {sup}
            return x
    """

    def test_suppression_with_reason_suppresses(self, tmp_path):
        src = self.SRC.format(
            sup="# hvd: disable=HVD001 -- negotiated out-of-band")
        r = lint(src, tmp_path, select={"HVD001"})
        assert r.findings == []
        assert len(r.suppressed) == 1
        assert r.suppressed[0][1] == "negotiated out-of-band"

    def test_suppression_on_preceding_comment_line(self, tmp_path):
        src = """
            import jax
            from horovod_tpu.ops import collectives as C

            def f(x):
                if jax.process_index() == 0:
                    # hvd: disable=HVD001 -- proven unreachable in prod
                    return C.allreduce(x)
                return x
        """
        r = lint(src, tmp_path, select={"HVD001"})
        assert r.findings == []
        assert len(r.suppressed) == 1

    def test_reasonless_suppression_is_its_own_finding(self, tmp_path):
        src = self.SRC.format(sup="# hvd: disable=HVD001")
        r = lint(src, tmp_path, select={"HVD001"})
        rules = rules_fired(r)
        # the original finding STAYS (no reason = no suppression) and
        # the engine adds HVD000 for the bad disable
        assert rules == {"HVD000", "HVD001"}, \
            [f.format() for f in r.findings]

    def test_hvd000_cannot_be_suppressed(self, tmp_path):
        src = self.SRC.format(
            sup="# hvd: disable=HVD001,HVD000")
        r = lint(src, tmp_path, select={"HVD001"})
        assert "HVD000" in rules_fired(r)

    def test_wrong_rule_id_does_not_suppress(self, tmp_path):
        src = self.SRC.format(sup="# hvd: disable=HVD002 -- wrong rule")
        r = lint(src, tmp_path, select={"HVD001"})
        assert rules_fired(r) == {"HVD001"}

    def test_baseline_round_trip(self, tmp_path):
        src = self.SRC.format(sup="")
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(src))
        first = run_analysis([str(p)], select={"HVD001"},
                             root=str(tmp_path))
        assert len(first.findings) == 1
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), first.findings)
        second = run_analysis([str(p)], select={"HVD001"},
                              baseline_path=str(bl), root=str(tmp_path))
        assert second.findings == []
        assert len(second.baselined) == 1
        # a NEW violation (different context line) is not absorbed
        p.write_text(p.read_text().replace(
            "return C.allreduce(x)",
            "return C.allreduce(x + 1)"))
        third = run_analysis([str(p)], select={"HVD001"},
                             baseline_path=str(bl), root=str(tmp_path))
        assert len(third.findings) == 1

    def test_baseline_survives_line_shift(self, tmp_path):
        src = self.SRC.format(sup="")
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(src))
        first = run_analysis([str(p)], select={"HVD001"},
                             root=str(tmp_path))
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), first.findings)
        # prepend lines: same context, different lineno — still matched
        p.write_text("# header\n# header\n" + p.read_text())
        shifted = run_analysis([str(p)], select={"HVD001"},
                               baseline_path=str(bl), root=str(tmp_path))
        assert shifted.findings == []
        assert len(shifted.baselined) == 1


# -- CLI --------------------------------------------------------------------

class TestCli:
    def test_json_mode_and_exit_codes(self, tmp_path, capsys):
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(BAD_DIVERGENT))
        rc = cli_main(["--json", "--select", "HVD001", str(p)])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert len(out["findings"]) == 2
        assert out["findings"][0]["rule"] == "HVD001"
        p.write_text(textwrap.dedent(GOOD_DIVERGENT))
        assert cli_main(["--json", "--select", "HVD001", str(p)]) == 0

    def test_list_rules(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("HVD001", "HVD002", "HVD003", "HVD004", "HVD005",
                    "HVD006"):
            assert rid in out

    def test_changed_scope(self, tmp_path):
        subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
        env_git = ["git", "-C", str(tmp_path),
                   "-c", "user.email=t@t", "-c", "user.name=t"]
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(GOOD_DIVERGENT))
        subprocess.run(env_git + ["add", "-A"], check=True)
        subprocess.run(env_git + ["commit", "-qm", "init"], check=True)
        assert changed_files(str(tmp_path)) == []
        p.write_text(textwrap.dedent(BAD_DIVERGENT))
        (tmp_path / "untracked.py").write_text(
            textwrap.dedent(BAD_DIVERGENT))
        changed = changed_files(str(tmp_path))
        assert sorted(Path(c).name for c in changed) == \
            ["mod.py", "untracked.py"]
        r = run_analysis(changed, select={"HVD001"}, root=str(tmp_path))
        assert len(r.findings) == 4    # 2 per file

    def test_changed_on_this_repo_is_clean(self):
        """The tier-1 wiring: the pre-commit view of horovod_tpu/ must
        lint clean (scoped to the package so test fixtures with
        intentionally-bad snippets don't count)."""
        rc = cli_main(["--changed", str(REPO / "horovod_tpu")])
        assert rc == 0


# -- the tier-1 self-run ----------------------------------------------------

class TestSelfRun:
    def test_package_lints_clean(self):
        """The acceptance gate: the merged tree has zero live findings
        (fixed, suppressed-with-reason, or baselined) and the scan fits
        the <30 s budget on CPU."""
        t0 = time.perf_counter()
        report = run_analysis(
            [str(REPO / "horovod_tpu")],
            baseline_path=str(BASELINE) if BASELINE.exists() else None,
            root=str(REPO))
        elapsed = time.perf_counter() - t0
        assert report.files_scanned > 80
        assert report.findings == [], \
            "\n".join(f.format() for f in report.findings)
        assert elapsed < 30, f"self-run took {elapsed:.1f}s"

    def test_cli_self_run_exit_zero(self):
        assert cli_main([str(REPO / "horovod_tpu")]) == 0

    def test_every_rule_can_fire(self, tmp_path):
        """Liveness: the six rules each demonstrably fire on their
        known-bad fixture — a rule that silently stopped matching would
        otherwise look like a clean self-run."""
        fired = set()
        for src, sel in ((BAD_DIVERGENT, "HVD001"),
                         (BAD_HOTPATH, "HVD002"),
                         (BAD_RETRACE, "HVD003"),
                         (BAD_THREADS, "HVD004"),
                         (BAD_FAULTS, "HVD006")):
            r = lint(src, tmp_path, select={sel}, name=f"{sel}.py")
            fired |= rules_fired(r)
        r5 = _mini_repo(tmp_path / "r5", """
            import os
            def f():
                return os.environ.get("HOROVOD_ROGUE_KNOB", "1")
        """)
        fired |= rules_fired(r5)
        assert {"HVD001", "HVD002", "HVD003", "HVD004", "HVD005",
                "HVD006"} <= fired


# -- offline HLO / artifact rule pack ---------------------------------------

class TestHloLint:
    RS_LINE = ("  %rs = (f32[104]{0}, f32[13]{0}) reduce-scatter-start"
               "(%x), replica_groups=[1,4]<=[8], dimensions={0}, "
               "to_apply=%add")
    RS_DONE = "  %rsd = f32[13]{0} reduce-scatter-done(%rs)"

    def test_gradient_sized_allreduce_fires(self):
        text = "\n".join([
            self.RS_LINE, self.RS_DONE,
            "  %ar = f32[100000]{0} all-reduce(%g), "
            "replica_groups=[1,8]<=[8], to_apply=%add",
        ])
        findings = hlo_lint.lint_hlo_text(text)
        assert any(f.rule == "HLO001" for f in findings), findings

    def test_scalar_allreduce_is_fine(self):
        text = "\n".join([
            self.RS_LINE, self.RS_DONE,
            "  %loss = f32[]{} all-reduce(%l), "
            "replica_groups=[1,8]<=[8], to_apply=%add",
        ])
        assert [f for f in hlo_lint.lint_hlo_text(text)
                if f.rule == "HLO001"] == []

    def test_broken_async_pairing_fires(self):
        findings = hlo_lint.lint_hlo_text(self.RS_LINE)   # start, no done
        assert any(f.rule == "HLO002" for f in findings), findings
        assert [f for f in hlo_lint.lint_hlo_text(
            self.RS_LINE + "\n" + self.RS_DONE)
            if f.rule == "HLO002"] == []

    def test_two_level_without_int8_dcn_fires(self):
        full = "\n".join([
            self.RS_LINE, self.RS_DONE,
            "  %rs2 = f32[13]{0} reduce-scatter(%y), "
            "replica_groups=[4,2]<=[8]T(1,0), dimensions={0}, "
            "to_apply=%add",
        ])
        findings = hlo_lint.lint_hlo_text(full,
                                          expect_hierarchy="two_level")
        assert any(f.rule == "HLO003" for f in findings), findings
        quantized = full + (
            "\n  %q = s8[13]{0} all-to-all(%z), "
            "replica_groups=[4,2]<=[8]T(1,0), dimensions={0}")
        assert [f for f in hlo_lint.lint_hlo_text(
            quantized, expect_hierarchy="two_level")
            if f.rule == "HLO003"] == []

    def test_two_level_single_scope_fires(self):
        findings = hlo_lint.lint_hlo_text(
            self.RS_LINE + "\n" + self.RS_DONE,
            expect_hierarchy="two_level")
        assert any(f.rule == "HLO004" for f in findings), findings

    def test_artifact_checks(self):
        good = {"exchange_hierarchy": "two_level",
                "exchange_rs_scopes": [2, 4],
                "exchange_grad_sized_allreduces": 0,
                "overlap_fraction": 0.8}
        assert hlo_lint.lint_artifact(good) == []
        bad = {"exchange_hierarchy": "two_level",
               "exchange_rs_scopes": [8],
               "exchange_grad_sized_allreduces": 2,
               "overlap_fraction": 1.7}
        rules = {f.rule for f in hlo_lint.lint_artifact(bad)}
        assert rules == {"HLO001", "HLO004"}, rules

    def test_artifact_prefixed_fields(self):
        art = {"transformer_exchange_hierarchy": "flat",
               "transformer_exchange_rs_scopes": [2, 4]}
        findings = hlo_lint.lint_artifact(art)
        assert any(f.rule == "HLO004" and "transformer" in f.message
                   for f in findings), findings

    def test_artifact_file_and_multichip_wrapper(self, tmp_path):
        p = tmp_path / "bench.json"
        p.write_text(json.dumps({
            "parsed": {"exchange_hierarchy": "two_level",
                       "exchange_rs_scopes": [8]}}))
        findings = hlo_lint.lint_artifact_path(str(p))
        assert any(f.rule == "HLO004" for f in findings), findings

    def test_repo_artifacts_lint_clean(self):
        """The trajectory fixture and the checked-in MULTICHIP stubs
        pass the rule pack — the offline gate the satellite asks for."""
        arts = sorted(REPO.glob(
            "tests/fixtures/gate_trajectory/gate_input_r0*.json")) + \
            sorted(REPO.glob("MULTICHIP_r0*.json"))
        assert arts, "no checked-in bench artifacts found"
        for art in arts:
            findings = hlo_lint.lint_artifact_path(str(art))
            assert findings == [], (art.name,
                                    [f.format() for f in findings])

    def test_cli_artifact_mode(self, tmp_path, capsys):
        p = tmp_path / "bench.json"
        p.write_text(json.dumps({"exchange_hierarchy": "two_level",
                                 "exchange_rs_scopes": [8]}))
        rc = cli_main(["--artifact", str(p)])
        assert rc == 1
        assert "HLO004" in capsys.readouterr().out

    def test_probe_report_emits_grad_sized_field(self):
        from horovod_tpu.utils.overlap_probe import OverlapReport

        rep = OverlapReport(backward_s=1.0, exchange_s=1.0, fused_s=1.5,
                            overlap_fraction=0.5, world=8,
                            payload_bytes=1024, hierarchy="two_level",
                            rs_scopes=(2, 4), ag_scopes=(2, 4),
                            grad_sized_allreduces=0)
        fields = rep.as_bench_fields(prefix="transformer_")
        assert fields["transformer_exchange_grad_sized_allreduces"] == 0
        assert hlo_lint.lint_artifact(fields) == []


class TestSerialTailRule:
    """HLO005 (ISSUE 9): a serial exchange tail — the final RS/AG
    start..done pair with no compute scheduled between — must be
    flagged in HLO dumps, and an artifact claiming fused_collectives=on
    must not still report one."""

    SERIAL = "\n".join([
        "ENTRY %main () -> f32[13] {",
        "  %p = f32[104]{0} parameter(0)",
        "  %rs = (f32[104]{0}, f32[13]{0}) reduce-scatter-start(%p), "
        "replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add",
        "  %rsd = f32[13]{0} reduce-scatter-done(%rs)",
        "  ROOT %r = f32[13]{0} copy(%rsd)",
        "}",
    ])

    def test_serial_tail_fires(self):
        findings = hlo_lint.lint_hlo_text(self.SERIAL)
        assert any(f.rule == "HLO005" for f in findings), findings

    def test_overlapped_tail_clean(self):
        overlapped = self.SERIAL.replace(
            "  %rsd = ",
            "  %d = f32[16,16]{1,0} dot(%a, %b), "
            "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"
            "  %rsd = ")
        assert [f for f in hlo_lint.lint_hlo_text(overlapped)
                if f.rule == "HLO005"] == []

    def test_synchronous_module_not_judged(self):
        sync = ("  %rs = f32[13]{0} reduce-scatter(%p), "
                "replica_groups=[1,8]<=[8], dimensions={0}, "
                "to_apply=%add")
        assert [f for f in hlo_lint.lint_hlo_text(sync)
                if f.rule == "HLO005"] == []

    def test_non_final_serial_pair_not_flagged(self):
        """Only the FINAL pair is the tail; an early serial pair has
        later compute to hide under and stays HLO005-clean."""
        from horovod_tpu.utils import hlo as H

        early = self.SERIAL.replace(
            "  ROOT %r = f32[13]{0} copy(%rsd)",
            "  %ag = (f32[13]{0}, f32[104]{0}) all-gather-start(%rsd), "
            "replica_groups=[1,8]<=[8], dimensions={0}\n"
            "  %d = f32[16,16]{1,0} dot(%a, %b), "
            "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"
            "  %agd = f32[104]{0} all-gather-done(%ag)\n"
            "  ROOT %r = f32[104]{0} copy(%agd)")
        assert H.serial_tail_collectives(early) == 0

    def test_artifact_fused_on_with_serial_tail_fires(self):
        art = {"overlap_fraction": 0.5,
               "fused_collectives": "on",
               "exchange_serial_tail_collectives": 1}
        assert any(f.rule == "HLO005"
                   for f in hlo_lint.lint_artifact(art))

    def test_artifact_fused_off_serial_tail_expected(self):
        art = {"overlap_fraction": 0.5,
               "fused_collectives": "off",
               "exchange_serial_tail_collectives": 1}
        assert [f for f in hlo_lint.lint_artifact(art)
                if f.rule == "HLO005"] == []

    def test_legacy_artifact_without_fields_passes(self):
        assert [f for f in hlo_lint.lint_artifact(
            {"overlap_fraction": 0.5})
            if f.rule == "HLO005"] == []

    def test_prefixed_artifact_fields(self):
        art = {"fused_overlap_fraction": 0.5,
               "fused_fused_collectives": "on",
               "fused_exchange_serial_tail_collectives": 2}
        assert any(f.rule == "HLO005"
                   for f in hlo_lint.lint_artifact(art))


class TestMoeDispatchRule:
    """HLO006 (ISSUE 16): a serial boundary-wide MoE dispatch — the
    final all-to-all start..done pair with no compute inside its
    window — must be flagged in HLO dumps, and an ep>1 artifact that
    claims the fused dispatch must not still report one."""

    SERIAL = "\n".join([
        "ENTRY %main () -> f32[8,16] {",
        "  %p = f32[8,16]{1,0} parameter(0)",
        "  %a2a = (f32[8,16]{1,0}, f32[8,16]{1,0}) "
        "all-to-all-start(%p), replica_groups={{0,1,2,3,4,5,6,7}}, "
        "dimensions={0}",
        "  %a2ad = f32[8,16]{1,0} all-to-all-done(%a2a)",
        "  ROOT %r = f32[8,16]{1,0} copy(%a2ad)",
        "}",
    ])

    def test_serial_dispatch_fires(self):
        findings = hlo_lint.lint_hlo_text(self.SERIAL)
        assert any(f.rule == "HLO006" for f in findings), findings

    def test_overlapped_dispatch_clean(self):
        """Expert matmul scheduled inside the start..done window — the
        fused ring's shape — hides the wire; no finding."""
        overlapped = self.SERIAL.replace(
            "  %a2ad = ",
            "  %d = f32[16,16]{1,0} dot(%a, %b), "
            "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"
            "  %a2ad = ")
        assert [f for f in hlo_lint.lint_hlo_text(overlapped)
                if f.rule == "HLO006"] == []

    def test_synchronous_dispatch_not_judged(self):
        sync = ("  %a2a = f32[8,16]{1,0} all-to-all(%p), "
                "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}")
        assert [f for f in hlo_lint.lint_hlo_text(sync)
                if f.rule == "HLO006"] == []

    def test_artifact_fused_ep_with_serial_dispatch_fires(self):
        art = {"moe_fused_collectives": "on", "moe_ep": 4,
               "moe_serial_tail_alltoalls": 1}
        assert any(f.rule == "HLO006"
                   for f in hlo_lint.lint_artifact(art))

    def test_artifact_ep_one_or_unfused_expected(self):
        # ep=1: experts local, no boundary to judge
        assert [f for f in hlo_lint.lint_artifact(
            {"moe_fused_collectives": "on", "moe_ep": 1,
             "moe_serial_tail_alltoalls": 1})
            if f.rule == "HLO006"] == []
        # fused off: the serial all-to-all IS the unfused schedule
        assert [f for f in hlo_lint.lint_artifact(
            {"moe_fused_collectives": "off", "moe_ep": 4,
             "moe_serial_tail_alltoalls": 1})
            if f.rule == "HLO006"] == []

    def test_legacy_artifact_without_moe_fields_passes(self):
        assert [f for f in hlo_lint.lint_artifact(
            {"overlap_fraction": 0.5})
            if f.rule == "HLO006"] == []


class TestSpRingRule:
    """HLO007 (ISSUE 17): a serial sp ring hop — the final
    collective-permute start..done pair with no compute inside its
    window — must be flagged in HLO dumps, and an sp>1 artifact that
    claims the fused ring-flash attention must show a clean ring."""

    SERIAL = "\n".join([
        "ENTRY %main () -> f32[8,16] {",
        "  %p = f32[8,16]{1,0} parameter(0)",
        "  %cp = (f32[8,16]{1,0}, f32[8,16]{1,0}) "
        "collective-permute-start(%p), "
        "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}",
        "  %cpd = f32[8,16]{1,0} collective-permute-done(%cp)",
        "  ROOT %r = f32[8,16]{1,0} copy(%cpd)",
        "}",
    ])

    def test_serial_ring_hop_fires(self):
        findings = hlo_lint.lint_hlo_text(self.SERIAL)
        assert any(f.rule == "HLO007" for f in findings), findings

    def test_overlapped_ring_hop_clean(self):
        """Flash compute scheduled inside the start..done window — the
        double-buffered ring's shape — hides the hop; no finding."""
        overlapped = self.SERIAL.replace(
            "  %cpd = ",
            "  %d = f32[16,16]{1,0} dot(%a, %b), "
            "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"
            "  %cpd = ")
        assert [f for f in hlo_lint.lint_hlo_text(overlapped)
                if f.rule == "HLO007"] == []

    def test_synchronous_permute_not_judged(self):
        sync = ("  %cp = f32[8,16]{1,0} collective-permute(%p), "
                "source_target_pairs={{0,1},{1,0}}")
        assert [f for f in hlo_lint.lint_hlo_text(sync)
                if f.rule == "HLO007"] == []

    def test_artifact_fused_sp_with_dirty_ring_fires_each_probe(self):
        """All three structural probes fire independently: an exposed
        hop, a full-sequence gather, and a too-short permute count."""
        art = {"sp_fused_collectives": "on", "sp": 4,
               "sp_serial_tail_permutes": 1,
               "sp_attention_allgathers": 2,
               "sp_collective_permutes": 3}   # < 2*(4-1)
        findings = [f for f in hlo_lint.lint_artifact(art)
                    if f.rule == "HLO007"]
        assert len(findings) == 3, findings

    def test_artifact_clean_fused_ring_passes(self):
        art = {"sp_fused_collectives": "on", "sp": 2,
               "sp_serial_tail_permutes": 0,
               "sp_attention_allgathers": 0,
               "sp_collective_permutes": 10}
        assert [f for f in hlo_lint.lint_artifact(art)
                if f.rule == "HLO007"] == []

    def test_artifact_sp_one_or_unfused_expected(self):
        # sp=1: the sequence is local, no ring to judge
        assert [f for f in hlo_lint.lint_artifact(
            {"sp_fused_collectives": "on", "sp": 1,
             "sp_serial_tail_permutes": 1})
            if f.rule == "HLO007"] == []
        # fused off: the serial hop IS the jnp/unfused schedule
        assert [f for f in hlo_lint.lint_artifact(
            {"sp_fused_collectives": "off", "sp": 4,
             "sp_serial_tail_permutes": 1})
            if f.rule == "HLO007"] == []

    def test_legacy_artifact_without_sp_fields_passes(self):
        assert [f for f in hlo_lint.lint_artifact(
            {"overlap_fraction": 0.5})
            if f.rule == "HLO007"] == []
