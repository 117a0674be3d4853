"""The per-layer readers of the program's own spans
(benchmark/program_spans.py, horovod_tpu/telemetry/spans.py): each on a
hand-made record with known answers, on a program without the recorder,
and named by the rehearsal in the cells where they apply."""

import importlib
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from test_benchmark_rehearse import _run  # noqa: E402

from horovod_tpu import telemetry  # noqa: E402
from horovod_tpu.telemetry.spans import Span  # noqa: E402

READERS = ["step_prepare_ms", "step_launch_ms", "input_source_ms",
           "input_place_ms", "input_feeder_busy_share", "step_lower_s",
           "step_compile_s"]

# the window is 100 s to 110 s on the spans' clock: two blocks of one
# step, 100 to 104.1 and 105 to 110
OBSERVED = types.SimpleNamespace(spans=types.SimpleNamespace(records={
    "next_batch": [(100.0, 0.001), (105.0, 0.001)],
    "dispatch": [(100.001, 0.005), (105.001, 0.005)],
    "read_loss": [(104.0, 0.1), (109.9, 0.1)]}))


def _handmade() -> list:
    rows, ids = [], iter(range(1, 1000))

    def add(name, start, seconds, thread="MainThread", seq=None,
            attrs=None):
        rows.append(Span(next(ids), None, name, start, start + seconds,
                         thread, seq, attrs))

    # the parity check's step: an earlier instance, its own ordinal 0
    add("train_step.call", 10.0, 10.0, seq=0)
    add("train_step.lower", 11.0, 2.5, seq=0)
    add("train_step.compile", 13.5, 6.0, seq=0, attrs={"hit": False})
    # the measured step's first call, then a warm-up step
    add("train_step.call", 50.0, 8.0, seq=0)
    add("train_step.prepare", 50.0, 7.0, seq=0)
    add("train_step.lower", 50.5, 2.0, seq=0)
    add("train_step.compile", 52.5, 3.5, seq=0, attrs={"hit": True})
    add("train_step.launch", 57.0, 1.0, seq=0)
    add("train_step.prepare", 60.0, 0.5, seq=1)
    add("train_step.launch", 60.5, 0.5, seq=1)
    # the window's steps: prepare 3, 4, 5 ms; launch 1, 2, 3 ms
    for i, (prepare, launch) in enumerate(
            [(0.003, 0.001), (0.005, 0.003), (0.004, 0.002)]):
        add("train_step.prepare", 101.0 + i, prepare, seq=11 + i)
        add("train_step.launch", 101.5 + i, launch, seq=11 + i)
    # the feeder: one batch across the window's opening (half a second
    # of it inside), 40, 60, 50 ms inside the first block, 50 ms between
    # the blocks; the workers 3, 8, 4 ms
    add("input.source", 99.5, 1.0, "feeder", seq=20)
    add("input.source", 104.5, 0.05, "feeder", seq=24)
    for i, (source, place) in enumerate(
            [(0.04, 0.003), (0.06, 0.008), (0.05, 0.004)]):
        add("input.source", 101.0 + i, source, "feeder", seq=21 + i)
        add("input.place", 101.1 + i, place, f"worker_{i % 2}", seq=21 + i)
    # past the window's close: never read
    add("train_step.prepare", 111.0, 0.5, seq=99)
    add("input.source", 111.0, 0.5, "feeder", seq=99)
    return rows


@pytest.mark.parametrize("name,expected", zip(READERS, [
    4.0, 2.0, 50.0, 4.0, 100.0 * (0.5 + 0.04 + 0.06 + 0.05) / (4.1 + 5.0),
    2.0, 3.5]))
def test_reader_on_a_hand_made_record(name, expected, monkeypatch):
    def snapshot(since=None, until=None):
        assert since is None and until == pytest.approx(110.0)
        return [s for s in _handmade() if s.start <= until]

    monkeypatch.setattr(telemetry.spans, "snapshot", snapshot)
    reader = importlib.import_module(f"benchmark.metrics.{name}")
    assert reader.read(OBSERVED) == pytest.approx(expected, rel=1e-9)


def test_on_a_program_without_the_recorder_every_reader_reads_nothing(
        monkeypatch):
    """The parent commit, under this benchmark: no value, no error."""
    monkeypatch.delattr(telemetry, "spans")
    monkeypatch.setitem(sys.modules, "horovod_tpu.telemetry.spans", None)
    for name in READERS:
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.read(OBSERVED) is None, name


def test_the_rehearsal_names_the_new_metrics_where_they_apply(tmp_path):
    """All seven in a host-fed cell; ``applies`` and the contract test
    keep the three of the input layer out of a resident one."""
    proc = _run("--workload", "resnet50-b256-hostfed", "--seed", "5",
                "--seconds", "1", "--trace", "1", "--rehearse",
                cache_dir=tmp_path)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    named = proc.stdout.rsplit("metrics it would name:", 1)[1]
    assert all(f"'{name}'" in named for name in READERS), named
