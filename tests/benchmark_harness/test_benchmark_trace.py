"""The trace reducer on plain event lists with known answers."""

import gzip
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import trace  # noqa: E402

FIXTURES = REPO / "benchmark" / "fixtures"


def _reduce(fixture: dict) -> dict:
    return trace.reduce_trace(
        [tuple(e) for e in fixture["events"]],
        {"classes": fixture["classes"], "done_of": fixture["done_of"]})


def test_interval_arithmetic():
    u = trace.union([[5, 7], [1, 3], [2, 4], [7, 8], [9, 9]])
    assert u == [[1, 4], [5, 8]] and trace.length(u) == 6
    assert trace.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == \
        [[0, 1], [2, 4], [6, 9]]
    assert trace.subtract([[0, 2], [3, 5]], [[1, 4]]) == [[0, 1], [4, 5]]
    assert trace.subtract([[0, 2]], []) == [[0, 2]]
    assert trace.clip([[0, 5], [8, 12], [20, 30]], 3, 10) == \
        [[3, 5], [8, 10]]


def test_handmade_trace_busy_union_exposed_collective_and_gaps():
    fixture = json.loads((FIXTURES / "handmade_trace.json").read_text())
    want, got = fixture["expect_ns"], _reduce(fixture)

    def ns(seconds):
        return round(seconds * 1e9)

    assert got["devices"] == want["devices"]
    assert got["slowest"] == want["slowest"]
    assert ns(got["window_s"]) == want["window"]
    assert ns(got["busy_s"]) == want["busy_average"]
    assert got["idle_share"] == pytest.approx(
        want["idle_slowest"] / want["window"])
    assert {k: ns(v) for k, v in got["class_s"].items()} == want["class"]
    assert ns(got["collective_s"]) == want["collective_in_flight"]
    assert ns(got["collective_exposed_s"]) == want["collective_exposed"]
    assert {k: ns(v) for k, v in got["idle_gaps"]} == want["idle_gaps"]
    assert [got["device_ops"][0][0], ns(got["device_ops"][0][1])] == \
        want["top_op"]
    assert len(got["device_ops"]) <= 10 and got["unclassified_s"] == 0


def test_an_operation_the_module_does_not_name_counts_as_compute():
    fixture = json.loads((FIXTURES / "handmade_trace.json").read_text())
    del fixture["classes"]["fusion.3"]
    got = _reduce(fixture)
    assert round(got["unclassified_s"] * 1e9) == 1500
    assert round(got["class_s"]["compute"] * 1e9) == 5500


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    events = [("/host:CPU", "python3", "traced_window", 0, 100),
              ("/host:CPU", "python3", "dispatch", 10, 20)]
    assert trace.reduce_trace(events, {"classes": {}, "done_of": {}}) == {}


def test_a_device_trace_without_the_window_span_is_an_error():
    events = [("/device:TPU:0", "XLA Ops", "%fusion.1", 0, 100)]
    with pytest.raises(ValueError, match="traced_window"):
        trace.reduce_trace(
            events, {"classes": {"fusion.1": "compute"}, "done_of": {}})


def test_trace_recorded_on_the_chip_two_steps_of_the_lm():
    """Real events (``fixtures/``, cut to two steps): what must hold of
    any sound reduction, and the figures this one gave when recorded."""
    with gzip.open(FIXTURES / "chip_trace_lm871m_two_steps.json.gz",
                   "rt") as f:
        fixture = json.load(f)
    got = trace.reduce_trace([tuple(e) for e in fixture["events"]], fixture)
    assert got["devices"] == 1 and got["unclassified_s"] == 0
    # one core runs one operation at a time: the classes' self times add
    # up to the busy union
    assert sum(got["class_s"].values()) == pytest.approx(got["busy_s"],
                                                         rel=1e-9)
    # busy time plus the gaps inside the two program runs is the two
    # runs, which the trace's own Steps line puts at 272.82 ms each
    gaps = dict(got["idle_gaps"])
    assert (got["busy_s"] + gaps["in_program"]) / fixture["steps"] * 1e3 \
        == pytest.approx(fixture["step_ms_from_the_steps_line"], abs=0.01)
    assert got["busy_s"] == pytest.approx(0.545600816, rel=1e-9)
    assert got["class_s"]["mosaic"] == pytest.approx(0.044997771, rel=1e-9)
    assert got["class_s"]["compute"] == pytest.approx(0.500603045, rel=1e-9)
    assert got["collective_s"] == 0
    # the device waits for the block's first dispatch, then never again
    assert got["idle_gaps"][0][0] == "dispatch"
    assert got["idle_share"] == pytest.approx(0.00693, abs=1e-5)
    assert got["device_ops"][0] == ["fusion bwd wi/dot_general x32",
                                    pytest.approx(0.0869903, rel=1e-6)]


def test_host_spans_are_put_on_the_trace_clock_by_the_quickest_launch():
    # host clock h (seconds) is trace clock (h - 100) seconds.  Three
    # fenced runs of the clock mark, which started on the device 700, 90
    # and 300 us after the host launched them
    def trace_ns(h):
        return (h - 100.0) * 1e9

    launches = [100.0010, 100.0040, 100.0070]
    mark = f"jit_{trace.CLOCK_MARK}(123)"
    device = [
        ("/device:TPU:0", "XLA Modules", mark, trace_ns(100.0017), 20e3),
        ("/device:TPU:0", "XLA Modules", mark, trace_ns(100.00409), 20e3),
        ("/device:TPU:0", "XLA Modules", mark, trace_ns(100.0073), 20e3),
        ("/device:TPU:0", "XLA Modules", "jit_step(1)", trace_ns(100.0100),
         5e6),
    ]
    spans = {"dispatch": [(100.0090, 0.002), (100.5, 0.002)],
             "read_loss": [(100.0120, 0.0035)], "next_batch": []}
    events = trace.host_events(device, spans, (100.0085, 100.0160), launches)
    got = {name: (start, duration) for _, _, name, start, duration in events}
    assert set(got) == {"traced_window", "dispatch", "read_loss"}
    # late by the quickest launch, 90 us, and by no more
    late = 90e3
    assert got["dispatch"][0] == pytest.approx(trace_ns(100.0090) + late,
                                               abs=100)
    assert got["dispatch"][1] == pytest.approx(2e6)
    assert got["traced_window"] == (
        pytest.approx(trace_ns(100.0085) + late, abs=100),
        pytest.approx(7.5e6))
    # a launch the trace does not hold, or none: nothing to tie with
    assert trace.host_events(device, spans, (100.0085, 100.0160),
                             launches[:2]) == []
    assert trace.host_events(device[3:], spans, (100.0085, 100.0160),
                             []) == []
