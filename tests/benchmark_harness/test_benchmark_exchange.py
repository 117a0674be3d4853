"""``benchmark/exchange.py`` on a hand-written compiled step and trace
with known answers: the exchange read where a compiler fused it."""

import json
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import exchange, hlo  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    exchange_bytes,
    exchange_exposed_ms,
    exchange_ms,
)

FIXTURE = json.loads(
    (REPO / "benchmark" / "fixtures" / "handmade_exchange.json").read_text())
TEXT = "\n".join(FIXTURE["hlo"])
EVENTS = [tuple(e) for e in FIXTURE["events"]]
WANT = FIXTURE["expect"]


def test_a_channel_counts_once_whatever_number_of_lines_carry_it():
    assert hlo.collective_bytes(TEXT, 4) == WANT["bytes_by_line_over_4"]
    assert exchange.exchange_bytes(TEXT, 4) == WANT["bytes_over_4"]
    channels = exchange.read_step(TEXT)["channels"]
    assert len(channels) == WANT["channels"]
    assert sum(c["fused"] for c in channels.values()) == \
        WANT["fused_channels"]
    assert channels["channel 7"] == \
        {"bytes": 2048, "group_size": 4, "fused": True}


def test_which_operation_holds_a_channel_alone_and_which_shares_it():
    holds = exchange.read_step(TEXT)["holds"]
    assert sorted(n for n, (_, alone) in holds.items() if alone) == \
        WANT["alone"]
    assert sorted(n for n, (_, alone) in holds.items() if not alone) == \
        WANT["shared"]
    # a fusion without a collective is no one's: the trace reads it as
    # other work
    assert "fusion.1" not in holds and "fusion.9" not in holds
    assert holds["all-gather-done.1"][0] == holds["all-gather-start.1"][0]
    assert holds["fusion.2"][0] == holds["async-collective-done"][0] == \
        {"channel 7"}


def test_exposed_and_in_flight_a_run_of_the_step_on_the_slowest_device():
    got = exchange.reduce_events(EVENTS, exchange.read_step(TEXT)["holds"])
    assert got["runs"] == WANT["runs"]
    assert round(got["exchange_exposed_s"] * 1e9) == WANT["exposed_ns_a_run"]
    assert round(got["exchange_s"] * 1e9) == WANT["in_flight_ns_a_run"]


def test_a_step_without_collectives_or_a_trace_without_devices_reads_nothing():
    plain = "\n".join(ln for ln in FIXTURE["hlo"]
                      if "all-reduce" not in ln and "all-gather" not in ln)
    step = exchange.read_step(plain)
    assert step == {"channels": {}, "holds": {}}
    assert exchange.reduce_events(EVENTS, step["holds"]) == {}
    host_only = [("/host:CPU", "benchmark", "dispatch", 0, 10)]
    assert exchange.reduce_events(
        host_only, exchange.read_step(TEXT)["holds"]) == {}


def observed(tmp_path, monkeypatch, traced: bool):
    """What the readers take of ``loop.Observed``, with the profiler's
    file where ``loop`` would have had it written."""
    from benchmark import loop, trace

    monkeypatch.setattr(loop, "TRACE_ROOT", str(tmp_path))
    if traced:
        xplane = tmp_path / "a-cell" / "plugins" / "profile" / "1" / \
            "host.xplane.pb"
        xplane.parent.mkdir(parents=True)
        xplane.write_bytes(b"")
        monkeypatch.setattr(trace, "load_events", lambda path: EVENTS)
    exchange._reduced.cache_clear()
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name="a-cell", chips=4),
        hlo_text=TEXT, trace={"devices": 2} if traced else {})


def test_the_readers_take_milliseconds_a_step_and_bytes(tmp_path,
                                                        monkeypatch):
    obs = observed(tmp_path, monkeypatch, traced=True)
    assert exchange_exposed_ms.read(obs) == pytest.approx(
        WANT["exposed_ns_a_run"] / 1e6)
    assert exchange_ms.read(obs) == pytest.approx(
        WANT["in_flight_ns_a_run"] / 1e6)
    assert exchange_bytes.read(obs) == WANT["bytes_over_4"]


def test_the_readers_leave_the_metric_out_where_nothing_was_traced(
        tmp_path, monkeypatch):
    obs = observed(tmp_path, monkeypatch, traced=False)
    assert exchange_exposed_ms.read(obs) is None
    assert exchange_ms.read(obs) is None
    # the profiler wrote nothing where the reducer found device events
    obs.trace = {"devices": 2}
    assert exchange_ms.read(obs) is None
    obs.hlo_text = ""
    assert exchange_bytes.read(obs) is None


@pytest.mark.parametrize("reader", [exchange_ms, exchange_exposed_ms,
                                    exchange_bytes])
def test_the_readers_apply_where_the_cell_has_more_than_one_chip(reader):
    assert reader.applies({}, {"chips": 4})
    assert not reader.applies({}, {"chips": 1})
