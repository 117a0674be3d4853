"""The flight recorder (horovod_tpu/telemetry/spans.py, docs/metrics.md
"Spans"): the ring itself, and the spans the train step, the compile
path and the input pipeline record into it."""

import statistics
import sys
import threading
import time

import jax.numpy as jnp
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import telemetry
from horovod_tpu.data.prefetch import PrefetchIterator
from horovod_tpu.telemetry import spans
from horovod_tpu.utils.timeline import load_trace


def _since(t0, name=None):
    """The spans the code under test opened (a collection that paused
    the interpreter meanwhile records one of its own, ``host.gc``)."""
    return [s for s in spans.snapshot(since=t0)
            if (s.name != "host.gc" if name is None else s.name == name)]


def test_parent_from_nesting_and_from_an_explicit_cause():
    t0 = time.perf_counter()
    with telemetry.span("outer", seq=7) as outer:
        with telemetry.span("inner") as inner:
            pass
    got = {}

    def elsewhere():
        with telemetry.span("caused", parent=outer.id) as s:
            got["id"] = s.id

    worker = threading.Thread(target=elsewhere, name="spans-test-worker")
    worker.start()
    worker.join(timeout=10)
    by_name = {s.name: s for s in _since(t0)}
    assert by_name["outer"].parent is None
    assert by_name["inner"].parent == outer.id
    assert by_name["inner"].seq == 7            # inherited
    assert by_name["caused"].parent == outer.id
    assert by_name["caused"].thread == "spans-test-worker"
    assert by_name["outer"].thread == threading.current_thread().name
    assert by_name["outer"].start <= by_name["inner"].start \
        <= by_name["inner"].end <= by_name["outer"].end
    assert inner.seconds == by_name["inner"].end - by_name["inner"].start


def test_a_span_an_exception_ends_is_recorded_with_its_error():
    t0 = time.perf_counter()
    with pytest.raises(KeyError):
        with telemetry.span("fails", attrs={"k": 1}):
            raise KeyError("x")
    with telemetry.span("after"):
        pass
    failed, after = _since(t0)
    assert failed.attrs == {"k": 1, "error": "KeyError"}
    assert after.parent is None                 # the stack was unwound


def test_ring_wraps_at_capacity_and_snapshot_stays_ordered():
    import gc

    t0 = time.perf_counter()
    gc.disable()        # no collection, so no host.gc span among them
    try:
        for i in range(spans.CAPACITY + 10):
            with telemetry.span("fill", seq=i):
                pass
    finally:
        gc.enable()
    held = spans.snapshot()
    assert len(held) == spans.CAPACITY
    assert [s.seq for s in held] == list(range(10, spans.CAPACITY + 10))
    assert all(a.start <= b.start for a, b in zip(held, held[1:]))
    assert held[0].start >= t0


def test_snapshot_keeps_what_overlaps_the_interval():
    marks = []
    for name in ("before", "across", "inside", "after"):
        with telemetry.span(name) as s:
            pass
        marks.append(s)
    before, across, inside, after = marks
    since = (across.start + across.end) / 2
    until = (inside.end + after.start) / 2
    names = [s.name for s in spans.snapshot(since=since, until=until)]
    assert names == ["across", "inside"]
    assert [s.name for s in spans.snapshot(since=after.start)] == ["after"]


def test_disable_records_nothing_and_still_times():
    t0 = time.perf_counter()
    spans.disable()
    try:
        with telemetry.span("unrecorded") as s:
            pass
    finally:
        spans.enable()
    with telemetry.span("recorded"):
        pass
    assert [x.name for x in _since(t0)] == ["recorded"]
    assert s.seconds >= 0.0


@pytest.fixture
def slow_clock(monkeypatch):
    """The recorder's clock, made to run ``step[0]`` seconds on at every
    reading: a forced collection then lasts what the test says."""
    step, now = [0.0], [time.perf_counter()]

    def clock():
        now[0] += step[0]
        return now[0]

    monkeypatch.setattr(spans, "_now", clock)
    return step


def _collections(t0):
    return [s for s in spans.snapshot(since=t0) if s.name == "host.gc"]


def test_a_collection_at_the_floor_is_a_span_and_a_shorter_one_is_not(
        slow_clock):
    import gc

    t0 = spans._now()
    slow_clock[0] = spans.GC_FLOOR_S / 4     # start -> stop: under the floor
    gc.collect(1)
    assert not _collections(t0)
    slow_clock[0] = spans.GC_FLOOR_S         # start -> stop: the floor
    gc.collect(2)
    slow_clock[0] = 0.0
    (pause,) = _collections(t0)
    assert pause.end - pause.start == pytest.approx(spans.GC_FLOOR_S)
    assert pause.attrs["generation"] == 2 and pause.attrs["collected"] >= 0
    assert pause.parent is None and pause.seq is None
    assert pause.thread == threading.current_thread().name
    # no span of the thread's own is its parent, open or not
    with telemetry.span("around") as around:
        slow_clock[0] = spans.GC_FLOOR_S
        gc.collect(0)
        slow_clock[0] = 0.0
    inside = _collections(around.start)[-1]
    assert inside.attrs["generation"] == 0 and inside.parent is None


def test_disable_stops_the_collector_s_spans_and_nothing_of_the_collector(
        slow_clock):
    import gc

    assert gc.callbacks.count(spans._on_gc) == 1
    before = (gc.isenabled(), gc.get_threshold(), gc.get_freeze_count())
    t0 = spans._now()
    slow_clock[0] = spans.GC_FLOOR_S
    spans.disable()
    try:
        assert spans._on_gc not in gc.callbacks     # the hook is gone too
        gc.collect()
    finally:
        spans.enable()
        spans.enable()
    assert gc.callbacks.count(spans._on_gc) == 1
    assert not _collections(t0)
    gc.collect()
    slow_clock[0] = 0.0
    assert len(_collections(t0)) == 1
    assert (gc.isenabled(), gc.get_threshold(),
            gc.get_freeze_count()) == before


def test_a_collection_inside_the_timeline_s_put_hangs_nothing(
        tmp_path, slow_clock):
    """The collector runs its callbacks wherever an allocation trips it
    — inside ``Queue.put`` with the queue's lock held, too.  The hook
    touches no timeline: the ring holds the span, the trace no lane."""
    import gc
    from horovod_tpu.runtime import state as rt_state

    hvd.init()
    path = str(tmp_path / "timeline.json")
    hvd.start_timeline(path)
    try:
        t0 = spans._now()
        slow_clock[0] = spans.GC_FLOOR_S
        with rt_state._state.timeline._queue.mutex:
            gc.collect(2)
        slow_clock[0] = 0.0
        with telemetry.span("beside"):
            pass
    finally:
        hvd.stop_timeline()
        hvd.shutdown()
    (pause,) = _collections(t0)
    assert pause.attrs["generation"] == 2
    events = load_trace(path)
    assert not [e for e in events
                if "host.gc" in (e.get("tid"), e.get("name"))]
    assert [e["ph"] for e in events if e.get("name") == "beside"] == ["B"]


def test_a_span_costs_under_five_microseconds():
    """The budget is 1.5 us a span on this host (measured 1.0-1.3,
    docs/metrics.md); pinned where the registry's disabled path is."""
    def once(n=20000):
        t0 = time.perf_counter()
        for i in range(n):
            with telemetry.span("cost", seq=i):
                pass
        return (time.perf_counter() - t0) / n

    assert statistics.median(once() for _ in range(5)) < 5e-6


def test_threads_record_and_snapshot_together_without_loss():
    """More threads than cores, a short switch interval: every span of
    every thread is in the ring once, under its own thread's name, while
    another thread takes snapshots."""
    threads, each = 32, 500
    stop = threading.Event()
    taken = []

    def record(k):
        for i in range(each):
            with telemetry.span("stress", seq=k * each + i):
                with telemetry.span("stress.inner"):
                    pass

    def take():
        while not stop.is_set():
            taken.append(len(spans.snapshot()))

    t0 = time.perf_counter()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        taker = threading.Thread(target=take)
        taker.start()
        workers = [threading.Thread(target=record, args=(k,),
                                    name=f"stress-{k}")
                   for k in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        stop.set()
        taker.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not taker.is_alive() and not any(w.is_alive() for w in workers)
    outer = _since(t0, "stress")
    inner = _since(t0, "stress.inner")
    assert sorted(s.seq for s in outer) == list(range(threads * each))
    assert len({s.id for s in outer + inner}) == 2 * threads * each
    assert all(s.thread == f"stress-{s.seq // each}" for s in outer)
    by_id = {s.id: s for s in outer}
    assert all(by_id[s.parent].seq == s.seq for s in inner)
    assert taken


def test_seq_and_cause_through_a_real_prefetch_iterator():
    t0 = time.perf_counter()
    feed = PrefetchIterator(iter(range(6)), place=lambda x: x * 10,
                            depth=2, threads=2, name="spans")
    try:
        assert list(feed) == [0, 10, 20, 30, 40, 50]
        stalls = list(feed.stall_samples)
    finally:
        feed.close()
    got = _since(t0)
    source = {s.seq: s for s in got if s.name == "input.source"}
    place = {s.seq: s for s in got if s.name == "input.place"}
    wait = [s for s in got if s.name == "input.wait" and not
            (s.attrs or {}).get("error")]
    assert sorted(place) == [s.seq for s in wait] == list(range(6))
    for seq in range(6):
        assert place[seq].parent == source[seq].id
        assert place[seq].start >= source[seq].end
        assert place[seq].thread.startswith("hvd-input-spans_")
        assert source[seq].thread == "hvd-input-spans-feeder"
    # one clock reading, two views: the wait span IS the stall sample
    assert [s.end - s.start for s in wait] == stalls
    assert all(0 <= s.attrs["depth"] <= 2 for s in wait)
    # the exhausted source and the delivery that found it say so
    assert source[6].attrs == {"error": "StopIteration"}
    assert [s.seq for s in got if s.name == "input.wait"
            and (s.attrs or {}).get("error") == "StopIteration"] == [6]


def _loss(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def _tiny_step():
    step = hvd.DistributedTrainStep(_loss, optax.sgd(0.1))
    params, opt = step.init({"w": jnp.ones((8, 4))})
    batch = step.shard_batch({"x": jnp.ones((16, 8)),
                              "y": jnp.zeros((16, 4))})
    return step, params, opt, batch


def _names(got):
    return sorted(s.name for s in got)


def test_train_step_spans_first_call_later_calls_and_a_warm_cache(cache_dir):
    step, params, opt, batch = _tiny_step()
    t0 = time.perf_counter()
    params, opt, _ = step(params, opt, batch)
    first = _since(t0)
    assert _names(first) == ["train_step.call", "train_step.compile",
                             "train_step.launch", "train_step.lower",
                             "train_step.prepare"]
    by_name = {s.name: s for s in first}
    call, prepare = by_name["train_step.call"], by_name["train_step.prepare"]
    assert call.seq == 0 and call.parent is None
    assert prepare.parent == by_name["train_step.launch"].parent == call.id
    assert by_name["train_step.lower"].parent == prepare.id
    assert by_name["train_step.compile"].parent == prepare.id
    compiled = by_name["train_step.compile"].attrs
    assert compiled["hit"] is False
    # how the exchange was compiled rides on the same span: the CPU's
    # all-reduce over the eight devices is synchronous, and no option
    # set is laid off a TPU
    assert compiled["exchange_ops"] >= 1
    assert compiled["exchange_async_ops"] == compiled["exchange_options"] == 0
    assert {s.seq for s in first} == {0}

    t1 = time.perf_counter()
    for _ in range(3):
        params, opt, _ = step(params, opt, batch)
    later = _since(t1)
    assert _names(later) == sorted(
        ["train_step.call", "train_step.prepare", "train_step.launch"] * 3)
    calls = {s.id: s for s in later if s.name == "train_step.call"}
    assert sorted(s.seq for s in calls.values()) == [1, 2, 3]
    for s in later:
        if s.name != "train_step.call":
            outer = calls[s.parent]
            assert s.seq == outer.seq
            assert outer.start <= s.start <= s.end <= outer.end

    t2 = time.perf_counter()
    fresh, params, opt, batch = _tiny_step()
    fresh(params, opt, batch)
    assert fresh.compile_cache_hit is True
    # a hit reports what the miss counted, read from its own executable
    assert [s.attrs for s in _since(t2, "train_step.compile")] == \
        [{**compiled, "hit": True}]


def test_step_seconds_histogram_observes_the_spans_own_duration(cache_dir):
    step, params, opt, batch = _tiny_step()
    telemetry.enable()
    try:
        params, opt, _ = step(params, opt, batch)
        before = telemetry.snapshot()["histograms"]["hvd_step_seconds"]
        t0 = time.perf_counter()
        step(params, opt, batch)
        after = telemetry.snapshot()["histograms"]["hvd_step_seconds"]
    finally:
        telemetry.disable()
    by_name = {s.name: s for s in _since(t0)}
    assert after["count"] == before["count"] + 1
    assert after["sum"] - before["sum"] == pytest.approx(
        by_name["train_step.launch"].end - by_name["train_step.call"].start,
        abs=1e-9)


def test_timeline_shows_the_steps_host_side(cache_dir, tmp_path):
    step, params, opt, batch = _tiny_step()
    path = str(tmp_path / "timeline.json")
    hvd.start_timeline(path)
    try:
        step(params, opt, batch)
    finally:
        hvd.stop_timeline()
    events = load_trace(path)
    lane = threading.current_thread().name
    begun = {(e["tid"], e["name"]) for e in events if e["ph"] == "B"}
    assert {(lane, "train_step.call"), (f"{lane}/1", "train_step.prepare"),
            (f"{lane}/2", "train_step.lower"),
            (f"{lane}/2", "train_step.compile"),
            (f"{lane}/1", "train_step.launch")} <= begun
    for tid in (lane, f"{lane}/1", f"{lane}/2"):
        assert sum(e["ph"] == "B" for e in events if e["tid"] == tid) == \
            sum(e["ph"] == "E" for e in events if e["tid"] == tid)
