"""The job every cell runs: set up, warm up, measure one window.

The five-line recipe with its defaults — ``hvd.init()`` →
``DistributedTrainStep(loss_fn, optimizer, **job["train_step"])`` →
``step.init`` → one ``step(params, opt_state, batch)`` a *fresh* batch.
No fence a step: the losses of a block of ``log_every`` steps are read
together at its end, as a training loop logs them.  The window opens on
a fence after warm-up and closes at the first block boundary past
``seconds``.  A rate is the units of the window's whole blocks over the
window's time, stalls and all: a user pays wall time.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
import math
import os
import shutil
import time

import jax
import numpy as np

from benchmark import hlo, parity, peaks, trace
from benchmark.cells import ROOT, Cell
from benchmark.feed import make_feed
from benchmark.observe import (
    CompileCounter,
    Spans,
    device_footprint_bytes,
    devices_holding,
)

TRACE_ROOT = os.path.join(ROOT, ".benchmark_out", "trace")
# with --trace 1 the profiler is on for this one block of the window
TRACED_BLOCK = 1


@dataclasses.dataclass
class Observed:
    """What a run saw; the per-layer readers take their metric from it."""

    cell: Cell
    built: object
    peaks: dict                 # of this device_kind; {} in a rehearsal
    setup: dict                 # seconds by phase of set-up
    steps_per_block: int = 0
    block_seconds: tuple = ()   # of every block of the window
    window_s: float = 0.0       # first block's start to last block's end
    traced_steps: int = 0
    spans: Spans = dataclasses.field(default_factory=Spans)
    stall_samples: tuple = ()
    hlo_text: str = ""
    trace: dict = dataclasses.field(default_factory=dict)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             process_t0: float, on_chip: bool):
    """Returns ``(observed, facts)``: ``facts`` holds ``checks`` (name →
    bool, all of which make ``correct``), the losses read, step counts and
    the device footprint."""
    import horovod_tpu as hvd

    def say(msg: str) -> None:          # with the seconds since the start
        print(f"benchmark: [{time.perf_counter() - process_t0:6.1f}s] {msg}",
              flush=True)

    compiles = CompileCounter()
    hvd.init()
    say("hvd.init() done")
    chips = cell.chips
    if hvd.size() != chips:
        raise RuntimeError(f"hvd.size() is {hvd.size()}, the cell asks "
                           f"for {chips}")
    config, job = cell.config, cell.job
    module = importlib.import_module(f"benchmark.configs.{config['name']}")
    checks, setup = {}, {}

    t = time.perf_counter()
    facts = {"parity": parity.check(
        hvd, module.parity_case(config, job, chips, seed),
        job["train_step"], chips, seed)}
    checks["parity_with_plain_reference"] = facts["parity"]["ok"]
    setup["parity_s"] = time.perf_counter() - t
    say(f"parity {facts['parity']}")

    t = time.perf_counter()
    built = module.build(config, job, chips, seed)
    step = hvd.DistributedTrainStep(built.loss_fn, built.optimizer,
                                    **job["train_step"])
    params, opt_state = step.init(
        jax.jit(built.init)(jax.random.PRNGKey(seed)))
    global_batch = job["batch_per_chip"] * chips
    feed = make_feed(job, built.sample, step, seed, global_batch)
    jax.block_until_ready((params, opt_state))
    setup["build_s"] = time.perf_counter() - t
    say("model, optimizer state and data are on the device")

    obs = Observed(cell=cell, built=built, setup=setup,
                   peaks=peaks.lookup(jax.devices()[0].device_kind)
                   if on_chip else {})
    try:
        t = time.perf_counter()
        batch = next(feed)
        checks["batch_on_every_device"] = \
            len(devices_holding(batch)) == chips
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready(loss)
        setup["first_step_s"] = time.perf_counter() - t

        # the compiled step's text costs a second lowering (1.5-2.8 s,
        # chip runs, PR 22): taken only where something reads it, the
        # check of the kernels' operands or a traced run's readers
        if traced or config["kernels"]:
            t = time.perf_counter()
            obs.hlo_text = step.compiled_text(params, opt_state, batch)
            setup["compiled_text_s"] = time.perf_counter() - t
            mosaic = hlo.mosaic_lines(obs.hlo_text)
            if built.kernel_operand is not None and on_chip:
                checks["mosaic_calls_on_per_chip_operands"] = \
                    bool(mosaic) and \
                    all(built.kernel_operand in ln for ln in mosaic)
            say(f"the compiled step holds {len(mosaic)} Mosaic call(s)")
        del batch

        for _ in range(job["warmup_steps"]):
            params, opt_state, loss = step(params, opt_state, next(feed))
        jax.block_until_ready((params, opt_state, loss))
        say(f"warm; the window opens; set-up so far {setup}")

        window = _window(step, feed, (params, opt_state), cell, seconds,
                         traced, compiles, on_chip)
        del params, opt_state
    finally:
        feed.close()
    setup["setup_s"] = window["opened"] - process_t0
    obs.steps_per_block = job["log_every"]
    obs.block_seconds = tuple(window["block_seconds"])
    obs.window_s = window["seconds"]
    obs.spans, obs.stall_samples = window["spans"], window["stalls"]

    losses = window["block_losses"]
    flat = [x for block in losses for x in block]
    checks["nothing_compiled_in_the_window"] = window["compiled"] == 0
    checks["every_loss_finite"] = all(math.isfinite(x) for x in flat)
    checks["loss_fell"] = len(losses) >= 2 and \
        float(np.mean(losses[-1])) < \
        float(np.mean(losses[0])) - job["loss_margin"]
    facts.update(
        checks=checks, attempted=len(flat),
        failed=sum(not math.isfinite(x) for x in flat),
        first_block_loss=float(np.mean(losses[0])),
        last_block_loss=float(np.mean(losses[-1])),
        footprint_bytes=window["footprint"])
    if traced:
        if window["traced_block"] is None:
            raise RuntimeError("--trace 1, but the window closed before "
                               f"block {TRACED_BLOCK} could be traced")
        obs.traced_steps = job["log_every"]
        events = trace.load_events(trace.newest_xplane(window["trace_dir"]))
        events += trace.host_events(events, window["spans"].records,
                                    window["traced_block"],
                                    window["launches"])
        obs.trace = trace.reduce_trace(events, hlo.op_classes(obs.hlo_text))
    hvd.shutdown()
    return obs, facts


def benchmark_clock_mark(x):
    """The tiny program whose fenced runs tie the host's clock to the
    trace's (``trace.host_events``); its name is ``trace.CLOCK_MARK``."""
    return x + 1


@contextlib.contextmanager
def _tracing(trace_dir: str, mark, launches: list):
    """The profiler on: the device's operations only.  The host tracer
    stays off (``observe.Spans``), so does the Python tracer; a few
    fenced runs of ``mark`` go first, the host's time before each into
    ``launches``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(5):
            launches.append(time.perf_counter())
            jax.block_until_ready(mark())
        yield
    finally:
        jax.profiler.stop_trace()


def _window(step, feed, state, cell, seconds, traced, compiles, on_chip):
    """The measured window.  ``state`` is ``(params, opt_state)``."""
    params, opt_state = state
    log_every = cell.job["log_every"]
    spans = Spans()
    trace_dir = os.path.join(TRACE_ROOT, cell.name)
    block_losses, blocks, traced_block, launches = [], [], None, []
    if traced:                       # compiled here, before the window
        zero = jax.numpy.zeros((), jax.numpy.int32)
        mark = functools.partial(jax.jit(benchmark_clock_mark), zero)
        jax.block_until_ready(mark())
    compiled_before = compiles.count
    stalls_before = len(feed.stall_samples)
    opened = closed = time.perf_counter()
    while closed - opened < seconds:
        with contextlib.ExitStack() as profiler:
            tracing = traced and len(blocks) == TRACED_BLOCK
            if tracing:
                profiler.enter_context(_tracing(trace_dir, mark, launches))
            started = time.perf_counter()
            pending = []
            for _ in range(log_every):
                with spans.span("next_batch"):
                    batch = next(feed)
                with spans.span("dispatch"):
                    params, opt_state, loss = step(params, opt_state, batch)
                pending.append(loss)
            with spans.span("read_loss"):
                block_losses.append(
                    [float(x) for x in jax.device_get(pending)])
            closed = time.perf_counter()
            blocks.append(closed - started)
            if tracing:
                traced_block = (started, closed)
    return {
        "opened": opened, "seconds": closed - opened,
        "block_seconds": blocks, "block_losses": block_losses,
        "traced_block": traced_block, "trace_dir": trace_dir,
        "spans": spans, "launches": launches,
        "stalls": tuple(feed.stall_samples[stalls_before:]),
        "compiled": compiles.count - compiled_before,
        # read while the device still holds the training state
        "footprint": device_footprint_bytes() if on_chip else {},
    }
