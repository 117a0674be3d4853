#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

One process: it loads, warms up, measures one window and prints, as the
last line of standard output, one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``).  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of one block
of steps.  Without a TPU, or with another number of chips than the cell
asks for, it exits non-zero and prints no result.  ``--rehearse`` runs
the cell's tiny ``rehearsal`` preset on the CPU to debug the harness; it
measures nothing and prints no result line either.
"""

import time

PROCESS_T0 = time.perf_counter()    # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):       # started as a file: import as a package
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import cells  # noqa: E402


def end_to_end_values(obs, facts) -> dict:
    """Every end-to-end metric the harness knows how to take."""
    job, built = obs.cell.job, obs.built
    units = len(obs.block_seconds) * obs.steps_per_block \
        * job["batch_per_chip"] * built.units_per_sample    # a chip
    rate = units / obs.window_s
    values = {obs.cell.rate_metric: rate, "setup_s": obs.setup["setup_s"]}
    if obs.peaks:
        values["mfu"] = rate * built.flops_per_unit \
            / obs.peaks["bf16_flops_per_s"]
    if facts["footprint_bytes"]:
        values["hbm_gb_per_chip"] = \
            max(facts["footprint_bytes"].values()) / 1e9
    return values


def per_layer_values(obs) -> dict:
    """Each per-layer metric of the cell from its reader,
    ``metrics/<name>.py``; a reader that finds nothing is left out."""
    values = {}
    for entry in obs.cell.per_layer:
        reader = importlib.import_module(
            f"benchmark.metrics.{entry['name']}")
        if not reader.applies(obs.cell.config, obs.cell.job):
            continue
        value = reader.read(obs)
        if value is not None:
            values[entry["name"]] = value
    return values


def result_line(correct: bool, attempted: int, failed: int, values: dict,
                units: dict, device: dict, breakdown=None) -> str:
    """The last line of standard output, with exactly the contract's
    keys.  A value is a number as measured, with all its digits."""
    out = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": float(value), "unit": units[name]}
                    for name, value in values.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    return json.dumps(out)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="debug the harness on the CPU at the cell's "
                             "tiny preset; no result line")
    args = parser.parse_args(argv)
    cell = cells.resolve(args.workload, rehearse=args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else cells.load_benchmark()["run_seconds"]

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"benchmark: cell {cell.name}, seed {args.seed}, {seconds}s, "
          f"trace {args.trace}; jax {jax.__version__} on {device}",
          flush=True)
    wanted = "cpu" if args.rehearse else "tpu"
    if device["platform"] != wanted or device["count"] != cell.chips:
        print(f"benchmark: cell {cell.name} "
              f"{'rehearses on' if args.rehearse else 'needs'} "
              f"{cell.chips} {wanted} device(s); JAX reports {device} — "
              f"no result", file=sys.stderr)
        return 2

    from benchmark import loop

    obs, facts = loop.run_cell(cell, args.seed, seconds, bool(args.trace),
                               PROCESS_T0, on_chip=not args.rehearse)
    correct = all(facts["checks"].values())
    blocks, waits = obs.block_seconds, obs.stall_samples
    print(f"benchmark: checks {facts['checks']}; loss "
          f"{facts['first_block_loss']:.4f} (first block) -> "
          f"{facts['last_block_loss']:.4f} (last); {len(blocks)} blocks of "
          f"{obs.steps_per_block} steps in {obs.window_s:.3f}s, a block "
          f"min {min(blocks):.4f} median {statistics.median(blocks):.4f} "
          f"max {max(blocks):.4f}s" + (
              f"; input waits median {statistics.median(waits) * 1e3:.3f} "
              f"max {max(waits) * 1e3:.1f} sum {sum(waits) * 1e3:.1f} ms"
              if waits else ""), flush=True)

    if args.trace:
        values, entries = per_layer_values(obs), cell.per_layer
    else:
        known, entries = end_to_end_values(obs, facts), cell.end_to_end
        values = {m["name"]: known[m["name"]] for m in entries
                  if m["name"] in known}
    units = {m["name"]: m["unit"] for m in entries}
    if args.rehearse:
        print(f"benchmark: CPU rehearsal of the tiny preset finished — it "
              f"debugs the harness and measures nothing; correct "
              f"{correct}; metrics it would name: {sorted(values)}",
              flush=True)
        return 0 if correct else 1

    if not args.trace and len(values) < len(entries):
        raise RuntimeError(
            f"no value for end-to-end metric(s) "
            f"{[m['name'] for m in entries if m['name'] not in values]}")
    device["memory_peak_bytes"] = max(facts["footprint_bytes"].values())
    breakdown = None
    if args.trace:
        if not obs.trace:
            raise RuntimeError("the trace holds no device operation")
        device["busy_s"] = obs.trace["busy_s"]
        device["window_s"] = obs.trace["window_s"]
        breakdown = {"device_ops": obs.trace["device_ops"],
                     "idle_gaps": obs.trace["idle_gaps"]}
    print(result_line(correct, facts["attempted"], facts["failed"], values,
                      units, device, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
