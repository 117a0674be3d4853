"""BENCHMARK.json against the contract, and every cell, configuration,
job and per-layer reader found by name.  No JAX device is touched."""

import importlib
import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, peaks  # noqa: E402

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
# the driver refused "share of peak": a unit is 1 to 16 of these
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# what `reduced` may never name (the contract's list of widths)
WIDTH = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_dim|d_model|d_ff|expansion|per_tok")


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert all((REPO / p).is_dir() for p in BENCH["paths"])
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_are_plain_and_used_once():
    names = CELLS + [c["name"] for c in BENCH["configs"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    whys = [x["why"] for x in BENCH["configs"] + BENCH["workloads"]]
    assert all(len(w) <= 200 for w in whys), [len(w) for w in whys]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for path in REPO.glob("benchmark/**/*"):
        rel = path.relative_to(REPO).as_posix()
        if "__pycache__" not in rel:
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_run_length_and_chip_counts_fit_the_check():
    seconds = BENCH["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    # a full check of 24 cells: 2 + 14 x 24 runs of run_seconds + 60 s,
    # 2 x 90 s a cell to compile, 1200 s spare, inside 43200 s
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 180 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)


def test_every_configuration_is_used_and_says_what_it_cut():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for entry in BENCH["configs"]:
        assert entry["name"] in used
        assert entry["source"].startswith("https://")
        assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
        config = json.loads((REPO / entry["file"]).read_text())
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        assert not [k for k in entry["reduced"] if WIDTH.search(k)]
        module = importlib.import_module(
            f"benchmark.configs.{entry['name']}")
        assert callable(module.build) and callable(module.parity_case)


def test_bounds_and_sources_are_the_contract_s():
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in ends and ends["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
    for m in BENCH["per_layer"]:
        assert "bound" not in m
        assert NAME.match(m["layer"]), m    # plain, as PERF.md section 3 has it
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in ends and m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files_that_exist(name):
    cell = cells.resolve(name)
    assert cell.job["chips"] == cell.chips
    assert cell.job["feed"] in ("host", "resident")
    assert cell.job["log_every"] >= 1 and cell.job["loss_margin"] >= 0
    ends = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in ends and cell.rate_metric in ends and "mfu" in ends
    assert cell.per_layer
    # the tiny preset overrides sizes, never adds a key of its own
    tiny = cells.resolve(name, rehearse=True)
    assert set(tiny.config) <= set(cell.config)
    assert set(tiny.job) <= set(cell.job)


@pytest.mark.parametrize("name", CELLS)
def test_benchmark_json_lists_a_reader_s_cells_as_its_applies_says(name):
    """The harness asks only ``applies``; the ``workloads`` lists of
    ``per_layer`` are for the driver, and are checked from it here."""
    cell = cells.resolve(name)
    ends = {m["name"] for m in cell.end_to_end}
    for entry in BENCH["per_layer"]:
        reader = importlib.import_module(
            f"benchmark.metrics.{entry['name']}")
        applies = reader.applies(cell.config, cell.job)
        assert applies == (name in entry.get("workloads", CELLS)), \
            entry["name"]
        if applies:       # reported only where the metric it moves is
            assert entry["moves"] in ends, entry["name"]


def test_every_reader_file_is_a_metric_of_benchmark_json():
    files = {p.stem for p in (REPO / "benchmark/metrics").glob("*.py")
             if p.stem != "__init__"}
    assert files == {m["name"] for m in BENCH["per_layer"]}


def test_result_line_has_exactly_the_contract_keys():
    from benchmark import run

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 15_100_000_000}
    line = run.result_line(True, 70, 0, {"setup_s": 12.3456789},
                           {"setup_s": "s"}, device)
    assert "\n" not in line
    assert json.loads(line) == {
        "correct": True, "attempted": 70, "failed": 0,
        "metrics": {"setup_s": {"value": 12.3456789, "unit": "s"}},
        "device": device}
    traced = json.loads(run.result_line(
        True, 70, 0, {}, {}, dict(device, busy_s=2.5, window_s=2.7),
        {"device_ops": [["fusion.1", 1.0]], "idle_gaps": []}))
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_peak_lookup_knows_the_v5e_and_refuses_any_other_device():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    for kind in ("TPU v5", "TPU v5p", "cpu", "TPU v5 lite "):
        with pytest.raises(KeyError, match="no published peaks"):
            peaks.lookup(kind)
