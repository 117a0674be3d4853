"""The expert layer's rows-to-tokens sum in the steps that run it,
compiled — not run — for a described v5e, and the steps that must not
notice it.

Both MoE cells at the published widths and the timed shape, depth cut
(``xing4`` ``* D * E``, ``nemotron3nano`` ``E M *``): an expert layer is
the sum's Mosaic call, ``moe_row_sum``, once a buffer size under
``moe/combine`` (the forward; the rematerialised forward's result is
not needed by the backward and goes) and once under ``moe/dispatch``
(the transpose of the gather), six in all; no ``scatter`` is left under
either path; the calls read the grouped matmuls' rows where they lie;
none is named for a kernel another reader reads by name; the traced
step says ``moe_row_sum_layers``.  The whole cells' lowered steps are
pinned by digest (the Mosaic calls' serialized bodies left out: those
carry source lines): the two MoE cells' re-pinned here, the steps with
no expert layer equal to the parent's.  Nothing here is a time or a
measurement.

Three tests under ``tests/benchmark_harness/`` hold a census of Mosaic
calls that was true before later kernels and may not be edited by the
PR that makes it stale (PERF.md §7, edits 12 and 14); this file holds
the sentence that is true of the expert layer now.

The topology is described inside a fixture of this one file, never while
a module is imported (only one process a machine may load libtpu).
"""

import hashlib
import re
import sys
from collections import Counter
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
for entry in (str(REPO), str(REPO / "tests")):
    if entry not in sys.path:
        sys.path.insert(0, entry)

from benchmark import hlo, modules  # noqa: E402

from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402
from test_hc_offchip_compile import _step_and_arguments  # noqa: E402

# (cut, tokens, d, top_k)
CELLS = {
    "xing4-s4096-b1": (dict(layers="*D*E", num_layers=2), 4096, 3584, 4),
    "nemotron3nano-s8192-b1": (dict(layers="EM*", num_layers=3),
                               8192, 2688, 6),
}

# sha256 of the lowered (StableHLO) step of the whole cell on one
# described chip, every ``backend_config`` emptied.  The two MoE cells
# are re-pinned in PR 39, which means to alter them (the sums became
# ``moe_row_sum``; until then 252ea06d…65b5e5 and 859d76b3…37afa6); the
# three with no expert layer are the parent's (PR 38's), as
# tests/test_hc_offchip_compile.py and tests/test_mamba_offchip_compile.py
# pin them
STEPS = {
    "xing4-s4096-b1": 
        "24ba1fec2e5e2dc50ee468b1ed30ec8c551b4af12ce768d2d295d6268318eeaa",
    "nemotron3nano-s8192-b1": 
        "8e08fb60d344b62d221080fb8222a6c04b0350074bdf409095fc447adebbbd91",
    "granite4hmicro-s8192-b1":
        "b35714722f59a04800de6a2219e09aeebdab414d6fef6e9f97b36476dcfedd6e",
    "lm871m-s1024-b6":
        "3232ba4861df87a08f50356b704394f74c21b7a9c79a2f71461ded4eed5c21f0",
    "lm871m-s4096-b1":
        "d8f074aebdb392814a23adfbe63199446944b053e6f2b18418732c8ccc49c360",
}
EXPERT_LAYERS = {"xing4-s4096-b1": 4, "nemotron3nano-s8192-b1": 4}


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def kernels_selected(monkeypatch):
    """The default backend here is the CPU; the program asks it whether
    to select its TPU kernels.  The test answers for the described chip."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)


def _lowering_said(since):
    from horovod_tpu import telemetry

    return [s for s in telemetry.spans.snapshot(since=since)
            if s.name == "train_step.lower"][-1].attrs


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_an_expert_layer_is_six_sums_and_no_scatter(topo, kernels_selected,
                                                    cell):
    from horovod_tpu import telemetry

    cut, tokens, d, top_k = CELLS[cell]
    layers = cut["layers"].count("E")
    step, args = _step_and_arguments(topo, cell, **cut)
    since = telemetry.spans._now()
    text = step.compiled_text(*args)
    assert _lowering_said(since)["moe_row_sum_layers"] == layers

    mosaic = {hlo._OP_RE.match(ln).group(1): ln
              for ln in hlo.mosaic_lines(text)}
    by_name = Counter(re.sub(r"\.\d+$", "", name) for name in mosaic)
    # a buffer size: the combine forward, the dispatch's transpose
    caps = sorted({tokens * m for m in (1, 2, top_k)})
    assert by_name["moe_row_sum"] == 2 * len(caps) * layers
    # the grouped matmuls beside them are the ones the step had
    assert (by_name["gmm"], by_name["tgmm"]) == (12 * layers, 6 * layers)
    known = modules.read_step(text)
    under = Counter()
    for name, line in mosaic.items():
        if not name.startswith("moe_row_sum"):
            continue
        # no reader that goes by a kernel's name takes it for its own
        assert not name.startswith(
            ("gmm", "tgmm", "flash_", "ssd_", "hc_", "mamba_"))
        path = hlo._SOURCE_RE.search(line).group(1)
        parts = path.split("/")
        assert "moe" in parts, (name, path)
        side = "combine" if "combine" in parts else "dispatch"
        assert side in parts
        # the combine is the forward's, the dispatch's the backward's
        assert ("transpose(" in path) == (side == "dispatch"), (name, path)
        assert known[name][0] == "moe"          # moe_ms reads it
        # the rows where they lie, in the compute type, never fp32
        rows = re.search(rf"bf16\[(\d+),{d}\]", line.split("custom-call(")[1])
        assert rows and int(rows.group(1)) in caps, name
        assert f"f32[{tokens},{d}]" not in line
        under[side, int(rows.group(1))] += 1
        # no copy and no fusion of XLA's stands between the grouped
        # matmul and the sum: the rows are the kernel's result itself, or
        # the ``reduce-precision`` jax.checkpoint lays on a kept result
        feeds = hlo._OPERAND_RE.findall(line.split("custom-call(")[1]
                                        .split(")")[0])[-1]
        assert feeds.startswith(("gmm", "tgmm", "reduce_precision")), \
            (name, feeds)
    assert under == Counter({(side, cap): layers
                             for side in ("combine", "dispatch")
                             for cap in caps})
    # no scatter over the buffer's rows is left under either path: the
    # one the expert layer keeps hands ``dw`` back to (tokens, top_k)
    for line in text.splitlines():
        m = hlo._OP_RE.match(line)
        source = hlo._SOURCE_RE.search(line)
        if m is None or source is None or m.group(3) != "scatter":
            continue
        parts = source.group(1).split("/")
        if "moe" in parts and ("combine" in parts or "dispatch" in parts):
            assert f",{d}]" not in m.group(2), line[:300]
    assert hlo.collective_bytes(text, 1) == 0 and not hlo.collectives(text)


@pytest.mark.parametrize("tokens,d,top_k", [
    (4096, 3584, 4), (8192, 2688, 6)])
def test_the_call_asks_for_vmem_the_chip_has(tokens, d, top_k):
    """At the cells' tile (256 tokens): above Mosaic's default scope, so
    the call asks; under half a v5e's 128 MiB.  That the compiler takes
    it at this limit is the test above."""
    assert pk.moe_row_sum_tile(tokens, d, top_k, 8, 2) == 256
    need = pk._row_sum_vmem_bytes(256, d, top_k, 8, 2)
    assert pk._MOSAIC_VMEM_SCOPE < need < (128 << 20) // 2


@pytest.mark.parametrize("cell", sorted(STEPS))
def test_the_whole_cells_lower_to_their_pinned_steps(topo, kernels_selected,
                                                     cell):
    from horovod_tpu import telemetry

    step, args = _step_and_arguments(topo, cell)
    since = telemetry.spans._now()
    with telemetry.span("probe"), step._ambient_mesh():
        text = step._step.lower(*args).as_text()
    assert ("moe_row_sum" in text) == (cell in EXPERT_LAYERS)
    said = [s for s in telemetry.spans.snapshot(since=since)
            if s.name == "probe"][-1].attrs
    assert said.get("moe_row_sum_layers", 0) == EXPERT_LAYERS.get(cell, 0)
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    text = re.sub(r"backend_config = \{[^\n]*\}", "backend_config = {}",
                  text)
    assert hashlib.sha256(text.encode()).hexdigest() == STEPS[cell]
