"""The Mamba-2 mixer's elementwise kernels in the steps that run them,
compiled — not run — for a described v5e, and the steps that must not
notice them.

Both Mamba cells at the published widths and the timed shape (8,192 x
1), depth cut (``nemotron3nano`` ``E M *``, ``granite4hmicro`` ``M D * D``):
a Mamba layer is the convolution's call forward, again in the
rematerialised block, and once backward, the gated norm's likewise,
beside the scan's three; every new call stands under ``mamba/conv`` or
``mamba/gated_norm`` (the backward's too: ``ssm_ms`` and
``mamba_pointwise_ms`` find them by path), none is named for a kernel
another reader reads by name, they ask for VMEM the chip has, and the
convolution writes no fp32 array of ``xBC``'s size to HBM.  The steps
with no Mamba layer (``xing4``, ``lm871m``) lower as they did before the
kernels (a digest, with the Mosaic calls' serialized bodies left out:
those carry source lines).  Nothing here is a time or a measurement.

Two tests under ``tests/benchmark_harness/`` hold the sentence that was
true before these kernels ("three kinds of Mosaic call and no fourth")
and may not be edited by the PR that makes it stale (PERF.md §7, edit
14); this file holds the one that is true now.

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
from benchmark.metrics import mamba_pointwise_ms  # noqa: E402

from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402
from test_hc_offchip_compile import _step_and_arguments  # noqa: E402

V5E_VMEM = 128 << 20
NEW = ("mamba_conv_fwd", "mamba_conv_bwd", "mamba_gated_norm_fwd",
       "mamba_gated_norm_bwd")

# (cut, xBC's channels, the calls beside the Mamba layer's nine)
# (flash forward once: the attention block keeps the call's results; it
# ran twice while the block kept nothing)
CELLS = {
    "nemotron3nano-s8192-b1": (
        dict(layers="EM*", num_layers=3), 6144,
        # since PR 39 the expert layer's six rows-to-tokens sums too
        {"flash_fwd": 1, "flash_bwd": 1, "gmm": 12, "tgmm": 6,
         "moe_row_sum": 6}),
    "granite4hmicro-s8192-b1": (
        dict(layers="MD*D", num_layers=2), 4352,
        {"flash_fwd": 1, "flash_bwd": 1}),
}

# sha256 of the lowered (StableHLO) step of the whole cell on one
# described chip, every ``backend_config`` emptied: equal to the digests
# of the commit before the Mamba kernels (PR 37).  These steps hold no
# Mamba layer; a change meant to alter them re-pins and says so:
# ``xing4`` re-pinned in PR 38, which means to alter it — its ``D``
# sublayer's backward is written out (``models/hybrid.gated_mlp``; until
# then 4aecc950…bdc16d) — and again in PR 39, which means to alter it: its
# expert layers' rows-to-tokens sums became ``moe_row_sum`` (until then
# 252ea06d…65b5e5), and again when its attention blocks came to keep
# the flash forward's results and its expert buffers one set of slots,
# which means to alter it (until then 24ba1fec…18eeaa); the two
# ``lm871m`` steps are as they were before the Mamba kernels
NO_MAMBA_STEPS = {
    "xing4-s4096-b1":
        "41d584140da8cbc467afd0c1b8223cc1f7e4e9b7fe9f418d2b6580d0d00ab046",
    "lm871m-s1024-b6":
        "3232ba4861df87a08f50356b704394f74c21b7a9c79a2f71461ded4eed5c21f0",
    "lm871m-s4096-b1":
        "d8f074aebdb392814a23adfbe63199446944b053e6f2b18418732c8ccc49c360",
}


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


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_mamba_layer_is_nine_calls_under_their_scopes(
        topo, kernels_selected, cell):
    from horovod_tpu import telemetry

    cut, channels, others = CELLS[cell]
    mamba_layers = cut["layers"].count("M")
    step, args = _step_and_arguments(topo, cell, **cut)
    since = telemetry.spans._now()
    text = step.compiled_text(*args)
    lowering = [s for s in telemetry.spans.snapshot(since=since)
                if s.name == "train_step.lower"][-1]
    assert lowering.attrs["mamba_fused_layers"] == mamba_layers
    assert lowering.attrs["mamba_conv_channels"] == channels
    assert lowering.attrs["ssd_impl"] == "mosaic"

    mosaic = {hlo._OP_RE.match(ln).group(1): ln
              for ln in hlo.mosaic_lines(text)}
    by_name = Counter(re.sub(r"\.\d+$", "", name) for name in mosaic)
    # forward, the forward again in the rematerialised block, backward
    assert {name: by_name[name] for name in NEW + ("ssd_fwd", "ssd_bwd")} \
        == {"mamba_conv_fwd": 2 * mamba_layers,
            "mamba_conv_bwd": mamba_layers,
            "mamba_gated_norm_fwd": 2 * mamba_layers,
            "mamba_gated_norm_bwd": mamba_layers,
            "ssd_fwd": 2 * mamba_layers, "ssd_bwd": mamba_layers}
    # the calls beside them are the ones the step had
    assert {name: n for name, n in by_name.items()
            if not name.startswith(("mamba_", "ssd_"))} == others
    known = modules.read_step(text)
    pointwise = mamba_pointwise_ms._pointwise(text)
    for name, line in mosaic.items():
        kind = re.sub(r"\.\d+$", "", name)
        if kind not in NEW:
            continue
        # no reader that goes by a kernel's name takes it for its own
        assert not name.startswith(("ssd_", "flash_", "gmm", "tgmm"))
        path = hlo._SOURCE_RE.search(line).group(1).split("/")
        part = "conv" if "conv" in kind else "gated_norm"
        assert "mamba" in path and part in path, (name, path)
        assert known[name] == ("mamba", part, None)     # ssm_ms reads it
        assert name in pointwise
        # time on the lanes, in the compute type; the scan's cotangent
        # is the one fp32 array of the layer's width
        assert "bf16[1,4096,8192]" in line, name
        if part == "conv":
            assert not re.search(r"f32\[1,\d+,8192\]", line), name
    # the scan's calls are not this reader's, the projections neither
    assert not [n for n in pointwise if re.search(r"ssd_(fwd|bwd)", n)]
    # no fp32 copy of xBC outside a fusion's registers: none is an
    # instruction of the entry computation (what lives in HBM)
    entry = text[text.index("\nENTRY "):]
    assert not re.findall(
        rf"= f32\[(?:1,)?(?:{channels},8192|8192,{channels})\]", entry)
    # in_proj's result is read where it lies: no copy of it, whole or
    # xBC's rows, feeds the calls
    wide = {"nemotron3nano-s8192-b1": 10304,
            "granite4hmicro-s8192-b1": 8512}[cell]
    assert not re.findall(
        rf"= bf16\[1,(?:{wide}|{channels}),8192\]\S* copy\(", entry)
    assert hlo.collective_bytes(text, 1) == 0 and not hlo.collectives(text)


@pytest.mark.parametrize("need", [
    pk._mamba_conv_vmem_bytes(128, 8192, 2),        # both cells
    pk._mamba_norm_vmem_bytes(512, 2048, 2),        # nemotron3nano
    pk._mamba_norm_vmem_bytes(4096, 256, 2),        # granite4hmicro
])
def test_the_calls_ask_for_vmem_the_chip_has(need):
    """The backward calls at the cells' blocks: above Mosaic's default
    scope, so the calls ask; under half a v5e's 128 MiB.  That the
    compiler takes them at these limits is the test above."""
    assert pk._MOSAIC_VMEM_SCOPE < need < V5E_VMEM // 2


@pytest.mark.parametrize("cell", sorted(NO_MAMBA_STEPS))
def test_steps_without_a_mamba_layer_lower_as_before_the_kernels(
        topo, kernels_selected, cell):
    step, args = _step_and_arguments(topo, cell)
    with step._ambient_mesh():
        text = step._step.lower(*args).as_text()
    assert "mamba_" not in text
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    text = re.sub(r"backend_config = \{[^\n]*\}", "backend_config = {}",
                  text)
    assert hashlib.sha256(text.encode()).hexdigest() == NO_MAMBA_STEPS[cell]


# the compiler's peak (``memory_analysis().peak_memory_in_bytes``, a
# prediction) of the whole cell's step before its attention block kept the
# flash forward's results and its expert buffers one set of slots; and
# what the step may keep above it: nothing, or one attention sublayer's
# output (8,192 x 32 heads x 64, bf16, the heads' 64 lanes held in 128)
# and logsumexp ((32, 8, 8,192) fp32)
PARENT_PEAK = {
    "nemotron3nano-s8192-b1": (13_716_984_320, 0),
    "granite4hmicro-s8192-b1": (13_565_370_880,
                                8192 * 32 * 128 * 2 + 32 * 8 * 8192 * 4),
}


@pytest.mark.parametrize("cell", sorted(PARENT_PEAK))
def test_the_whole_step_keeps_what_it_kept_or_less(topo, kernels_selected,
                                                   cell):
    """The one attention sublayer runs the flash forward once and keeps
    its results; ``nemotron3nano``'s four expert sublayers keep 8,192 x 6
    rows of results where they kept 8,192 x 9, and its compiled peak
    falls; ``granite4hmicro``, with no expert layer, holds the kept
    results above its peak and nothing else."""
    from horovod_tpu import telemetry

    step, args = _step_and_arguments(topo, cell)
    since = telemetry.spans._now()
    with telemetry.span("probe"), step._ambient_mesh():
        compiled = step._step.lower(*args).compile()
    said = [s for s in telemetry.spans.snapshot(since=since)
            if s.name == "probe"][-1].attrs
    rows = 49152 if cell.startswith("nemotron") else 0
    assert (said["flash_kept_layers"], said["moe_kept_rows"]) == (1, rows)
    by_name = Counter(re.sub(r"\.\d+$", "", hlo._OP_RE.match(ln).group(1))
                      for ln in hlo.mosaic_lines(compiled.as_text()))
    assert (by_name["flash_fwd"], by_name["flash_bwd"]) == (1, 1)
    parent, kept = PARENT_PEAK[cell]
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak < parent if not kept else peak <= parent + kept
