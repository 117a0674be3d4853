"""The hybrid cell's step with the Mamba-2 scan as Mosaic kernels,
compiled — not run — for a described v5e beside
``test_benchmark_offchip_compile_hybrid.py`` (whose sentence "every other
Mosaic call is the experts' grouped matmul" predates the third kernel):
at the published widths and the timed shape, one layer of each kind
(``E M *``).  What the compiled module must show: three kinds of Mosaic
call and no fourth, the scan's calls under ``mamba/ssd`` where ``ssd_ms``
reads them, none of them on an ``attn`` path, and outside them no
(chunk x chunk) matrix per head among the results under ``mamba/ssd`` —
the matrices the kernels exist to keep out of HBM.  Nothing here is a
time or a measurement.

The step is compiled once, inside a fixture of this one file (only one
process a machine may load libtpu; never while a module is imported).
"""

import importlib
import re
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, hlo, modules, trace  # noqa: E402

CELL = "nemotron3nano-s8192-b1"


@pytest.fixture(scope="module")
def step_text():
    """The optimized module of one ``E M *`` step for one described v5e
    chip, the TPU kernels selected as they are on the chip."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops import pallas_kernels

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cell = cells.resolve(CELL)
    layers = cell.config["parity"]["layers"]
    config = dict(cell.config, layers=layers, num_layers=len(layers))
    job = cell.job
    built = importlib.import_module(
        f"benchmark.configs.{config['name']}").build(config, job, 1, seed=0)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dcn", "ici"))
    step = hvd.DistributedTrainStep(built.loss_fn, built.optimizer,
                                    mesh=mesh, **job["train_step"])

    def shaped(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    replicated = NamedSharding(mesh, P())
    params = jax.eval_shape(built.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(built.optimizer.init, params)
    batch = {k: jax.ShapeDtypeStruct((job["batch_per_chip"],) + v.shape[1:],
                                     v.dtype, sharding=step.batch_sharding)
             for k, v in built.sample(np.random.default_rng(0), 1).items()}
    # an executable for a described chip cannot be read back from the
    # persistent cache without one: keep this compile out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as patch:
            # the default backend here is the CPU; the program asks it
            # whether to select its TPU kernels
            patch.setattr(pallas_kernels, "_on_tpu", lambda: True)
            return step.compiled_text(shaped(params, replicated),
                                      shaped(opt_state, replicated), batch)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _mosaic(text):
    """``[(line, classification)]`` of the step's Mosaic calls."""
    known = modules.read_step(text)
    return [(ln, known.get(hlo._OP_RE.match(ln).group(1)))
            for ln in hlo.mosaic_lines(text)]


def test_every_mosaic_call_is_one_of_three_kernels(step_text):
    found = {kind for _, kind in _mosaic(step_text)}
    assert found == {("attn", None, "gqa_flash"),
                     ("moe", "experts", "grouped_matmul"),
                     ("mamba", "ssd", None)}


def test_flash_is_still_four_calls_on_the_repeated_heads(step_text):
    """No scan call took an ``attn`` path (``gqa_flash_ms`` would have
    read it as flash)."""
    flash = [ln for ln, kind in _mosaic(step_text)
             if kind == ("attn", None, "gqa_flash")]
    assert len(flash) == 4
    assert all("[32,8192,128]" in ln for ln in flash)


def test_the_scan_is_three_calls_a_layer(step_text):
    """Forward, the forward again inside the rematerialised block, and
    one backward kernel — what ``ssd_kernel_calls_per_layer`` says —
    each on the group-major operands: ``x`` as (1, 8192, 4096), the
    starting states as (1, 8, 64, 512, 128) fp32."""
    scan = [ln for ln, kind in _mosaic(step_text)
            if kind == ("mamba", "ssd", None)]
    names = sorted(re.sub(r"[._\d]+$", "", hlo._OP_RE.match(ln).group(1))
                   for ln in scan)
    assert len(scan) == 3, names
    assert sum("ssd_fwd" in name for name in names) == 2
    assert sum("ssd_bwd" in name for name in names) == 1
    assert all("f32[1,8,64,512,128]" in ln for ln in scan)


def test_no_chunk_by_chunk_matrix_a_head_is_left_in_hbm(step_text):
    """Outside the Mosaic calls nothing under ``mamba/ssd`` has a result
    of (..., groups 8, heads 8, 128, 128): the decay matrices, their
    product with C B^T and the cotangents of both were 64 x 64 such
    matrices a layer and a pass in the einsum form."""
    known = modules.read_step(step_text)
    under = [ln for ln in step_text.splitlines()
             if (m := hlo._OP_RE.match(ln)) is not None
             and known.get(m.group(1), (None,) * 3)[:2] == ("mamba", "ssd")
             and hlo.MOSAIC_TARGET not in ln]
    assert under        # the layout prologue and the triangle product
    per_head = re.compile(r"(?:f32|bf16)\[[\d,]*8,8,128,128\]")
    assert not [ln[:160] for ln in under
                if per_head.search(hlo._OP_RE.match(ln).group(2))]


# ---------------------------------------------------------------------------
# ssd_ms's reader, on a hand-made trace
# ---------------------------------------------------------------------------

STEP = """\
HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(step)/layer_1/mamba/ssd/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/conv/mul"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/ssd/zgcrs,ls->zgcrl/dot_general"}
  %jvp_ssd_fwd_.3 = f32[8] custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/ssd/ssd_fwd"}
  %jvp_ssd_fwd_.4 = f32[8] custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/rematted_computation/layer_1/mamba/ssd/ssd_fwd"}
  %transpose_jvp_ssd_bwd__.5 = f32[8] custom-call(%jvp_ssd_fwd_.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/layer_1/mamba/ssd/ssd_bwd"}
  %fusion.6 = f32[8] fusion(%transpose_jvp_ssd_bwd__.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(HybridLM))/layer_1/mamba/in_proj/dot_general"}
  ROOT %custom-call.7 = f32[8] custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer_8/attn/pallas_call"}
}
"""


class _Observed:
    def __init__(self, seconds):
        self.traced_steps = 10
        self._seconds = seconds


def test_ssd_ms_adds_up_kernels_and_fusions_under_mamba_ssd(monkeypatch):
    """Mosaic call or fusion, forward, recomputation and backward, by
    the one rule of ``benchmark/modules.py``; the convolution and the
    projections are ``ssm_ms``'s alone."""
    from benchmark.metrics import ssd_ms, ssm_ms

    known = modules.read_step(STEP)
    for name in ("jvp_ssd_fwd_.3", "jvp_ssd_fwd_.4",
                 "transpose_jvp_ssd_bwd__.5", "fusion.2"):
        assert known[name] == ("mamba", "ssd", None)
    assert known["fusion.1"] == ("mamba", "conv", None)
    assert known["custom-call.7"] == ("attn", None, "gqa_flash")
    dev = "/device:TPU:0"

    def op(name, start, duration):
        return (dev, trace.OPS_LINE, f"%{name} = f32[8] x()", start, duration)
    events = [op("fusion.1", 0, 700), op("fusion.2", 1000, 30),
              op("jvp_ssd_fwd_.3", 2000, 1500),
              op("jvp_ssd_fwd_.4", 4000, 1500),
              op("transpose_jvp_ssd_bwd__.5", 6000, 1600),
              op("fusion.6", 8000, 9000), op("custom-call.7", 20000, 500),
              (dev, trace.MODULES_LINE, "jit_step", 0, 21000)]
    reduced = modules.reduce_events(events, known)
    assert reduced["module_s"]["mamba/ssd"] == pytest.approx(4630e-9)
    assert reduced["kernel_s"] == {"gqa_flash": pytest.approx(500e-9)}
    monkeypatch.setattr(modules, "of_run", lambda obs: obs._seconds)
    obs = _Observed(reduced)
    assert ssd_ms.read(obs) == pytest.approx(4630e-9 / 10 * 1e3)
    assert ssm_ms.read(obs) == pytest.approx(
        (4630 + 700 + 9000) * 1e-9 / 10 * 1e3)
    # a step with no scan under that name (another configuration, an
    # untraced run) reads as nothing, and does not raise
    assert ssd_ms.read(_Observed({})) is None
    assert ssd_ms.read(_Observed({"module_s": {"mamba": 1.0}})) is None
    hybrid = cells.resolve(CELL)
    decoder = cells.resolve("lm871m-s4096-b1")
    assert ssd_ms.applies(hybrid.config, hybrid.job)
    assert not ssd_ms.applies(decoder.config, decoder.job)
