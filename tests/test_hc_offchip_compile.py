"""The hyper-connection's kernels in the step that runs them, compiled —
not run — for a described v5e, and the steps that must not notice them.

The ``xing4`` step at the published widths and the timed shape (4,096 x
1), layers ``*D*E``: each side of each hyper-connection is one Mosaic
call forward and one backward, all four names stand under the scope
``hc`` (the backward's too: the by-path reader ``hc_ms`` finds them),
they ask for VMEM the chip has, and no fp32 array of the streams' size
is written to HBM.  The two plain-residual cells share ``HybridBlock``
and ``HybridLM`` and never enter the connector: their lowered steps are
the ones they were before the kernels (a digest, with the Mosaic calls'
serialized bodies left out: those carry source lines).  Nothing here is
a time or a measurement.

The topology is described inside a fixture of this one file, never while
a module is imported (only one process a machine may load libtpu).
"""

import hashlib
import importlib
import re
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, hlo  # noqa: E402
from benchmark.metrics import hc_ms  # noqa: E402

from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402

CALLS = ("hc_read_fwd", "hc_read_bwd", "hc_write_fwd", "hc_write_bwd")
V5E_VMEM = 128 << 20

# sha256 of the lowered (StableHLO) step of the whole cell on one
# described chip, every ``backend_config`` emptied.  A change meant to
# alter these programs re-pins them and says so: re-pinned in PR 37,
# which means to alter both — the Mamba mixer's convolution and gated
# norm became Mosaic calls (until then 3a480ffd…23860 and 20deba82…45869,
# the digests of the commit before the hyper-connection's kernels,
# PR 35); ``granite4hmicro`` again in PR 38, which means to alter it —
# the ``D`` sublayers' backward is written out (``models/hybrid.
# gated_mlp``; until then 702dcc83…a565f8) — while ``nemotron3nano``,
# which has no ``D``, kept PR 37's until PR 39, which means to alter it —
# its expert layers' rows-to-tokens sums became ``moe_row_sum`` (until
# then 859d76b3…37afa6); the connector still leaves both alone ("hc_"
# not in the text)
PLAIN_RESIDUAL_STEPS = {
    "nemotron3nano-s8192-b1":
        "8e08fb60d344b62d221080fb8222a6c04b0350074bdf409095fc447adebbbd91",
    "granite4hmicro-s8192-b1":
        "b35714722f59a04800de6a2219e09aeebdab414d6fef6e9f97b36476dcfedd6e",
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


def _step_and_arguments(topo, cell_name, **cut):
    """The cell's ``DistributedTrainStep`` on one described chip and the
    shapes of its arguments."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    cell = cells.resolve(cell_name)
    config, job = dict(cell.config, **cut), cell.job
    built = importlib.import_module(
        f"benchmark.configs.{config['name']}").build(config, job, 1, seed=0)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dcn", "ici"))
    step = hvd.DistributedTrainStep(built.loss_fn, built.optimizer,
                                    mesh=mesh, **job["train_step"])
    replicated = NamedSharding(mesh, P())

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=replicated), tree)

    params = jax.eval_shape(built.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(built.optimizer.init, params)
    batch = {k: jax.ShapeDtypeStruct((job["batch_per_chip"],) + v.shape[1:],
                                     v.dtype, sharding=step.batch_sharding)
             for k, v in built.sample(np.random.default_rng(0), 1).items()}
    return step, (shaped(params), shaped(opt_state), batch)


def test_xing4_step_holds_the_four_calls_under_hc(topo, kernels_selected):
    from horovod_tpu import telemetry

    layers = "*D*E"
    step, args = _step_and_arguments(topo, "xing4-s4096-b1", layers=layers,
                                     num_layers=len(layers) // 2)
    since = telemetry.spans._now()
    text = step.compiled_text(*args)
    lowering = [s for s in telemetry.spans.snapshot(since=since)
                if s.name == "train_step.lower"][-1]
    assert lowering.attrs["hc_fused_sublayers"] == len(layers)
    assert lowering.attrs["hc_streams"] == 4

    mosaic = {hlo._OP_RE.match(ln).group(1): ln
              for ln in hlo.mosaic_lines(text)}
    by_name = Counter(re.sub(r"\.\d+$", "", name) for name in mosaic)
    # a sublayer: both sides forward and backward, and the read side
    # again where the block is rematerialised whole (the expert block's
    # connector is rematerialised on its own, and the read side's
    # backward needs nothing of its forward); the write side never twice
    assert {name: by_name[name] for name in CALLS} == {
        "hc_read_fwd": 2 * len(layers) - layers.count("E"),
        "hc_read_bwd": len(layers), "hc_write_fwd": len(layers),
        "hc_write_bwd": len(layers)}
    # the calls beside them are the ones the step had, and since PR 39
    # the expert layer's rows-to-tokens sums (a buffer size: the combine
    # forward, the dispatch's transpose; tests/test_moe_offchip_compile.py)
    assert {name: n for name, n in by_name.items() if name not in CALLS} \
        == {"flash_fwd": 4, "flash_bwd": 2, "gmm": 12, "tgmm": 6,
            "moe_row_sum": 6}
    under_hc = hc_ms._under_hc(text)
    for name, line in mosaic.items():
        if not name.startswith("hc_"):
            continue
        path = hlo._SOURCE_RE.search(line).group(1).split("/")
        assert "hc" in path and name in under_hc, (name, path)
        # the streams as they are: (tokens, 4 x 3584) bf16, never fp32
        assert "bf16[4096,14336]" in line and "f32[4096,14336]" not in line
    # no fp32 copy of the streams outside a fusion's registers: none is
    # an instruction of the entry computation (what lives in HBM)
    entry = text[text.index("\nENTRY "):]
    assert not re.findall(r"= f32\[(?:1,)?4096,14336\]", entry)
    assert hlo.collective_bytes(text, 1) == 0 and not hlo.collectives(text)


@pytest.mark.parametrize("wide,narrow,matrix", [
    (1, 1, True), (2, 1, True), (2, 1, False), (3, 2, False)])
def test_the_calls_ask_for_vmem_the_chip_has(wide, narrow, matrix):
    """Read forward, read backward, write forward, write backward at the
    cell's block (256 tokens of 4 x 3584 bf16): above Mosaic's default
    scope, so the calls ask; under half a v5e's 128 MiB.  That the
    compiler takes them at these limits is the test above."""
    need = pk._hc_vmem_bytes(256, 4, 3584, 2, wide, narrow, matrix)
    assert pk._MOSAIC_VMEM_SCOPE < need < V5E_VMEM // 2
    assert need < pk._hc_vmem_bytes(256, 4, 3584, 2, wide + 1, narrow,
                                    matrix)
    assert pk.hc_token_block(4096) == 256 and pk.hc_token_block(384) == 128
    assert pk.hc_token_block(1000) is None


@pytest.mark.parametrize("cell", sorted(PLAIN_RESIDUAL_STEPS))
def test_plain_residual_steps_lower_as_before_the_kernels(
        topo, kernels_selected, cell):
    step, args = _step_and_arguments(topo, cell)
    with step._ambient_mesh():
        text = step._step.lower(*args).as_text()
    assert "hc_" not in text
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    text = re.sub(r"backend_config = \{[^\n]*\}", "backend_config = {}",
                  text)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PLAIN_RESIDUAL_STEPS[cell]
