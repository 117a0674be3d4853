"""Each cell's step, compiled — not run — for a described v5e 2x2.

XLA:TPU and Mosaic are installed here without a chip (on-chip-measurement
guide, section 2.3), so what the chip's compiler would refuse, it refuses
here, and the compiled module shows what the benchmark's checks and
readers will find on the chip: Mosaic calls on per-chip operands, the
gradient all-reduce over all chips, the convolutions.  In
tier-1 the steps are compiled at the cells' widths with the depth cut as
for the parity check; the full depths are marked slow.  Nothing here is a
time or a measurement.

The topology is described inside a fixture of this one file, never while
a module is imported (only one process a machine may load libtpu).
"""

import importlib
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, hlo  # noqa: E402


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
    from horovod_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)


def compiled_step_text(topo, cell_name: str, cut_depth: bool) -> tuple:
    """The optimized HLO of the cell's train step for ``cell.chips`` of
    the described devices, and the ``Built`` it was made from."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    cell = cells.resolve(cell_name)
    config, job, chips = dict(cell.config), cell.job, cell.chips
    if cut_depth:
        config.update({k: v for k, v in config["parity"].items()
                       if k in ("num_layers", "stage_sizes")})
    module = importlib.import_module(f"benchmark.configs.{config['name']}")
    built = module.build(config, job, chips, seed=0)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("dcn", "ici"))
    step = hvd.DistributedTrainStep(built.loss_fn, built.optimizer,
                                    mesh=mesh, **job["train_step"])

    def shaped(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    replicated = NamedSharding(mesh, P())
    params = jax.eval_shape(built.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(built.optimizer.init, params)
    rows = job["batch_per_chip"] * chips
    batch = {k: jax.ShapeDtypeStruct((rows,) + v.shape[1:], v.dtype,
                                     sharding=step.batch_sharding)
             for k, v in built.sample(np.random.default_rng(0), 1).items()}
    text = step.compiled_text(shaped(params, replicated),
                              shaped(opt_state, replicated), batch)
    return text, built, config


def check_lm(text, built, config, chips):
    mosaic = hlo.mosaic_lines(text)
    # forward, backward dq, backward dk/dv a layer, each on this chip's
    # rows only
    assert len(mosaic) == 3 * config["num_layers"]
    assert all(built.kernel_operand in line for line in mosaic)
    classes = hlo.op_classes(text)["classes"]
    assert sum(c == "mosaic" for c in classes.values()) == len(mosaic)
    # XLA:TPU turns every dot into a convolution: 4 matmuls a layer, x3
    # with backward
    assert text.count(" convolution(") >= 12 * config["num_layers"]
    moved = hlo.collective_bytes(text, chips)
    if chips == 1:
        assert moved == 0
    else:
        # every matmul weight's gradient crosses as bf16 — the tied
        # embedding's twice, once from the head and once from the lookup,
        # reduced apart — the norm scales' as fp32, and the loss
        weights = config["num_layers"] * (
            4 * config["d_model"] ** 2
            + 2 * config["d_model"] * config["d_ff"])
        embedding = config["vocab_size"] * config["d_model"]
        scales = (2 * config["num_layers"] + 1) * config["d_model"]
        assert moved == 2 * (weights + 2 * embedding) + 4 * scales + 4


@pytest.mark.parametrize("cell_name", ["lm871m-s1024-b6",
                                       "lm871m-s1024-b6-dp4"])
def test_lm_step_at_full_width_two_layers(topo, kernels_selected, cell_name):
    text, built, config = compiled_step_text(topo, cell_name, cut_depth=True)
    check_lm(text, built, config, cells.resolve(cell_name).chips)


def test_resnet_step_on_batch_statistics_at_batch_256(topo):
    """``train=True, mutable=["batch_stats"]`` inside
    ``DistributedTrainStep``, uint8 in: four bottleneck blocks at the
    published widths."""
    text, _, config = compiled_step_text(topo, "resnet50-b256",
                                         cut_depth=True)
    blocks = sum(config["stage_sizes"])
    # a block has three convolutions and, first of its stage, a
    # projection; with the stem and the head, x3 with backward
    assert text.count(" convolution(") >= 3 * 3 * blocks
    assert not hlo.mosaic_lines(text) and not hlo.collectives(text)
    assert "u8[256,224,224,3]" in text


@pytest.mark.slow
@pytest.mark.parametrize("cell_name", sorted(
    w["name"] for w in cells.load_benchmark()["workloads"]))
def test_every_cell_s_step_at_full_depth(topo, kernels_selected, cell_name):
    text, built, config = compiled_step_text(topo, cell_name,
                                             cut_depth=False)
    if "flash" in config["kernels"]:
        check_lm(text, built, config, cells.resolve(cell_name).chips)
    else:
        assert text.count(" convolution(") >= 150
