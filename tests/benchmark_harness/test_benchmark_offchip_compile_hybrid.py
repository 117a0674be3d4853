"""The hybrid cell's step, compiled — not run — for a described v5e, as
``test_benchmark_offchip_compile.py`` does for ``lm871m``: at the
published widths and the timed shape (8,192 x 1), one layer of each kind
(``E M *``).  What the chip's compiler would refuse it refuses here, and
the compiled module shows what the by-name readers will find on the chip:
the flash calls under ``attn`` on the operand after the key/value heads
are repeated, the experts' grouped-matmul Mosaic calls under ``moe``,
every mixer's operations under its module's name.  Nothing here is a time
or a measurement.

The topology is described inside a fixture of this one file, never while
a module is imported (only one process a machine may load libtpu).
"""

import importlib
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, hlo, modules  # noqa: E402

CELL = "nemotron3nano-s8192-b1"


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


def test_hybrid_step_at_full_width_one_layer_of_each_kind(
        topo, kernels_selected):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    cell = cells.resolve(CELL)
    layers = cell.config["parity"]["layers"]
    config = dict(cell.config, layers=layers, num_layers=len(layers))
    job = cell.job
    module = importlib.import_module(f"benchmark.configs.{config['name']}")
    built = module.build(config, job, 1, seed=0)
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
    text = step.compiled_text(shaped(params, replicated),
                              shaped(opt_state, replicated), batch)

    known = modules.read_step(text)
    kinds = Counter(kind for kind, _, _ in known.values())
    assert set(kinds) == {"mamba", "moe", "attn"}
    parts = {f"{kind}/{part}" for kind, part, _ in known.values() if part}
    assert {"mamba/ssd", "mamba/conv", "moe/router", "moe/dispatch",
            "moe/experts", "moe/shared", "moe/combine"} <= parts
    kernels = Counter(kernel for _, _, kernel in known.values() if kernel)
    # flash: forward, its recomputation, backward dq, backward dk/dv —
    # each on all 32 heads, the 2 key/value heads repeated
    flash = [ln for ln in hlo.mosaic_lines(text)
             if known.get(hlo._OP_RE.match(ln).group(1),
                          (None,) * 3)[2] == "gqa_flash"]
    assert kernels["gqa_flash"] == len(flash) == 4
    assert all("[32,8192,128]" in ln for ln in flash)
    # every other Mosaic call is the experts' grouped matmul, under
    # moe/experts: the by-class readers (flash_ms) would have read them
    # as flash
    others = [ln for ln in hlo.mosaic_lines(text) if ln not in flash]
    assert others and all(
        known.get(hlo._OP_RE.match(ln).group(1))
        == ("moe", "experts", "grouped_matmul") for ln in others)
    assert kernels["grouped_matmul"] >= 6   # up, down: fwd, dgrad, wgrad
    assert hlo.collective_bytes(text, 1) == 0 and not hlo.collectives(text)
