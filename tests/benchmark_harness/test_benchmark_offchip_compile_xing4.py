"""The ``xing4`` cell's step, compiled — not run — for a described v5e, as
``test_benchmark_offchip_compile_hybrid.py`` does for the hybrid: at the
published widths and the timed shape (4,096 x 1), two layers (``*D*E``:
latent attention, the dense MLP, latent attention, the expert layer, each
inside its hyper-connection).  What the chip's compiler would refuse — a
192-wide q / k block, the VMEM the backward asks for — it refuses here,
and the compiled module shows what the by-name readers will find on the
chip.  The counts below are this program's, read from this compile.
Nothing here is a time or a measurement.

The topology is described inside a fixture of this one file, never while
a module is imported (only one process a machine may load libtpu).
"""

import importlib
import re
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, hlo, modules  # noqa: E402
from benchmark.metrics import hc_ms  # noqa: E402

CELL = "xing4-s4096-b1"
LAYERS = "*D*E"


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


def test_xing4_step_at_full_width_two_layers(topo, kernels_selected):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    cell = cells.resolve(CELL)
    config = dict(cell.config, layers=LAYERS, num_layers=len(LAYERS) // 2)
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
    assert {kind for kind, _, _ in known.values()} == {"moe", "attn"}
    parts = {f"{kind}/{part}" for kind, part, _ in known.values() if part}
    assert {"moe/router", "moe/dispatch", "moe/experts", "moe/shared",
            "moe/combine"} <= parts
    # the hyper-connections' operations carry their scope, and no
    # mixer's name with it
    under_hc = hc_ms._under_hc(text)
    assert len(under_hc) > 100 and not set(under_hc) & set(known)

    mosaic = {hlo._OP_RE.match(ln).group(1): ln
              for ln in hlo.mosaic_lines(text)}
    by_name = Counter(re.sub(r"\.\d+$", "", name) for name in mosaic)
    # a flash layer: forward, its recomputation and one backward; an
    # expert layer: [gate | up] and down through gmm forward and for the
    # rows' gradients, tgmm for the weights', at each of three buffers
    assert by_name == {"flash_fwd": 4, "flash_bwd": 2, "gmm": 12, "tgmm": 6}
    for name, line in mosaic.items():
        kernel = known.get(name, (None,) * 3)[2]
        if name.startswith("flash"):
            assert kernel == "gqa_flash"
            # q and k 192 wide, v and the output 128, all 32 heads
            assert "bf16[32,4096,192]" in line
            assert "bf16[32,4096,128]" in line
        else:
            assert known[name] == ("moe", "experts", "grouped_matmul")
    wide = [ln for name, ln in mosaic.items()
            if name.startswith("gmm") and "bf16[4096,2048]" in ln]
    assert wide      # [gate | up] side by side: 2 x 1024 out of one call
    assert hlo.collective_bytes(text, 1) == 0 and not hlo.collectives(text)
