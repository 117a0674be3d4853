"""The multi-token-prediction step, compiled — not run — for a described
v5e, and the steps that must not notice the module.

The ``joyaiflash`` cell's step at the published widths and the timed
shape (8,192 x 1), five layers and the module: flash forward and
backward once for each of six attention sublayers (each block keeps the
forward's results), the module's two under ``mtp``; every operation the
module adds under ``mtp``; two forward head matmuls, on the one ``head``
leaf; the module's facts on the lowering span; a compiled peak below
the one before the blocks kept flash's results and the expert buffers
one set of slots.  At ``mtp_layers`` 0 the three hybrid cells lower to
their pinned text (a digest, every ``backend_config`` emptied).  Nothing
here is a time or a measurement; the compiler's peak is a prediction
(PERF.md).

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
from benchmark.metrics import mtp_ms  # noqa: E402

from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402

CELL = "joyaiflash-s8192-b1"
CHIP_BYTES = 16.91e9

# sha256 of the lowered (StableHLO) step of the whole cell on one
# described chip, every ``backend_config`` emptied, as
# tests/test_moe_offchip_compile.py pins them, which the module's code
# must leave as they are at ``mtp_layers`` 0.  Re-pinned when the
# attention blocks came to keep the flash forward's results and the
# expert buffers one set of slots, which means to alter all three (until
# then 24ba1fec…18eeaa, 8e08fb60…bbd91 and b3571472…edd6e)
PARENT_STEPS = {
    "xing4-s4096-b1":
        "41d584140da8cbc467afd0c1b8223cc1f7e4e9b7fe9f418d2b6580d0d00ab046",
    "nemotron3nano-s8192-b1":
        "10a308122eccf335164dc8f39fac79a35abb57d478f1091bd0954d2d08e15f21",
    "granite4hmicro-s8192-b1":
        "91a15011ca8735a9fa2fdc9fdbaf6160a87c63343f5887fc20a9a3f82207f4ab",
}

TRANSPOSED_GRADS = {"xing4-s4096-b1": 2, "nemotron3nano-s8192-b1": 0,
                    "granite4hmicro-s8192-b1": 3}

# the compiler's peak (``memory_analysis().peak_memory_in_bytes``, a
# prediction) of the whole cell's step before its attention blocks kept
# the flash forward's results and its expert buffers one set of slots
PARENT_PEAK = 14_770_343_424


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


def _step_and_arguments(topo, cell_name):
    """The cell's ``DistributedTrainStep`` on one described chip, the
    shapes of its arguments, and what ``build`` made."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    cell = cells.resolve(cell_name)
    config, job = cell.config, cell.job
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
    return step, (shaped(params), shaped(opt_state), batch), built


def _path(line: str) -> list:
    source = hlo._SOURCE_RE.search(line)
    return source.group(1).split("/") if source else []


def test_the_module_s_step_holds_six_attention_layers_and_one_head(
        topo, kernels_selected):
    from horovod_tpu import telemetry

    step, args, _ = _step_and_arguments(topo, CELL)
    since = telemetry.spans._now()
    with telemetry.span("probe"), step._ambient_mesh():
        compiled = step._step.lower(*args).compile()
    said = [s for s in telemetry.spans.snapshot(since=since)
            if s.name == "probe"][-1].attrs
    assert said["mtp_layers"] == 1 and said["mtp_positions"] == 8191
    # six attention sublayers keep flash's results; an expert sublayer
    # keeps 8,192 x 8 rows of grouped-matmul results, not 8,192 x 11
    assert (said["flash_kept_layers"], said["moe_kept_rows"]) == (6, 65536)
    # q / k 192 wide: dq and dk add up transposed, dv (128) as given
    assert said["flash_bwd_transposed_grads"] == 2
    text = compiled.as_text()
    # fits the chip, and in less than before, by the compiler's account
    # (a prediction)
    assert compiled.memory_analysis().peak_memory_in_bytes < PARENT_PEAK \
        < CHIP_BYTES

    mosaic = {hlo._OP_RE.match(ln).group(1): ln
              for ln in hlo.mosaic_lines(text)}
    by_name = Counter(re.sub(r"\.\d+$", "", name) for name in mosaic)
    # six attention sublayers, each rematerialised whole but for the
    # flash forward's results: the forward once, the backward once (12
    # forward calls while the blocks kept nothing); five expert sublayers
    assert by_name == {"flash_fwd": 6, "flash_bwd": 6, "gmm": 60,
                       "tgmm": 30, "moe_row_sum": 30}
    in_module = Counter(re.sub(r"\.\d+$", "", name)
                        for name, line in mosaic.items()
                        if "mtp" in _path(line))
    # the module's one attention and one expert sublayer (2 forward
    # flash calls while the block kept nothing)
    assert in_module == {"flash_fwd": 1, "flash_bwd": 1, "gmm": 12,
                         "tgmm": 6, "moe_row_sum": 6}
    under_mtp = mtp_ms._under_mtp(text)
    for name, line in mosaic.items():
        if name.startswith("flash") and "mtp" in _path(line):
            assert "attn" in _path(line) and name in under_mtp
    # what the module adds stands under mtp, forward and backward
    for line in text.splitlines():
        path = _path(line)
        if {"eh_proj", "enorm", "hnorm"} & set(path):
            assert "mtp" in path, line[:300]
    # two forward head matmuls, the main one and the module's
    heads = Counter(
        "/".join(_path(ln)) for ln in text.splitlines()
        if " convolution(" in ln and _path(ln)[-1:] == ["dot_general"]
        and "head" in _path(ln)
        and not any(s.startswith("transpose(") for s in _path(ln)))
    assert heads == {
        "jit(step)/loss_fn/jvp(HybridLM)/head/dot_general": 1,
        "jit(step)/loss_fn/jvp(HybridLM)/mtp/head/dot_general": 1}


def test_both_head_matmuls_read_the_one_head_leaf(topo):
    """In the step's program (its jaxpr, before XLA), the two forward
    matmuls under a ``head`` scope take the same leaf, ``head``, cast:
    the module has no head of its own."""
    import jax

    step, (params, _, batch), built = _step_and_arguments(topo, CELL)
    closed = jax.make_jaxpr(built.loss_fn)(params, batch)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    head_at = [i for i, (path, _) in enumerate(leaves)
               if jax.tree_util.keystr(path) == "['params']['head']"]
    assert len(head_at) == 1
    head_var = closed.jaxpr.invars[head_at[0]]
    made_by = {}

    def walk(jaxpr, stack=""):
        found = []
        for eqn in jaxpr.eqns:
            for out in eqn.outvars:
                made_by[out] = eqn
            where = stack + "/" + str(eqn.source_info.name_stack)
            if eqn.primitive.name == "dot_general" and "head" in where:
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += walk(sub, where)
        return found

    def source(var):
        while var in made_by and made_by[var].primitive.name == \
                "convert_element_type":
            var = made_by[var].invars[0]
        return var

    heads = walk(closed.jaxpr)
    assert len(heads) == 2
    assert [source(eqn.invars[1]) for eqn in heads] == [head_var, head_var]


@pytest.mark.parametrize("cell", sorted(PARENT_STEPS))
def test_without_the_module_the_steps_lower_as_the_parent_s(
        topo, kernels_selected, cell):
    from horovod_tpu import telemetry

    step, args, _ = _step_and_arguments(topo, cell)
    since = telemetry.spans._now()
    with telemetry.span("probe"), step._ambient_mesh():
        text = step._step.lower(*args).as_text()
    said = [s for s in telemetry.spans.snapshot(since=since)
            if s.name == "probe"][-1].attrs
    assert said["mtp_layers"] == said["mtp_positions"] == 0
    # the gradients flash's backward adds up transposed: q / k 192 wide,
    # 128-wide heads, 64-wide heads
    assert said["flash_bwd_transposed_grads"] == TRANSPOSED_GRADS[cell]
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    text = re.sub(r"backend_config = \{[^\n]*\}", "backend_config = {}",
                  text)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEPS[cell]
