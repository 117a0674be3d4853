"""The ``granite4hmicro`` cell's step, compiled — not run — for a described
v5e, as ``test_benchmark_offchip_compile_xing4.py`` does for ``xing4``: at
the published widths and the timed shape (8,192 x 1) on the parity cut
(``MD*D``: a Mamba-2 layer of 64 heads in one B/C group and the attention
layer of 64-wide heads, each with its MLP).  What the chip's compiler
would refuse — a 64-wide flash block, the VMEM a block of the scan's
heads takes — it refuses here, and the compiled module shows what the
by-name readers will find on the chip.  The counts below are this
program's, read from this compile.  Nothing here is a time or a
measurement.

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
from benchmark.metrics import mlp_ms, ssd_roofline  # noqa: E402

CELL = "granite4hmicro-s8192-b1"


@pytest.fixture(scope="module")
def compiled():
    """``(optimized text, memory analysis)`` of one ``MD*D`` step for one
    described v5e chip, the TPU kernels selected as they are on the
    chip."""
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
    config = dict(cell.config, layers=layers, num_layers=len(layers) // 2)
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
    args = (shaped(params, replicated), shaped(opt_state, replicated), batch)
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
            with step._ambient_mesh():
                executable = step._executable_for(args)
            return executable.as_text(), executable.memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _mosaic(text):
    return {hlo._OP_RE.match(ln).group(1): ln
            for ln in hlo.mosaic_lines(text)}


def test_mosaic_calls_of_exactly_three_kinds(compiled):
    """Flash forward (and its recomputation) and one backward on the
    repeated heads at width 64; the scan's forward (and its
    recomputation) and backward at 64 heads a group; nothing else."""
    text, _ = compiled
    mosaic = _mosaic(text)
    by_name = Counter(re.sub(r"^(?:transpose_)?(?:jvp_)?|[._\d]+$", "", name)
                      for name in mosaic)
    assert by_name == {"flash_fwd": 2, "flash_bwd": 1,
                       "ssd_fwd": 2, "ssd_bwd": 1}
    known = modules.read_step(text)
    scan = ssd_roofline._scan_calls(text)
    assert len(scan) == 3
    for name, line in mosaic.items():
        if "flash" in name:
            assert known[name] == ("attn", None, "gqa_flash")
            # 8 key/value heads repeated to 32, 64 wide, whole sequences
            assert "bf16[32,8192,64]" in line
            assert "[32,8192,128]" not in line
        else:
            assert name in scan and known[name] == ("mamba", "ssd", None)
            # x as (1, 64 x 64, 8192); the 32 chunks' starting states of
            # the one group's 4,096 rows
            assert "bf16[1,4096,8192]" in line
            assert "f32[1,1,32,4096,128]" in line


def test_the_scan_takes_its_heads_in_blocks_and_asks_for_no_more(compiled):
    """The time-on-sublanes prefix is cut by head block, (1, 64 / Rb,
    8192, Rb), and the backward's ``db`` / ``dc`` come back a share a
    head block, (1, (64 / Rb) x 128, 8192)."""
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_kernels import ssd_head_block

    text, _ = compiled
    rb = ssd_head_block(64, 64, 128, 256, jnp.dtype(jnp.bfloat16).itemsize)
    assert rb < 64 and 64 % rb == 0
    backward = next(ln for name, ln in _mosaic(text).items()
                    if "ssd_bwd" in name)
    assert f"f32[1,{64 // rb},8192,{rb}]" in backward
    assert f"bf16[1,{64 // rb * 128},8192]" in backward


def test_no_score_matrix_and_no_einsum_scan_is_left_in_hbm(compiled):
    """Nothing (heads, 8192, 8192) — dense attention's scores; an MLP's
    (tokens, width) activation is (8192, 8192) too, with nothing but the
    batch's 1 before it — and nothing (..., 256, 256) a head — the einsum
    form's decay matrices — among the module's results."""
    text, _ = compiled
    shapes = re.compile(r"(?:f32|bf16)\[([\d,]+)\]")
    for line in text.splitlines():
        m = hlo._OP_RE.match(line)
        if m is None:
            continue
        for dims in shapes.findall(m.group(2)):
            dims = [int(d) for d in dims.split(",")]
            assert not (dims[-2:] == [8192, 8192]
                        and np.prod(dims[:-2]) > 1), line[:200]
            assert not (dims[-2:] == [256, 256] and len(dims) > 3), \
                line[:200]
    assert hlo.collective_bytes(text, 1) == 0 and not hlo.collectives(text)


def test_the_readers_find_their_modules_and_the_cut_fits(compiled):
    """``mlp``, ``mamba`` (with ``mamba/ssd``) and ``attn`` paths are
    there for ``mlp_ms``, ``ssm_ms`` / ``ssd_ms`` and ``attn_ms``; the
    compiled peak of the two-layer cut is stated (the ten-layer cell's
    is the chip's to say)."""
    text, memory = compiled
    known = modules.read_step(text)
    assert {kind for kind, _, _ in known.values()} == {"mamba", "attn"}
    assert {"mamba/conv", "mamba/ssd", "mamba/gated_norm"} <= {
        f"{kind}/{part}" for kind, part, _ in known.values() if part}
    under_mlp = mlp_ms._under_mlp(text)
    assert len(under_mlp) > 10 and not set(under_mlp) & set(known)
    peak = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 137M parameters of the cut at 12 bytes, and an 8k sequence's
    # activations and fp32 logits beside them
    assert 137_030_656 * 12 < peak < 8e9, peak
