"""The step's scopes in the program the chip runs: three steps compiled —
not run — for a described v5e, at the cells' widths with the depth cut
as for the parity check (``lm871m`` on one chip, its ``zero4`` step on
four, ``nemotron3nano``).  Every top-level operation of the compiled
step that takes device time falls in exactly one group of
``benchmark/phases.py`` (a collective in none); what stays unnamed is
XLA's own plumbing, never a fusion of the program's arithmetic; the
flash kernels are found by their names.  That the lowered programs
themselves did not move is ``test_hc_offchip_compile.py``'s pinned
digests (``PLAIN_RESIDUAL_STEPS``) and ``test_step_scopes.py``'s
text-for-text comparison.  Nothing here is a time or a measurement.

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

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, hlo, phases  # noqa: E402

from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402

# operations that take no device time of their own
FREE = ("parameter", "constant", "get-tuple-element", "bitcast", "tuple",
        "partition-id", "replica-id", "iota")
# what XLA makes, or leaves, without a source path: copies between memory
# spaces and layouts, the buffers it concatenates in place, a stray
# convert or reshape of a parameter, and the hybrid's lax.switch over
# its buffers (a conditional keeps only what its branches leave)
XLA_S_OWN = {"copy", "copy-start", "copy-done", "slice-start", "slice-done",
             "custom-call", "broadcast", "convert", "reshape",
             "conditional"}


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


def _compiled(topo, cell_name):
    """The optimized HLO of the cell's step on ``cell.chips`` described
    chips, depth cut to the parity check's, and the span it compiled
    under."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import telemetry

    cell = cells.resolve(cell_name)
    config, job, chips = dict(cell.config), cell.job, cell.chips
    cut = config["parity"]
    if "layers" in cut:
        config.update(layers=cut["layers"], num_layers=len(cut["layers"]))
    else:
        config.update(num_layers=cut["num_layers"])
    built = importlib.import_module(
        f"benchmark.configs.{config['name']}").build(config, job, chips,
                                                     seed=0)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("dcn", "ici"))
    step = hvd.DistributedTrainStep(built.loss_fn, built.optimizer,
                                    mesh=mesh, **job["train_step"])
    replicated = NamedSharding(mesh, P())

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=replicated), tree)

    params = jax.eval_shape(built.init, jax.random.PRNGKey(0))
    # the step's own optimizer: the sharded exchange keeps slabs
    opt_state = jax.eval_shape(step._optimizer.init, params)
    batch = {k: jax.ShapeDtypeStruct(
        (job["batch_per_chip"] * chips,) + v.shape[1:], v.dtype,
        sharding=step.batch_sharding)
        for k, v in built.sample(np.random.default_rng(0), 1).items()}
    since = telemetry.spans._now()
    text = step.compiled_text(shaped(params), shaped(opt_state), batch)
    (compiling,) = [s for s in telemetry.spans.snapshot(since=since)
                    if s.name == "train_step.compile"]
    return text, compiling.attrs


def _top_level(text):
    """``(name, opcode)`` of the entry computation's operations that
    take device time."""
    entry = text[text.index("\nENTRY "):]
    for line in entry.splitlines():
        op = hlo._OP_RE.match(line)
        if op and op.group(3) not in FREE:
            yield op.group(1), op.group(3)


# cell -> (the groups its step must hold, those it must not, step_scopes)
STEPS = {
    "lm871m-s1024-b6": (
        {"update", "head", "embed", "loss_fn", "model", "unnamed"},
        {"exchange", "guard"}, "loss_fn,update"),
    "lm871m-s1024-b6-zero4": (
        {"update", "head", "embed", "loss_fn", "model", "unnamed",
         "exchange"}, {"guard"}, "loss_fn,exchange,update"),
    "nemotron3nano-s8192-b1": (
        {"update", "head", "embed", "loss", "model", "unnamed"},
        {"exchange", "guard"}, "loss_fn,update"),
}


@pytest.mark.parametrize("cell", sorted(STEPS))
def test_every_operation_of_the_compiled_step_falls_in_one_group(
        topo, kernels_selected, cell):
    holds, lacks, scopes = STEPS[cell]
    text, attrs = _compiled(topo, cell)
    assert attrs["step_scopes"] == scopes
    known = phases.read_step(text)
    classes = hlo.op_classes(text)["classes"]
    by_group, unnamed = {}, Counter()
    for name, opcode in _top_level(text):
        if classes[name].startswith("collective"):
            assert name not in known        # the exchange_* readers'
            continue
        group, part, kernel = known[name]   # exactly one: a table's entry
        by_group.setdefault(group, []).append((name, part, kernel))
        if group == "unnamed":
            unnamed[opcode] += 1
    assert holds <= set(by_group) and not lacks & set(by_group), \
        sorted(by_group)
    # unnamed is XLA's own plumbing: a handful of fusions at most (its
    # in-place concatenations, which have no path anywhere), none of
    # them a matmul's or the update's
    fusions = unnamed.pop("fusion", 0)
    assert fusions <= 8 and len(by_group["update"]) > fusions
    if cell.endswith("zero4"):
        # what the partitioner's collective-permutes are fed with
        for opcode in ("slice", "concatenate", "multiply", "add",
                       "subtract"):
            unnamed.pop(opcode, None)
    assert set(unnamed) <= XLA_S_OWN, unnamed
    # the Mosaic calls by name: flash forward (and its recomputation) and
    # one backward a layer, under attn
    flash = Counter(kernel for entries in by_group.values()
                    for _, part, kernel in entries if kernel)
    mosaic = [n for n, c in classes.items() if c == "mosaic"]
    named = [n for n in mosaic if phases.FLASH.match(n)]
    assert flash["flash_bwd"] >= 1 and flash["flash_fwd"] >= flash["flash_bwd"]
    assert sum(flash.values()) == len(named)
    assert all(known[n][:2] == ("model", "attn") for n in named)
    if cell.startswith("lm871m"):
        assert len(named) == len(mosaic)
    else:   # the scan's and the experts' kernels keep their mixers
        others = Counter(known[n][1] for n in mosaic if n not in named)
        assert set(others) == {"mamba", "moe"}
    if cell.endswith("zero4"):
        parts = Counter(part for _, part, _ in by_group["exchange"])
        assert parts["scatter"] and parts["gather"]
        # the reduce-scatters XLA decomposed: pad + all-reduce + slice
        assert all(re.match(r"fusion\.\d+$", n)
                   for n, _, _ in by_group["exchange_fused"])
    else:
        assert "exchange_fused" not in by_group
