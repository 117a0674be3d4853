"""The gated MLP's hand-written backward in the steps that run it,
compiled — not run — for a described v5e.

``granite4hmicro`` at the published widths and the timed shape (8,192 x
1) on the parity cut ``M D * D``, its MLP blocks under ``dots``: a ``D``
layer holds three forward and six backward matmuls under ``mlp`` and no
second copy of a forward one; the elementwise backward — ``h``, ``d_g``,
``d_p`` from ``g``, ``p``, ``dh`` — is made once a layer, as the epilogue
XLA gives ``dh = dy w_down^T`` (one fusion writing the three
``bf16[8192,8192]`` arrays, ``dh`` itself never in HBM: read on the chip
10 ms a step faster than the pass standing alone, PERF.md PR 38); none
of the five matmuls that read those arrays holds a transcendental (what
a weight gradient's fusion still divides by is AdamW's: the update rides
it); the compiled peak is the parent's.  ``xing4`` (layers ``* D * E``,
the ``D`` block rematerialised whole inside a hyper-connection) makes
its forward again once and the same nine.  Nothing here is a time or a
measurement.

The topology is described inside a fixture of this one file, never while
a module is imported (only one process a machine may load libtpu).
"""

import re
import sys
from collections import Counter
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
for entry in (str(REPO), str(REPO / "tests")):
    if entry not in sys.path:
        sys.path.insert(0, entry)

from benchmark import hlo, phases  # noqa: E402
from benchmark.metrics import mlp_ms  # noqa: E402

from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402
from test_hc_offchip_compile import _step_and_arguments  # noqa: E402

TRANSCENDENTAL = {"exponential", "logistic", "tanh", "exponential-minus-one"}
# bytes XLA:TPU says the cut step needs at the commit before this one
# (PR 37's tree compiled the same way, off the chip): arguments 1.952 GB
# + temporaries 1.620
PARENT_PEAK = {"granite4hmicro-s8192-b1": 3_564_301_824,
               "xing4-s4096-b1": 6_378_887_168}



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


def _computations(text: str) -> dict:
    """``{computation: (opcodes inside — those of the computations it
    calls too: a matmul's prologue is a fusion nested in its fusion —,
    its parameters' types)}``."""
    own, name = {}, None
    for line in text.splitlines():
        header = phases._COMPUTATION_RE.match(line)
        if header:
            name = header.group(1)
            own[name] = (Counter(), [], [])
            continue
        op = hlo._OP_RE.match(line)
        if name is not None and op is not None:
            own[name][0][op.group(3)] += 1
            if op.group(3) == "parameter":
                own[name][1].append(op.group(2))
            own[name][2].extend(phases._CALLS_RE.findall(line))

    def opcodes(name):
        return sum((opcodes(c) for c in own[name][2]), own[name][0])

    return {name: (opcodes(name), own[name][1]) for name in own}


def _mlp_fusions(text: str) -> list:
    """``(name, forward | backward, last scope, opcodes inside, operands'
    types, line)`` of every fusion of the entry computation whose own
    path holds ``mlp``."""
    inside = _computations(text)
    found = []
    for line in text[text.index("\nENTRY "):].splitlines():
        op, source = hlo._OP_RE.match(line), hlo._SOURCE_RE.search(line)
        calls = phases._CALLS_RE.search(line)
        if not (op and source and calls) or op.group(3) != "fusion":
            continue
        path = source.group(1).split("/")
        if "mlp" not in path:
            continue
        found.append((op.group(1),
                      "bwd" if "transpose(" in source.group(1) else "fwd",
                      path[-2], *inside[calls.group(1)], line))
    return found


def _compiled(topo, cell, layers):
    step, args = _step_and_arguments(topo, cell, layers=layers,
                                     num_layers=len(layers) // 2)
    with step._ambient_mesh():  # one compile for the text and the bytes
        executable = step._executable_for(args)
    text = executable.as_text()
    peak = executable.memory_analysis().peak_memory_in_bytes
    return text, peak


@pytest.mark.parametrize("cell,layers,width,recomputed", [
    ("granite4hmicro-s8192-b1", "MD*D", "8192,8192", {}),
    # the block's forward again: gate, up, and down for the write side
    ("xing4-s4096-b1", "*D*E", "4096,9216",
     {"gate": 1, "up": 1, "down": 1})])
def test_a_dense_layer_is_nine_matmuls_and_its_elementwise_backward_once(
        topo, kernels_selected, cell, layers, width, recomputed):
    text, peak = _compiled(topo, cell, layers)
    dense = layers.count("D")
    fusions = _mlp_fusions(text)
    under_mlp = mlp_ms._under_mlp(text)
    assert all(name in under_mlp for name, *_ in fusions)   # mlp_ms reads them
    matmuls = [(way, scope) for _, way, scope, ops, *_ in fusions
               if ops["convolution"]]
    assert all(ops["convolution"] <= 1 for _, _, _, ops, *_ in fusions)
    # forward one of each and no second copy but a rematerialised
    # block's; backward two of each (input and weight gradient)
    assert Counter(matmuls) == Counter({
        **{("fwd", s): dense for s in ("gate", "up", "down")},
        **{("bwd", s): dense * (2 + recomputed.get(s, 0))
           for s in ("gate", "up", "down")}})
    # the pass: once a layer, the epilogue of ``dh``'s matmul — g, p and
    # the matmul's operands in, h, d_g, d_p out in the compute type, no
    # fp32 array of their size and no ``dh`` in HBM
    wide = re.compile(rf"(\w+)\[(?:1,)?{width}\]")
    passes = [(name, ops, operands, line)
              for name, way, scope, ops, operands, line in fusions
              if way == "bwd" and len(wide.findall(line.split(" fusion(")[0]))
              == 3]
    assert len(passes) == dense
    for name, ops, operands, line in passes:
        assert ops["convolution"] == 1 and ops["exponential"] == 1, ops
        assert "/mlp/down/" in line
        assert wide.findall(line.split(" fusion(")[0]) == ["bf16"] * 3, line
        assert wide.findall(" ".join(operands)) == ["bf16"] * 2, operands
    # the five matmuls that read them wait on no transcendental; what
    # divides in a weight gradient's fusion is the AdamW update aboard
    # (twice).  A rematerialised block's forward ``down`` keeps its
    # prologue, as the forward does (below)
    made_once = {name for name, *_ in passes}
    readers = [(name, scope, ops) for name, way, scope, ops, _, line in fusions
               if way == "bwd" and ops["convolution"]
               and name not in made_once
               and not (scope == "down" and recomputed.get("down")
                        and ops["exponential"])]
    assert len(readers) == dense * (5 + sum(recomputed.values())
                                    - recomputed.get("down", 0))
    for name, scope, ops in readers:
        assert not TRANSCENDENTAL & set(ops), (name, scope, ops)
        assert ops["divide"] in (0, 2), (name, scope, ops)
    # the forward's silu(g) p is the parent's: a prologue of ``down``
    # where ``g`` and ``p`` are kept (``dots``; the block made again),
    # the epilogue of ``up`` where a rematerialised block keeps neither
    gated = Counter((way, scope) for name, way, scope, ops, *_ in fusions
                    if ops["exponential"] and name not in made_once)
    assert gated == Counter(
        {("fwd", "up" if recomputed else "down"): dense,
         **({("bwd", "down"): dense} if recomputed else {})})
    assert not hlo.mosaic_lines("\n".join(line for *_, line in fusions))
    assert abs(peak - PARENT_PEAK[cell]) < 0.15e9, peak
