"""``benchmark/phases.py`` and the readers on it, on a hand-written
compiled step and event list with known answers.  No JAX device is
touched."""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, phases, program_spans, trace  # noqa: E402
from benchmark.observe import Spans  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    exchange_fused_ms,
    exchange_glue_ms,
    flash_bwd_ms,
    flash_fwd_ms,
    head_ms,
    unnamed_ms,
    update_ms,
)

DEV = "/device:TPU:0"
MOSAIC = 'custom_call_target="tpu_custom_call"'

# one line an operation: name, nanoseconds in the trace, what it is, its
# path (None: no metadata), and the group the rules give it
STEP = """\
HloModule jit_per_device

%named_by_root (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  %c = f32[8] convert(%p), metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/layer_0/attn/convert_element_type"}
  ROOT %m = f32[8] multiply(%c, %c), metadata={op_name="jit(per_device)/shard_map/update/mul"}
}

%named_near_root (p: f32[8]) -> (f32[8], f32[8]) {
  %p = f32[8] parameter(0)
  %s = f32[8] slice(%p), metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/layer_1/attn/split"}
  %b = f32[8] bitcast(%s)
  ROOT %t = (f32[8], f32[8]) tuple(%s, %b)
}

%no_path_anywhere (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %d = f32[8] dynamic-update-slice(%p, %p)
}

%all-reduce-scatter (p: f32[8]) -> f32[2] {
  %p = f32[8] parameter(0)
  %pad = f32[8] pad(%p, %p)
  %ar = f32[8] all-reduce(%pad), channel_id=7, replica_groups={{0,1,2,3}}
  ROOT %ds = f32[2] dynamic-slice(%ar)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/layer_0/mlp/wi/dot_general"}
  %fusion.2 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root
  %fusion.3 = (f32[8], f32[8]) fusion(%a), kind=kLoop, calls=%named_near_root
  %fusion.4 = f32[8] fusion(%a), kind=kLoop, calls=%no_path_anywhere
  %fusion.5 = f32[2] fusion(%a), kind=kLoop, calls=%all-reduce-scatter
  %copy-start.6 = (f32[8], f32[8], u32[]) copy-start(%a)
  %copy-done.7 = f32[8] copy-done(%copy-start.6)
  %all-gather.8 = f32[8] all-gather(%fusion.5), channel_id=9, replica_groups={{0,1,2,3}}, metadata={op_name="jit(per_device)/shard_map/update/exchange/gather/all_gather"}
  %fusion.9 = f32[8] fusion(%all-gather.8), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/update/exchange/gather/reshape"}
  %fusion.10 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/update/exchange/scatter/pad"}
  %fusion.11 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/exchange/div"}
  %fusion.12 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/update/add"}
  %fusion.13 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/guard/jit(_where)/select_n"}
  %fusion.14 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/head/embed.attend/dot_general"}
  %fusion.15 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/transpose(jvp(LM))/head/ln_f/mul"}
  %fusion.16 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/transpose(jvp(loss))/jit(take_along_axis)/scatter-add"}
  %fusion.17 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/embed/jit(_take)/gather"}
  %fusion.18 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp()/reduce_max"}
  %fusion.19 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/transpose(jvp(LM))/jvp(LM)/checkpoint/rematted_computation/layer_1/ln1/mul"}
  %fusion.20 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/mul"}
  %fusion.21 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/broadcast.47"}
  %flash_fwd.22 = f32[8] custom-call(%a), """ + MOSAIC + """, metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/layer_0/attn/flash_fwd/pallas_call"}
  %flash_bwd.23 = f32[8] custom-call(%a), """ + MOSAIC + """, metadata={op_name="jit(per_device)/shard_map/loss_fn/transpose(loss_fn)/jvp(LM)/layer_0/attn/flash_bwd/pallas_call"}
  %gmm.24 = f32[8] custom-call(%a), """ + MOSAIC + """, metadata={op_name="jit(per_device)/shard_map/update/mul"}
  ROOT %fusion.25 = f32[8] fusion(%a), kind=kLoop, calls=%named_by_root, metadata={op_name="jit(per_device)/shard_map/loss_fn/jvp(LM)/layer_0/moe/router/dot_general"}
}
"""

# the same step as a parent commit lowers it: flax's names alone
BARE = STEP
for _scope in ("loss_fn/", "update/", "exchange/gather/",
               "exchange/scatter/", "exchange/", "guard/", "head/"):
    BARE = BARE.replace(_scope, "")
BARE = BARE.replace("jvp(loss)", "jvp()")

GROUPS = {
    "fusion.1": ("model", "mlp", None),     # its own path, not its root's
    "fusion.2": ("update", None, None),     # through its root
    "fusion.3": ("model", "attn", None),    # the nearest to a bare root
    "fusion.4": ("unnamed", None, None),    # no path anywhere
    "fusion.5": ("exchange_fused", None, None),     # ... but a collective
    "copy-start.6": ("unnamed", None, None),
    "copy-done.7": ("unnamed", None, None),
    "fusion.9": ("exchange", "gather", None),   # innermost: update loses
    "fusion.10": ("exchange", "scatter", None),
    "fusion.11": ("exchange", None, None),
    "fusion.12": ("update", None, None),
    "fusion.13": ("guard", None, None),
    "fusion.14": ("head", None, None),
    "fusion.15": ("head", None, None),      # the backward keeps the scope
    "fusion.16": ("loss", None, None),
    "fusion.17": ("embed", None, None),
    "fusion.18": ("loss_fn", None, None),   # the user's loss arithmetic
    "fusion.19": ("model", None, None),     # a module between the mixers
    "fusion.20": ("loss_fn", None, None),   # the model's own __call__
    "fusion.21": ("unnamed", None, None),   # only wrappers and a primitive
    "flash_fwd.22": ("model", "attn", "flash_fwd"),
    "flash_bwd.23": ("model", "attn", "flash_bwd"),
    "gmm.24": ("model", "moe", None),       # by its name, not its path
    "fusion.25": ("model", "moe", None),
}
READERS = (update_ms, head_ms, exchange_glue_ms, exchange_fused_ms,
           unnamed_ms, flash_fwd_ms, flash_bwd_ms)
# a traced block of two steps: each operation once a step, (n + 1) x 10 ns
# for operation n, the collective 1000 ns
ORDER = sorted(GROUPS, key=lambda name: int(name.rsplit(".", 1)[1]))


def _events():
    events, at = [], 0
    for _ in range(2):
        for name in ORDER + ["all-gather.8"]:
            ns = 1000 if name == "all-gather.8" \
                else 10 * (int(name.rsplit(".", 1)[1]) + 1)
            events.append((DEV, trace.OPS_LINE, f"%{name} = f32[8] x()",
                           at, ns))
            at += ns
    return events + [(DEV, trace.MODULES_LINE, "jit_per_device", 0, at)]


def _ns(*numbers) -> float:
    """Milliseconds a step of operations ``numbers``, each once a step."""
    return sum(10 * (n + 1) for n in numbers) * 1e-6


class _Observed:
    """What the readers take of a run: a traced block of two steps."""

    def __init__(self, text, cell="lm871m-s1024-b6-zero4"):
        self.traced_steps = 2
        self.trace = {"slowest": DEV, "window_s": 1e-5,
                      "collective_exposed_s": 2e-6, "idle_share": 0.1}
        self.hlo_text = text
        self.cell = cells.resolve(cell)
        self.spans = Spans()        # no window: the recorder says nothing


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(trace, "newest_xplane", lambda root: "profile")
    monkeypatch.setattr(trace, "load_events", lambda path: _events())
    phases._reduced.cache_clear()
    yield
    phases._reduced.cache_clear()


def test_every_operation_falls_in_one_group_by_the_rules():
    known = phases.read_step(STEP)
    assert {name: known[name] for name in GROUPS} == GROUPS
    # the collective is the exchange_* readers', and in no group
    assert "all-gather.8" not in known and "ar" not in known


@pytest.mark.parametrize("path,found", [
    ("jit(step)/loss_fn/transpose(jvp(TransformerLM))/layer_3/attn/qkv/"
     "dot_general", ["loss_fn", "TransformerLM", "layer_3", "attn", "qkv"]),
    ("jit(step)/loss_fn/jvp(loss)/jit(take_along_axis)/gather",
     ["loss_fn", "loss"]),
    ("jit(step)/loss_fn/transpose(loss_fn)/jvp(LM)/custom_vjp_call/"
     "checkpoint/rematted_computation/while/body/branch_1/closed_call/x/add",
     ["loss_fn", "loss_fn", "LM", "x"]),
    ("jit(step)/jvp()/pjit/reduce_max", []),
    ("update/exchange/scatter/psum_scatter",
     ["update", "exchange", "scatter"]),
    ("add", []),
])
def test_a_path_s_steps_are_what_jax_s_wrappers_leave(path, found):
    assert phases.steps(path) == found


def test_the_readers_on_a_hand_made_trace(traced):
    obs = _Observed(STEP)
    assert update_ms.read(obs) == pytest.approx(_ns(2, 12))
    assert head_ms.read(obs) == pytest.approx(_ns(14, 15))
    # pads, slices, the loss's plumbing; not the collective (1000 ns a
    # step), not the fusion that holds one
    assert exchange_glue_ms.read(obs) == pytest.approx(_ns(9, 10, 11))
    assert exchange_fused_ms.read(obs) == pytest.approx(_ns(5))
    assert unnamed_ms.read(obs) == pytest.approx(_ns(4, 6, 7, 21))
    assert flash_fwd_ms.read(obs) == pytest.approx(_ns(22))
    assert flash_bwd_ms.read(obs) == pytest.approx(_ns(23))
    assert phases.ms_per_step(obs, "guard") == pytest.approx(_ns(13))
    # the whole account: every line, and nothing left over but the
    # collective's own time, which is in no group
    lines = phases.account(obs)
    assert lines["block"] == pytest.approx(5e-3)
    assert lines["attn"] == pytest.approx(_ns(3, 22, 23))
    assert lines["moe"] == pytest.approx(_ns(24, 25))
    assert lines["model"] == pytest.approx(_ns(19))
    assert lines["exchange"] == pytest.approx(_ns(11))
    assert lines["exchange/gather"] == pytest.approx(_ns(9))
    assert lines["loss_fn"] == pytest.approx(_ns(18, 20))
    assert lines["idle"] == pytest.approx(5e-4)
    assert lines["collective_exposed"] == pytest.approx(1e-3)
    assert sum(v for k, v in lines.items() if k != "block") \
        == pytest.approx(lines["block"])
    named = sum(10 * (n + 1) for n in range(1, 26) if n != 8) * 1e-6
    assert lines["unaccounted"] == pytest.approx(5e-3 - 1.5e-3 - named)


def test_a_step_without_the_scopes_reads_nothing_by_scope(traced):
    """A parent commit: flax's module names and nothing else.  The
    by-scope readers are left out; everything outside the modules is
    unnamed."""
    bare = BARE
    known = phases.read_step(bare)
    # (the fusion round a bare collective is the exchange's on any step)
    assert {g for g, _, _ in known.values()} \
        == {"model", "embed", "unnamed", "exchange_fused"}
    obs = _Observed(bare)
    assert not phases.lays_scopes(obs)
    for reader in (update_ms, head_ms, exchange_glue_ms):
        assert reader.read(obs) is None, reader.__name__
    # the update, the glue, the guard and the loss arithmetic join what
    # was unnamed; the head is flax's embed.attend / ln_f, a module
    assert unnamed_ms.read(obs) == pytest.approx(
        _ns(2, 4, 6, 7, 9, 10, 11, 12, 13, 16, 18, 20, 21))
    assert phases.account(obs)["model"] == pytest.approx(_ns(14, 15, 19))
    assert flash_fwd_ms.read(obs) == pytest.approx(_ns(22))
    # the fusion round a bare collective rests on no scope
    assert exchange_fused_ms.read(obs) == pytest.approx(_ns(5))
    # a step that lays the scopes and has nothing under one reads 0
    no_guard = _Observed(STEP.replace("guard/", "update/"))
    assert phases.ms_per_step(no_guard, "guard") == 0.0
    untraced = _Observed(STEP)
    untraced.trace = {}
    for reader in READERS:
        assert reader.read(untraced) is None, reader.__name__
    assert phases.account(untraced) == {}
    # a step with no fusion round a collective reads 0 there
    plain = _Observed(STEP.replace("all-reduce(%pad), channel_id=7, "
                                   "replica_groups={{0,1,2,3}}",
                                   "negate(%pad)"))
    assert exchange_fused_ms.read(plain) == 0.0


def test_the_readers_keep_to_their_cells():
    applies = {name: [r.__name__.rsplit(".", 1)[1] for r in READERS
                      if r.applies(c.config, c.job)]
               for name, c in ((n, cells.resolve(n)) for n in (
                   "resnet50-b256", "lm871m-s1024-b6-dp4",
                   "lm871m-s1024-b6-zero4", "granite4hmicro-s8192-b1"))}
    lm = ["update_ms", "head_ms", "unnamed_ms", "flash_fwd_ms",
          "flash_bwd_ms"]
    # no reader by name on a step with no Mosaic body: its compile-cache
    # key does not move with the tree's names (phases.key_moves_with_names)
    assert applies == {
        "resnet50-b256": [], "lm871m-s1024-b6-dp4": lm,
        "lm871m-s1024-b6-zero4": lm[:2] + [
            "exchange_glue_ms", "exchange_fused_ms"] + lm[2:],
        "granite4hmicro-s8192-b1": lm}


class _Span:
    def __init__(self, name, start, end, attrs=None):
        self.name, self.start, self.end, self.attrs = \
            name, start, end, attrs


def _recorded(monkeypatch, *lowered, opened=100.0):
    """The recorder as ``program_spans.observed`` hands it out: the
    window opens at ``opened``, ``lowered`` are ``(end, attrs)`` of
    ``train_step.lower`` spans."""
    spans = [_Span("train_step.lower", end - 1.0, end, attrs)
             for end, attrs in lowered]
    spans.append(_Span("train_step.call", opened + 1.0, opened + 2.0))
    monkeypatch.setattr(program_spans, "observed",
                        lambda obs: (opened, opened + 10.0, spans))


def test_the_program_s_word_is_the_measured_step_s_lowering(monkeypatch):
    obs = _Observed(STEP)
    assert phases.said_scopes(obs) is None      # no recorder, no window
    # the parity check's step lowers first, the measured step after it;
    # a lowering inside the window (there is none in a sound run) is
    # not the measured step's
    _recorded(monkeypatch, (10.0, {"step_scopes": "loss_fn,update"}),
              (50.0, {"step_scopes": "loss_fn,exchange,update"}),
              (104.0, {"step_scopes": "late"}))
    assert phases.said_scopes(obs) == "loss_fn,exchange,update"
    _recorded(monkeypatch, (50.0, {"tokens": 8}))       # a parent commit
    assert phases.said_scopes(obs) == ""
    _recorded(monkeypatch, (50.0, None))
    assert phases.said_scopes(obs) == ""
    _recorded(monkeypatch)                      # nothing lowered at all
    assert phases.said_scopes(obs) is None


@pytest.mark.parametrize("text,said,foreign", [
    (STEP, "loss_fn,exchange,update", False),   # this tree's executable
    (BARE, "", False),                          # a parent's, its own
    (BARE, "loss_fn,update", True),     # served from a parent's cache
    (STEP, "", True),                   # a parent served this tree's
], ids=["own", "parent-own", "scopes-said-none-held",
        "scopes-held-none-said"])
def test_names_of_another_tree_read_as_nothing(traced, monkeypatch, capsys,
                                               text, said, foreign):
    """JAX's compile-cache key leaves scope names out: where what the
    program says it laid and what the executable holds disagree, no
    reader on phases.py reads a number — ``unnamed_ms`` neither, whose
    meaning would be another."""
    obs = _Observed(text)
    _recorded(monkeypatch, (50.0, {"step_scopes": said} if said else {}))
    phases._warn_once.cache_clear()
    assert phases.foreign_names(obs) is foreign
    read = {r.__name__.rsplit(".", 1)[1]: r.read(obs) for r in READERS}
    if foreign:
        assert set(read.values()) == {None}
        assert phases.account(obs) == {}
        err = capsys.readouterr().err
        assert err.count("names are not this program's") == 1    # once
        assert obs.cell.name in err
    else:
        assert read["unnamed_ms"] is not None
        assert read["flash_fwd_ms"] == pytest.approx(_ns(22))
        assert (read["update_ms"] is None) == (not said)
        assert phases.account(obs)["block"] == pytest.approx(5e-3)
        assert capsys.readouterr().err == ""
