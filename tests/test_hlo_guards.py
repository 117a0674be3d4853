"""Compiled-collective fusion guards.

The reference's fusion is runtime-observable (``controller.cc:686
FuseResponses`` merges pending tensors into one fused buffer per
negotiation cycle); here fusion is a *compile-time* artifact — autodiff
inserts one psum per gradient leaf and XLA's combiner merges them — so
these tests lower the real train step on the 8-device mesh and assert
on the optimized HLO module.  A regression that silently de-fused into
per-leaf collectives would pass every numerics test and the dryrun, and
only show up as wire overhead on a real pod; these guards fail instead.
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.utils import hlo as H


class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Dense(256)(x)
        x = nn.relu(x)
        x = nn.Dense(256)(x)
        return nn.Dense(10)(x)


def _loss_fn(model):
    def loss_fn(params, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, batch["x"]), batch["y"]).mean()
    return loss_fn


def _grad_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))


@pytest.fixture
def net_setup(hvd_runtime):
    hvd = hvd_runtime
    model = Net()
    init = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 64)))
    batch = {"x": jnp.zeros((16, 64), jnp.float32),
             "y": jnp.zeros((16,), jnp.int32)}
    return hvd, model, init, batch


class TestTrainStepFusion:
    def test_pjit_step_allreduces_payload_exactly_once(self, net_setup):
        """Every gradient leaf + the scalar loss ride all-reduces
        spanning all 8 devices, and the total collective payload equals
        the pytree + 4 bytes — nothing exchanged twice, nothing lost.
        (On toolchains whose pipeline runs the all-reduce combiner —
        TPU — these merge into ONE op; this image's CPU XLA has no
        combiner pass, so the op count is per-leaf and the guard pins
        the payload/grouping invariants that hold on both.)"""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3))
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        assert ops and all(o.kind == "all-reduce" for o in ops), \
            [o.line for o in ops]
        assert all(o.group_size in (8, None) for o in ops), \
            [(o.group_size, o.line) for o in ops]
        assert sum(o.bytes for o in ops) == _grad_bytes(init) + 4
        # never worse than one collective per gradient leaf + the loss
        nleaves = len(jax.tree_util.tree_leaves(init))
        assert len(ops) <= nleaves + 1

    def test_shard_map_step_groups_gradients_into_one_buffer(
            self, net_setup):
        """The explicit path (grouped_allreduce under shard_map)
        concatenates every same-dtype gradient itself, so exactly two
        values are all-reduced: the fused f32 gradient buffer and the
        4-byte scalar loss — the one-collective-per-dtype-group contract
        of the fusion buffer.  Read from the compiled HLO on this
        installation (jax 0.9.0): the pipeline runs XLA's all-reduce
        combiner, which folds the loss into the buffer's op, so the
        step holds ONE variadic all-reduce,
        ``(f32[85002], f32[]) all-reduce(...)``.  That is the right
        count here (the earlier pin of two was jax 0.4's pipeline, which
        ran no combiner) and it is pinned exactly: a second op means
        the combiner or the fusion buffer stopped doing its part."""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map")
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        assert H.count_by_kind(ops) == {"all-reduce": 1}, \
            [o.line for o in ops]
        nelems = _grad_bytes(init) // 4
        assert sorted(ops[0].shapes) == \
            [("f32", ()), ("f32", (nelems,))], ops[0].line

    def test_scanned_step_keeps_fusion(self, net_setup):
        """steps_per_call>1 wraps the step in lax.scan; the loop body
        must contain exactly the unscanned step's collectives (the scan
        must not unroll into per-step de-fused copies)."""
        hvd, model, init, bdata = net_setup
        plain = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3))
        params, opt = plain.init(init)
        batch = plain.shard_batch(bdata)
        plain_ops = H.collective_ops(
            plain.compiled_text(params, opt, batch))

        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        steps_per_call=4)
        params, opt = step.init(init)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        assert H.count_by_kind(ops) == H.count_by_kind(plain_ops), \
            [o.line for o in ops]
        assert sum(o.bytes for o in ops) == sum(o.bytes for o in plain_ops)

    def test_fsdp_step_shards_the_reduction(self, net_setup):
        """fsdp_axis: parameters are gathered on use (all-gather ops
        present) and gradient reduction is sharded — there must be NO
        full-payload all-reduce spanning all 8 devices.  (On TPU the
        sharded reduction lowers to reduce-scatter; the CPU backend
        decomposes it, so the guard pins the invariants that hold on
        both: gathers exist, and the only global-group all-reduces are
        scalar-sized.)"""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        fsdp_axis="ici",
                                        fsdp_min_weight_size=1024)
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        kinds = H.count_by_kind(ops)
        assert kinds.get("all-gather", 0) >= 1 or \
            kinds.get("reduce-scatter", 0) >= 1, kinds
        full = _grad_bytes(init)
        # group_size None covers replica_groups={} — HLO's spelling of
        # "all devices, one group" — so a global all-reduce can't evade
        # the guard by that form
        global_ars = [o for o in ops
                      if o.kind == "all-reduce" and
                      o.group_size in (8, None)]
        assert all(o.bytes < full for o in global_ars), \
            [(o.bytes, o.line) for o in global_ars]


class TestModelParallelCollectives:
    def test_tp_block_costs_exactly_one_psum(self, hvd_runtime):
        """Column→row parallel MLP block under jit over a tp mesh:
        exactly ONE all-reduce (the row-parallel psum) and ZERO
        all-gathers — the Megatron cost contract.  Guards the
        regression where the modules' partitioning metadata stops
        reaching GSPMD and the 'tensor-parallel' block silently runs
        replicated with no collectives at all (the exact state this
        test was written against)."""
        from horovod_tpu.parallel.mesh import make_parallel_mesh
        from horovod_tpu.parallel.tensor_parallel import (
            ColumnParallelDense,
            RowParallelDense,
        )

        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])

        class TpMlp(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = ColumnParallelDense(256, axis="tp")(x)
                h = nn.gelu(h)
                return RowParallelDense(128, axis="tp")(h)

        model = TpMlp()
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 128),
                              jnp.float32)
        variables = model.init(jax.random.PRNGKey(1), x)
        with jax.set_mesh(mesh):
            txt = jax.jit(model.apply).lower(variables, x).compile() \
                .as_text()
        ops = H.collective_ops(txt)
        assert H.count_by_kind(ops) == {"all-reduce": 1}, \
            [o.line for o in ops]
        (ar,) = ops
        assert ar.bytes == 16 * 128 * 4     # the block output, once

    def test_ring_attention_permutes_never_gathers(self, hvd_runtime):
        """Ring attention's compiled form moves K/V by collective
        permutes only — an all-gather would mean the O(T) sequence
        memory scaling silently regressed to O(T·sp)."""
        from horovod_tpu.parallel.mesh import make_parallel_mesh
        from horovod_tpu.parallel.ring_attention import ring_attention

        mesh = make_parallel_mesh(sp=8, devices=jax.devices("cpu")[:8])
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i),
                                     (2, 64, 4, 16), jnp.float32)
                   for i in range(3))

        def f(q, k, v):
            return ring_attention(q, k, v, "sp", causal=False)

        sm = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        ops = H.collective_ops(sm.lower(q, k, v).compile().as_text())
        kinds = H.count_by_kind(ops)
        assert kinds.get("collective-permute", 0) >= 1, kinds
        assert kinds.get("all-gather", 0) == 0, kinds
        assert kinds.get("all-reduce", 0) == 0, kinds


class TestGroupedAllreduceFusion:
    def test_grouped_mixed_dtypes_one_collective_per_group(
            self, hvd_runtime):
        """grouped_allreduce with mixed f32/bf16 leaves lowers to ONE
        all-reduce per dtype group — both f32 leaves concatenated into
        a single buffer, the bf16 leaf its own — the
        one-collective-per-cycle contract of the fusion buffer.  (A
        combiner-equipped XLA may further merge the two groups into one
        tuple-shaped op; this image's CPU pipeline does not, so the
        guard pins our own grouping.)"""
        from horovod_tpu.ops import collectives as C
        from horovod_tpu.runtime import state as S

        mesh = S.global_state().mesh
        leaves = [jnp.zeros((128,), jnp.float32),
                  jnp.zeros((64,), jnp.bfloat16),
                  jnp.zeros((32, 4), jnp.float32)]

        def f(*ls):
            return tuple(C.grouped_allreduce(list(ls), op=C.Sum,
                                             axis=("dcn", "ici")))

        sm = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(),) * 3, out_specs=(P(),) * 3,
            check_vma=False))
        ops = H.collective_ops(sm.lower(*leaves).compile().as_text())
        assert 1 <= len(ops) <= 2 and \
            all(o.kind == "all-reduce" for o in ops), \
            [o.line for o in ops]
        # payload complete: (128 + 32*4) f32 + the 64-elem bf16 leaf —
        # which the CPU backend may widen to f32 on the wire (2 or 4
        # bytes/elem), but must carry exactly once either way
        assert sum(o.bytes for o in ops) in (256 * 4 + 64 * 2,
                                             256 * 4 + 64 * 4)


class TestShardedExchangeHLO:
    """Guards for the ZeRO-style exchange: the compiled sharded step
    must move gradients by reduce-scatter + all-gather, never a
    full-gradient all-reduce — a silent fallback to all-reduce would
    pass every numerics test (same math) and only show up as 2x
    optimizer FLOPs and N x state memory on a real pod."""

    def test_sharded_step_reduce_scatters_not_allreduces(self, net_setup):
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map",
                                        shard_optimizer_states=True)
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        kinds = H.count_by_kind(ops)
        assert kinds.get("reduce-scatter", 0) >= 1, kinds
        assert kinds.get("all-gather", 0) >= 1, kinds
        # the ONLY all-reduce left is the 4-byte scalar loss; any
        # gradient-sized one means the exchange regressed to allreduce
        ars = [o for o in ops if o.kind == "all-reduce"]
        assert all(o.bytes == 4 for o in ars), \
            [(o.bytes, o.line) for o in ars]
        # reduce-scatter shard outputs cover the (padded) payload:
        # shard bytes x world >= the full gradient pytree
        rs_bytes = sum(o.bytes for o in ops if o.kind == "reduce-scatter")
        assert rs_bytes * 8 >= _grad_bytes(init)

    def test_bucketed_exchange_splits_collectives(self, net_setup):
        """exchange_bucket_bytes must yield one reduce-scatter per
        bucket — independent collectives XLA can start while later
        backward layers still compute.  A cap below the largest leaf
        still produces >= 2 buckets for this 6-leaf net."""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map",
                                        shard_optimizer_states=True,
                                        exchange_bucket_bytes=128 * 1024)
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        kinds = H.count_by_kind(ops)
        assert kinds.get("reduce-scatter", 0) >= 2, kinds

    def test_collectives_issue_as_start_done_pairs(self, net_setup):
        """Async issuance: every -start collective must close with a
        matching -done (a start whose done is missing or an op count
        mismatch means the async pairing broke).  The CPU test backend
        issues collectives synchronously — zero pairs is compliant
        here; on TPU the latency-hiding scheduler emits the async form
        and this guard requires it."""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map",
                                        shard_optimizer_states=True)
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        txt = step.compiled_text(params, opt, batch)
        for kind in ("reduce-scatter", "all-gather", "all-reduce"):
            starts = txt.count(f"{kind}-start(")
            dones = txt.count(f"{kind}-done(")
            assert starts == dones, (kind, starts, dones)
        if jax.devices()[0].platform == "tpu":
            ops = H.collective_ops(txt)
            assert any(o.asynchronous for o in ops
                       if o.kind in ("reduce-scatter", "all-gather")), \
                "TPU compile issued the sharded exchange synchronously"


class Net16(nn.Module):
    """``Net`` with every leaf cuttable by 8 (the head 16 wide)."""

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(256)(x)
        x = nn.relu(x)
        x = nn.Dense(256)(x)
        return nn.Dense(16)(x)


_BUFFER_RESULT = re.compile(
    r"= (\w+)\[([\d,]*)\]\S* (concatenate|dynamic-update-slice)\(")


def _largest_buffer_elements(text: str) -> int:
    """Elements of the largest ``concatenate`` / ``dynamic-update-slice``
    result anywhere in the module: what packing leaves behind."""
    sizes = [0]
    for _, dims, _ in _BUFFER_RESULT.findall(text):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        sizes.append(n)
    return max(sizes)


class TestLeafwiseExchangeHLO:
    """Guards for the plain sharded exchange: on a one-level topology,
    with nothing asked for that needs a buffer, the compiled step
    reduce-scatters and all-gathers every cuttable leaf in its own
    shape and builds no buffer of the model — and every request that
    does need a buffer still compiles the packed program."""

    W = 8

    def _compiled(self, hvd, model, init, bdata, **kw):
        from horovod_tpu import telemetry

        kw.setdefault("hierarchy", "flat")
        step = hvd.DistributedTrainStep(
            _loss_fn(model), optax.adamw(1e-3), mode="shard_map",
            shard_optimizer_states=True, **kw)
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        text = step.compiled_text(params, opt, batch)
        spans = [sp for sp in telemetry.spans.snapshot()
                 if sp.name == "train_step.compile"]
        return step, opt, text, spans[-1].attrs

    def test_one_scatter_and_one_gather_a_leaf(self, net_setup):
        from horovod_tpu.ops.collectives import scatter_dimension

        hvd, model, init, bdata = net_setup
        _, _, text, _ = self._compiled(hvd, model, init, bdata)
        ops = H.collective_ops(text)
        leaves = jax.tree_util.tree_leaves(init)
        cut = [x for x in leaves
               if scatter_dimension(x.shape, self.W) is not None]
        rest = [x for x in leaves
                if scatter_dimension(x.shape, self.W) is None]
        assert len(cut) == 5 and len(rest) == 1      # the (10,) bias
        rest_padded = -(-sum(x.size for x in rest) // self.W) * self.W
        rs = sorted(o.bytes for o in ops if o.kind == "reduce-scatter")
        ag = sorted(o.bytes for o in ops if o.kind == "all-gather")
        # a slab of every cuttable leaf, a shard of the remainder
        assert rs == sorted([x.size * 4 // self.W for x in cut]
                            + [rest_padded * 4 // self.W]), rs
        # every cuttable leaf gathered at its own bytes by ONE
        # all-gather; the remainder's shard level by level, as packed
        assert ag[-len(cut):] == sorted(x.size * 4 for x in cut), ag
        assert ag[-len(cut) - 1] == rest_padded * 4, ag
        assert all(b < rest_padded * 4 for b in ag[:-len(cut) - 1]), ag
        # each in the leaf's own rank: a 2-D weight stays 2-D
        shapes = {o.shapes[0][1] for o in ops if o.kind == "all-gather"}
        assert {tuple(x.shape) for x in cut} <= shapes, shapes
        # no leaf silently all-reduced: the scalar loss alone
        ars = [o for o in ops if o.kind == "all-reduce"]
        assert all(o.bytes == 4 for o in ars), \
            [(o.bytes, o.line) for o in ars]
        # and nothing packed that is larger than the largest leaf
        assert _largest_buffer_elements(text) <= \
            max(x.size for x in leaves)

    def test_packed_control_does_build_the_buffer(self, net_setup):
        """The sentence above is not vacuous: the packed program (an
        explicit bucket size) concatenates the whole model."""
        hvd, model, init, bdata = net_setup
        _, _, text, _ = self._compiled(hvd, model, init, bdata,
                                       exchange_bucket_bytes=1 << 30)
        total = sum(x.size for x in jax.tree_util.tree_leaves(init))
        assert _largest_buffer_elements(text) >= total

    @pytest.mark.parametrize("keeps", [
        "codec", "error_feedback", "two_level", "tree", "adasum",
        "bucket_bytes", "fused_on"])
    def test_a_request_that_needs_a_buffer_keeps_the_packed_program(
            self, net_setup, keeps):
        """One case a condition: the wire codec agrees one scale a
        buffer, error feedback carries a residual of the buffer's
        length, the two-level and tree exchanges scatter blocks of a
        buffer level by level, AdaSum combines pairs of blocks, an
        explicit bucket size asks for buffers of that size, and the
        tail tiling asked for by name tiles a buffer's tail."""
        hvd, model, init, bdata = net_setup
        kw = {
            "codec": {"compression": hvd.Compression.int8},
            "error_feedback": {"compression": hvd.Compression.int8,
                               "error_feedback": True},
            "two_level": {"hierarchy": "two_level"},
            "tree": {"hierarchy": "tree"},
            "adasum": {"reduction": "adasum"},
            "bucket_bytes": {"exchange_bucket_bytes": 1 << 30},
            "fused_on": {"fused_collectives": "on"},
        }[keeps]
        step, opt, text, attrs = self._compiled(hvd, model, init, bdata,
                                                **kw)
        leaves = jax.tree_util.tree_leaves(init)
        total = sum(x.size for x in leaves)
        # the state is 1-D slices of group buffers under group keys
        assert set(opt.inner[0].mu) == {"b0/float32"}
        assert opt.inner[0].mu["b0/float32"].shape == \
            (-(-total // self.W),)
        # the program packs the whole model into one buffer
        assert _largest_buffer_elements(text) >= total
        # and the step says so
        assert attrs["exchange_leaf_ops"] == 0
        assert attrs["exchange_packed_leaves"] == len(leaves)
        assert attrs["exchange_packed_bytes"] == total * 4
        assert step.fused_collectives == \
            ("on" if keeps == "fused_on" else "off")

    def test_span_attributes_read_as_the_tree_dictates(self, net_setup):
        hvd, _, _, bdata = net_setup
        # every leaf cuttable: n / 0 / 0
        model = Net16()
        init = jax.jit(model.init)(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 64)))
        _, opt, _, attrs = self._compiled(hvd, model, init, bdata)
        assert (attrs["exchange_leaf_ops"], attrs["exchange_packed_leaves"],
                attrs["exchange_packed_bytes"]) == (6, 0, 0)
        # and the state is the optimizer's own over the tree of slabs
        mu = opt.inner[0].mu["params"]
        assert mu["Dense_1"]["kernel"].shape == (256 // self.W, 256)
        # the (10,) bias of ``Net`` cannot be cut: 5 / 1 / 40 bytes
        hvd, model, init, bdata = net_setup
        _, _, _, attrs = self._compiled(hvd, model, init, bdata)
        assert (attrs["exchange_leaf_ops"], attrs["exchange_packed_leaves"],
                attrs["exchange_packed_bytes"]) == (5, 1, 40)

    def test_auto_on_one_level_runs_leaf_by_leaf(self, net_setup):
        """``hierarchy="auto"`` over axes of which one alone exceeds 1
        resolves to one level: the plain exchange, with no tail for
        ``fused_collectives="auto"`` to tile."""
        from jax.sharding import Mesh

        import numpy as np

        hvd, model, init, bdata = net_setup
        mesh = Mesh(np.asarray(jax.devices("cpu")[:8]).reshape(1, 8),
                    ("dcn", "ici"))
        step, _, text, attrs = self._compiled(
            hvd, model, init, bdata, hierarchy="auto", mesh=mesh)
        assert step.exchange_hierarchy == "flat"
        assert step.fused_collectives == "off"
        assert attrs["exchange_leaf_ops"] == 5
        kinds = H.count_by_kind(H.collective_ops(text))
        assert kinds.get("reduce-scatter", 0) == 6, kinds


class TestHierarchicalExchangeHLO:
    """Guards for the two-level (topology-aware) exchange: the compiled
    step must carry TWO distinct reduce-scatter scopes — the intra-slice
    (ici, group size 4 on the 2x4 mesh) and cross-slice (dcn, group
    size 2) levels — and still no gradient-sized all-reduce.  A silent
    fallback to the flat single-scope exchange would pass every
    numerics test (same math) and only show up as full-payload DCN
    traffic on a real pod; these guards fail instead."""

    def _two_level_ops(self, net_setup, **kw):
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map",
                                        shard_optimizer_states=True,
                                        hierarchy="two_level", **kw)
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        return step, H.collective_ops(step.compiled_text(params, opt,
                                                         batch))

    def test_two_distinct_reduce_scatter_scopes(self, net_setup):
        _, ops = self._two_level_ops(net_setup)
        scopes = H.scopes_by_kind(ops)
        # one scope per mesh level: ici (4) and dcn (2); the flat
        # exchange would show a single world-sized (8) scope
        assert scopes.get("reduce-scatter") == (2, 4), scopes
        assert 8 not in scopes.get("reduce-scatter", ()), scopes
        # the gather phase mirrors the scopes (cross-slice + intra)
        assert set(scopes.get("all-gather", ())) == {2, 4}, scopes

    def test_no_gradient_sized_allreduce(self, net_setup):
        _, ops = self._two_level_ops(net_setup)
        ars = [o for o in ops if o.kind == "all-reduce"]
        # the ONLY all-reduce left is the 4-byte scalar loss
        assert all(o.bytes == 4 for o in ars), \
            [(o.bytes, o.line) for o in ars]
        # payload conservation: intra-level reduce-scatter shard
        # outputs cover the (padded) gradient pytree
        rs_bytes = sum(o.bytes for o in ops
                       if o.kind == "reduce-scatter" and o.group_size == 4)
        assert rs_bytes * 4 >= _grad_bytes(net_setup[2])

    def test_bucketed_two_level_splits_both_scopes(self, net_setup):
        """exchange_bucket_bytes composes with the hierarchy: each
        bucket gets its own intra- AND cross-slice reduce-scatter."""
        _, ops = self._two_level_ops(net_setup,
                                     exchange_bucket_bytes=128 * 1024)
        per_scope: dict = {}
        for o in ops:
            if o.kind == "reduce-scatter":
                per_scope[o.group_size] = per_scope.get(o.group_size, 0) + 1
        assert per_scope.get(4, 0) >= 2, per_scope
        assert per_scope.get(2, 0) >= 2, per_scope

    def test_async_start_done_pairing(self, net_setup):
        """Every -start collective of the two-level exchange closes
        with a matching -done (the async issuance the per-level overlap
        depends on; the CPU backend may issue synchronously — zero
        pairs — which is compliant here, required async on TPU)."""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map",
                                        shard_optimizer_states=True,
                                        hierarchy="two_level")
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        txt = step.compiled_text(params, opt, batch)
        for kind in ("reduce-scatter", "all-gather", "all-reduce"):
            starts = txt.count(f"{kind}-start(")
            dones = txt.count(f"{kind}-done(")
            assert starts == dones, (kind, starts, dones)
        if jax.devices()[0].platform == "tpu":
            ops = H.collective_ops(txt)
            assert any(o.asynchronous for o in ops
                       if o.kind in ("reduce-scatter", "all-gather")), \
                "TPU compile issued the two-level exchange synchronously"

    def test_auto_on_factored_mesh_equals_two_level_structure(
            self, net_setup):
        """hierarchy='auto' on the 2x4 mesh must compile the SAME
        scope structure as the explicit two_level — the auto decision
        is structural, not advisory."""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map",
                                        shard_optimizer_states=True,
                                        hierarchy="auto")
        assert step.exchange_hierarchy == "two_level"
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        assert H.scopes_by_kind(ops).get("reduce-scatter") == (2, 4)

    def test_flat_keeps_single_scope(self, net_setup):
        """hierarchy='flat' pins the PR-1 single-scope exchange — the
        knob must actually select topologies, not alias them."""
        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(_loss_fn(model), optax.adamw(1e-3),
                                        mode="shard_map",
                                        shard_optimizer_states=True,
                                        hierarchy="flat")
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        assert H.scopes_by_kind(ops).get("reduce-scatter") == (8,)


class TestHloParser:
    def test_parses_tuple_allreduce(self):
        line = ("  %all-reduce.7 = (f32[256]{0}, bf16[256,64]{1,0}, f32[]) "
                "all-reduce(%a, %b, %c), channel_id=1, "
                "replica_groups=[1,8]<=[8], to_apply=%add")
        (op,) = H.collective_ops(line)
        assert op.kind == "all-reduce"
        assert op.shapes == [("f32", (256,)), ("bf16", (256, 64)),
                             ("f32", ())]
        assert op.bytes == 256 * 4 + 256 * 64 * 2 + 4
        assert op.group_size == 8

    def test_parses_explicit_groups_and_async(self):
        # TPU async form: result is an (input, output) tuple — payload
        # must count the gathered output only, not input+output
        text = "\n".join([
            "  %ag = (f32[8,128]{1,0}, f32[64,128]{1,0}) "
            "all-gather-start(%x), "
            "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}",
            "  %done = f32[64,128]{1,0} all-gather-done(%ag)",
        ])
        ops = H.collective_ops(text)
        assert len(ops) == 1          # start/done pair counts once
        assert ops[0].kind == "all-gather"
        assert ops[0].group_size == 4
        assert ops[0].bytes == 64 * 128 * 4

    def test_parses_async_reduce_scatter_pair(self):
        # TPU async reduce-scatter: start result is an (input, output)
        # tuple; payload counts the scattered output only, the op
        # carries asynchronous=True, and the -done line doesn't
        # double-count
        text = "\n".join([
            "  %rs = (f32[104]{0}, f32[13]{0}) reduce-scatter-start(%x), "
            "replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add",
            "  %rsd = f32[13]{0} reduce-scatter-done(%rs)",
        ])
        (op,) = H.collective_ops(text)
        assert op.kind == "reduce-scatter"
        assert op.asynchronous
        assert op.bytes == 13 * 4
        assert op.group_size == 8

    def test_sync_op_not_marked_async(self):
        line = ("  %rs = f32[13]{0} reduce-scatter(%x), channel_id=1, "
                "replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add")
        (op,) = H.collective_ops(line)
        assert not op.asynchronous
        assert op.bytes == 13 * 4

    def test_ignores_non_collective_lines(self):
        text = "  %dot.5 = f32[256,256]{1,0} dot(%a, %b)"
        assert H.collective_ops(text) == []

    def test_parses_tuple_wrapped_in_extra_parens(self):
        # newer XLA wraps the async (input, output) tuple in an extra
        # paren level and appends a u32[] context scalar:
        # ((f32[...], f32[...]), u32[]) — the old _OP_RE/shape handling
        # picked the context scalar as the payload
        line = ("  %rs = ((f32[104]{0}, f32[13]{0}), u32[]) "
                "reduce-scatter-start(%x), replica_groups=[1,8]<=[8], "
                "dimensions={0}, to_apply=%add")
        (op,) = H.collective_ops(line)
        assert op.kind == "reduce-scatter"
        assert op.asynchronous
        assert op.bytes == 13 * 4
        assert op.group_size == 8

    def test_context_scalar_not_mistaken_for_output(self):
        # the (payload, u32[]) two-element variant: element 1 is the
        # context scalar, NOT the gathered output — payload must be the
        # f32 tensor, not 4 bytes
        line = ("  %ag = (f32[64,128]{1,0}, u32[]) all-gather-start(%x), "
                "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}")
        (op,) = H.collective_ops(line)
        assert op.kind == "all-gather"
        assert op.bytes == 64 * 128 * 4

    def test_context_scalar_not_counted_in_allreduce_payload(self):
        line = ("  %ar = (f32[256]{0}, u32[]) all-reduce-start(%a), "
                "channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add")
        (op,) = H.collective_ops(line)
        assert op.bytes == 256 * 4

    def test_parses_missing_separator_space(self):
        # some dumps drop the space between the result tuple and the op
        line = ("  %rs = (f32[104]{0}, f32[13]{0})reduce-scatter-start"
                "(%x), replica_groups=[1,8]<=[8], dimensions={0}, "
                "to_apply=%add")
        (op,) = H.collective_ops(line)
        assert op.kind == "reduce-scatter"
        assert op.bytes == 13 * 4

    def test_tile_layout_parens_in_layout_block(self):
        line = ("  %rs = (f32[104]{0:T(256)}, f32[13]{0:T(256)S(1)}) "
                "reduce-scatter-start(%x), replica_groups=[1,8]<=[8], "
                "dimensions={0}, to_apply=%add")
        (op,) = H.collective_ops(line)
        assert op.bytes == 13 * 4


class TestFusedCollectiveHLO:
    """Guards for the tile-fused matmul⊗collective path (ISSUE 9): with
    ``fused_collectives="on"`` the compiled module must carry NO
    full-width serial collective at the parallelism boundary — the
    tensor-parallel boundaries lower to ppermute rings and the ZeRO
    final bucket to tile-granular sub-collectives.  A silent fall-back
    to the unfused schedule would pass every numerics test (same math)
    and only show up as an exposed exchange tail on a real pod; these
    guards fail instead."""

    W = 8

    def _tp_mesh(self):
        import numpy as np
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices("cpu")[:self.W])
        return Mesh(devs.reshape(self.W), ("tp",))

    def _lowered(self, fn, *args):
        sm = jax.jit(jax.shard_map(
            fn, mesh=self._tp_mesh(), in_specs=(P(),) * len(args),
            out_specs=P(), check_vma=False))
        return sm.lower(*args).compile().as_text()

    def test_matmul_reducescatter_ring_replaces_collective(
            self, hvd_runtime):
        from horovod_tpu.ops.pallas_kernels import matmul_reducescatter

        x = jnp.zeros((64, 16), jnp.float32)
        w = jnp.zeros((16, 8), jnp.float32)

        def fused(x, w):
            return jnp.sum(matmul_reducescatter(x, w, "tp", fused=True))

        def unfused(x, w):
            return jnp.sum(matmul_reducescatter(x, w, "tp", fused=False))

        ops = H.collective_ops(self._lowered(fused, x, w))
        kinds = H.count_by_kind(ops)
        # the boundary-wide reduce-scatter is GONE; the wire is the
        # ppermute ring (one hop per non-local tile, possibly emitted
        # as send/recv pairs — require at least world-1 hops)
        assert kinds.get("reduce-scatter", 0) == 0, kinds
        assert kinds.get("all-reduce", 0) == 0, kinds
        assert kinds.get("collective-permute", 0) >= self.W - 1, kinds
        ops_u = H.collective_ops(self._lowered(unfused, x, w))
        assert H.count_by_kind(ops_u).get("reduce-scatter", 0) == 1, \
            [o.line for o in ops_u]

    def test_allgather_matmul_ring_replaces_collective(self,
                                                       hvd_runtime):
        from horovod_tpu.ops.pallas_kernels import allgather_matmul

        x = jnp.zeros((4, 16), jnp.float32)
        w = jnp.zeros((16, 8), jnp.float32)

        def fused(x, w):
            return jnp.sum(allgather_matmul(x, w, "tp", fused=True))

        def unfused(x, w):
            return jnp.sum(allgather_matmul(x, w, "tp", fused=False))

        kinds = H.count_by_kind(
            H.collective_ops(self._lowered(fused, x, w)))
        assert kinds.get("all-gather", 0) == 0, kinds
        assert kinds.get("collective-permute", 0) >= self.W - 1, kinds
        kinds_u = H.count_by_kind(
            H.collective_ops(self._lowered(unfused, x, w)))
        assert kinds_u.get("all-gather", 0) == 1, kinds_u

    def test_zero_final_bucket_goes_tile_granular(self, net_setup):
        """fused_collectives="on" keeps the packed exchange and splits
        its final bucket into FUSED_TAIL_TILES independent
        reduce-scatters, each strictly smaller than the unfused packed
        monolith (asked for by what keeps a buffer: an explicit bucket
        size that holds every leaf) — no full-width serial collective
        remains at the boundary.  The plain call (``"off"``, one
        level) has no buffer and so no tail: one reduce-scatter a
        cuttable leaf and one for the remainder group."""
        from horovod_tpu.ops.collectives import (
            FUSED_TAIL_TILES,
            scatter_dimension,
        )

        hvd, model, init, bdata = net_setup

        def build(fused, **kw):
            step = hvd.DistributedTrainStep(
                _loss_fn(model), optax.adamw(1e-3), mode="shard_map",
                shard_optimizer_states=True, hierarchy="flat",
                fused_collectives=fused, **kw)
            params, opt = step.init(init)
            batch = step.shard_batch(bdata)
            return step, H.collective_ops(
                step.compiled_text(params, opt, batch))

        step_on, ops_on = build("on")
        step_off, ops_off = build("off", exchange_bucket_bytes=1 << 30)
        step_plain, ops_plain = build("off")
        assert step_on.fused_collectives == "on"
        assert step_off.fused_collectives == "off"
        assert step_plain.fused_collectives == "off"
        rs_on = [o for o in ops_on if o.kind == "reduce-scatter"]
        rs_off = [o for o in ops_off if o.kind == "reduce-scatter"]
        rs_plain = [o for o in ops_plain if o.kind == "reduce-scatter"]
        assert len(rs_off) == 1, [o.line for o in rs_off]
        assert len(rs_on) == FUSED_TAIL_TILES, [o.line for o in rs_on]
        cuttable = sum(
            scatter_dimension(x.shape, 8) is not None
            for x in jax.tree_util.tree_leaves(init))
        assert len(rs_plain) == cuttable + 1, [o.line for o in rs_plain]
        # tile-granular: every fused RS moves less than the monolith
        assert max(o.bytes for o in rs_on) < rs_off[0].bytes
        # payload conservation: the tiles still cover the whole shard
        assert sum(o.bytes for o in rs_on) == rs_off[0].bytes
        # and no gradient-sized all-reduce crept back in
        for ops in (ops_on, ops_plain):
            ars = [o for o in ops if o.kind == "all-reduce"]
            assert all(o.bytes == 4 for o in ars), \
                [(o.bytes, o.line) for o in ars]

    def test_two_level_fused_tail_tiles_the_inner_phase(self, net_setup):
        """The fused tail composes with the hierarchy: the final
        bucket's intra-slice (ici, scope 4) reduce-scatter goes
        tile-granular while the DCN phase keeps its single collective
        per bucket."""
        from horovod_tpu.ops.collectives import FUSED_TAIL_TILES

        hvd, model, init, bdata = net_setup
        step = hvd.DistributedTrainStep(
            _loss_fn(model), optax.adamw(1e-3), mode="shard_map",
            shard_optimizer_states=True, hierarchy="two_level",
            fused_collectives="on")
        params, opt = step.init(init)
        batch = step.shard_batch(bdata)
        ops = H.collective_ops(step.compiled_text(params, opt, batch))
        per_scope: dict = {}
        for o in ops:
            if o.kind == "reduce-scatter":
                per_scope[o.group_size] = per_scope.get(o.group_size,
                                                        0) + 1
        assert per_scope.get(4, 0) == FUSED_TAIL_TILES, per_scope
        assert per_scope.get(2, 0) == 1, per_scope

    def test_fused_tp_apply_has_no_boundary_collective(self,
                                                       hvd_runtime):
        """The fused sequence-parallel transformer: zero all-reduces
        anywhere (the Megatron psum per block is gone), ppermute rings
        at every matmul boundary, and exactly ONE all-gather — the
        final-logits reassembly after ln_f."""
        import flax.core

        from horovod_tpu.models.transformer import (
            TransformerConfig,
            TransformerLM,
            fused_tp_apply,
        )

        cfg = TransformerConfig(
            vocab_size=97, num_layers=2, num_heads=8, d_model=64,
            d_ff=128, max_seq_len=32, dtype=jnp.float32,
            attention_impl="dense", fused_collectives="on")
        model = TransformerLM(cfg)
        tokens = jnp.zeros((2, 32), jnp.int32)
        variables = flax.core.meta.unbox(
            jax.jit(model.init)(jax.random.PRNGKey(0), tokens))

        def f(v, toks):
            return fused_tp_apply(v, cfg, toks)

        sm = jax.jit(jax.shard_map(
            f, mesh=self._tp_mesh(), in_specs=(P(), P()),
            out_specs=P(), check_vma=False))
        ops = H.collective_ops(
            sm.lower(variables, tokens).compile().as_text())
        kinds = H.count_by_kind(ops)
        assert kinds.get("all-reduce", 0) == 0, kinds
        assert kinds.get("reduce-scatter", 0) == 0, kinds
        assert kinds.get("collective-permute", 0) >= self.W - 1, kinds
        assert kinds.get("all-gather", 0) == 1, kinds


class TestFusedExpertDispatchHLO:
    """Guards for the fused ``a2a ⊗ expert-matmul`` MoE dispatch
    (ISSUE 16 tentpole): under a dp×ep×tp plan with
    ``fused_dispatch="on"`` the compiled program must carry ZERO
    boundary-wide all-to-alls — the dispatch/combine exchange is the
    ppermute ring — and no serial all-to-all tail window.  A silent
    fall-back to the unfused schedule is numerically invisible and
    only shows up as an exposed expert exchange on a real pod; these
    guards fail instead."""

    def _lowered_switch_ffn(self, mode, ep=2):
        """Compiled text of a SwitchFFN forward on a dp×ep×tp mesh."""
        from horovod_tpu.models.moe import MoEConfig, SwitchFFN
        from horovod_tpu.parallel.mesh import make_parallel_mesh

        mesh = make_parallel_mesh(dp=2, ep=ep, tp=8 // (2 * ep),
                                  devices=jax.devices("cpu")[:8])
        cfg = MoEConfig(
            vocab_size=64, num_layers=2, num_heads=2, d_model=32,
            d_ff=64, max_seq_len=16, dtype=jnp.float32, num_experts=4,
            capacity_factor=8.0, moe_every=2, ep_axis="ep",
            fused_dispatch=mode)
        ffn = SwitchFFN(cfg)
        x = jnp.zeros((4, 8, 32), jnp.float32)
        local_init = SwitchFFN(
            MoEConfig(vocab_size=64, num_layers=2, num_heads=2,
                      d_model=32, d_ff=64, max_seq_len=16,
                      dtype=jnp.float32, num_experts=4,
                      capacity_factor=8.0, moe_every=2))
        params = local_init.init(jax.random.PRNGKey(0), x)["params"]

        sm = jax.jit(jax.shard_map(
            lambda p, x: ffn.apply({"params": p}, x), mesh=mesh,
            in_specs=(P(), P(("dp", "ep"))),
            out_specs=P(("dp", "ep")), check_vma=False))
        return sm.lower(params, x).compile().as_text()

    def test_fused_program_has_zero_alltoalls(self, hvd_runtime):
        text = self._lowered_switch_ffn("on")
        kinds = H.count_by_kind(H.collective_ops(text))
        assert kinds.get("all-to-all", 0) == 0, kinds
        # the exchange is the ring: >= 2·(ep−1) permute hops (dispatch
        # + combine directions; XLA may emit more as send/recv pairs)
        assert kinds.get("collective-permute", 0) >= 2, kinds
        # no serial boundary-wide dispatch window left to expose
        assert H.serial_tail_collectives(
            text, kinds=("all-to-all",)) == 0

    def test_unfused_control_keeps_alltoalls(self, hvd_runtime):
        text = self._lowered_switch_ffn("off")
        kinds = H.count_by_kind(H.collective_ops(text))
        assert kinds.get("all-to-all", 0) >= 1, kinds

    def test_eight_way_ring_scales_with_world(self, hvd_runtime):
        """At ep=8 the fused program still has zero all-to-alls and at
        least 2·(W−1) = 14 ring hops."""
        from horovod_tpu.models.moe import MoEConfig, SwitchFFN
        from horovod_tpu.parallel.mesh import make_parallel_mesh

        mesh = make_parallel_mesh(ep=8, devices=jax.devices("cpu")[:8])
        cfg = MoEConfig(
            vocab_size=64, num_layers=2, num_heads=2, d_model=32,
            d_ff=64, max_seq_len=16, dtype=jnp.float32, num_experts=8,
            capacity_factor=8.0, moe_every=2, ep_axis="ep",
            fused_dispatch="on")
        ffn = SwitchFFN(cfg)
        x = jnp.zeros((8, 8, 32), jnp.float32)
        params = SwitchFFN(
            MoEConfig(vocab_size=64, num_layers=2, num_heads=2,
                      d_model=32, d_ff=64, max_seq_len=16,
                      dtype=jnp.float32, num_experts=8,
                      capacity_factor=8.0, moe_every=2)).init(
                          jax.random.PRNGKey(0), x)["params"]
        sm = jax.jit(jax.shard_map(
            lambda p, x: ffn.apply({"params": p}, x), mesh=mesh,
            in_specs=(P(), P("ep")), out_specs=P("ep"),
            check_vma=False))
        text = sm.lower(params, x).compile().as_text()
        kinds = H.count_by_kind(H.collective_ops(text))
        assert kinds.get("all-to-all", 0) == 0, kinds
        assert kinds.get("collective-permute", 0) >= 14, kinds


class TestRingFlashHLO:
    """Guards for the fused sp ring-flash attention (ISSUE 17
    tentpole): under an sp>1 plan the compiled program must carry ZERO
    full-sequence all-gathers — the K/V exchange is the ppermute ring,
    2·(sp−1) hops minimum — and no serial permute tail window.  A
    silent degeneration to gather-everything is numerically invisible
    (same softmax) and only shows up as O(T) per-chip memory on a real
    pod; these guards fail instead."""

    def _lowered_ring(self, sp, fused, causal=True):
        from horovod_tpu.parallel.mesh import make_parallel_mesh
        from horovod_tpu.parallel.ring_attention import ring_attention

        mesh = make_parallel_mesh(sp=sp,
                                  devices=jax.devices("cpu")[:sp])
        spec = P(None, "sp", None, None)
        shape = (2, sp * 32, 4, 16)
        q = jnp.zeros(shape, jnp.float32)

        def f(q_, k_, v_):
            def loss(qq):
                o = ring_attention(qq, k_, v_, "sp", causal=causal,
                                   fused=fused, interpret=True)
                return (o.astype(jnp.float32) ** 2).sum(), o

            (_, o), dq = jax.value_and_grad(loss, has_aux=True)(q_)
            return o, dq

        sm = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(spec,) * 3,
            out_specs=(spec, spec), check_vma=False))
        return sm.lower(q, q, q).compile().as_text()

    @pytest.mark.parametrize("sp", [2, 4])
    def test_fused_ring_is_allgather_free(self, hvd_runtime, sp):
        text = self._lowered_ring(sp, fused=True)
        kinds = H.count_by_kind(H.collective_ops(text))
        assert kinds.get("all-gather", 0) == 0, kinds
        # K and V each hop sp−1 times forward + the dK/dV ring back
        assert kinds.get("collective-permute", 0) >= 2 * (sp - 1), kinds
        assert H.serial_tail_collectives(
            text, kinds=("collective-permute",)) == 0

    def test_jnp_ring_is_also_allgather_free(self, hvd_runtime):
        """The fallback formulation shares the wire contract: the jnp
        scan rides the same ppermute ring, never a gather."""
        text = self._lowered_ring(2, fused=False)
        kinds = H.count_by_kind(H.collective_ops(text))
        assert kinds.get("all-gather", 0) == 0, kinds
        assert kinds.get("collective-permute", 0) >= 2, kinds
