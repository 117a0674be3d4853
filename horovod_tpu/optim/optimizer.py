"""DistributedOptimizer / DistributedGradientTape for JAX training.

The reference wraps a framework optimizer so gradients are allreduced
before ``step()``: torch hooks per-parameter grad accumulators and fires
async allreduces as each gradient is produced
(``torch/optimizer.py:103-200``), TF rewrites ``compute_gradients``
(``tensorflow/__init__.py:289-316``), both honoring
``backward_passes_per_step`` accumulation and compression.

optax formulation: gradient averaging is itself a gradient transformation,
so ``DistributedOptimizer(opt)`` = ``chain(distributed_gradients(...),
opt)``, wrapped in ``optax.MultiSteps`` when ``backward_passes_per_step >
1``.  Three reduction modes, because JAX has three distribution idioms:

* ``"shard_map"`` (default): the transform runs inside
  ``shard_map``/``pmap`` with mesh axes bound; gradients are reduced with
  one fused in-graph collective per dtype
  (:func:`horovod_tpu.ops.collectives.grouped_allreduce`) which XLA
  overlaps with backward compute — the role of the reference's
  hook-fired async NCCL calls.
* ``"pjit"``: under global-array pjit the batch axis is sharded and XLA
  already inserts the gradient psum during autodiff; the transform is the
  identity (documented no-op, so user code is portable between modes).
* ``"process"``: host-level eager reduction across worker processes via
  the async-handle API (the closest literal analogue of the reference's
  per-tensor enqueue path).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import os

import jax
import jax.numpy as jnp
import optax

from horovod_tpu import telemetry
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.collectives import Average, ReduceOp
from horovod_tpu.runtime.topology import (
    GLOBAL_AXES,
    HIERARCHY_MODES,
    TOPOLOGY_MODES,
    resolve_hierarchy,
    resolve_topology,
)

AxisSpec = Union[str, Sequence[str]]


def _sparse_leaf_reduce(g: jax.Array, max_rows: int, op: ReduceOp,
                        axis: AxisSpec,
                        prescale_factor: Optional[float] = None,
                        postscale_factor: Optional[float] = None
                        ) -> jax.Array:
    """Row-sparse reduction of one dense-shaped gradient leaf.

    JAX embedding gradients arrive dense (scatter-add of the used rows),
    so the IndexedSlices decomposition is recovered in-graph: the leaf's
    nonzero rows are extracted with a static ``max_rows`` bound
    (``jnp.nonzero(size=...)`` keeps shapes XLA-static) and exchanged via
    :func:`~horovod_tpu.ops.collectives.sparse_allreduce` — allgather of
    ``max_rows`` rows per shard instead of a dense allreduce of the full
    table (reference IndexedSlices path,
    ``tensorflow/__init__.py:100-110``).  Fill slots use the
    out-of-range index ``V``: their gathered values read as zero and the
    scatter drops them.  Rows beyond ``max_rows`` are silently dropped —
    the bound is the caller's promise about touched rows per step.
    """
    rows = g.shape[0]
    mask = jnp.any(g.reshape(rows, -1) != 0, axis=1)
    if os.environ.get("HOROVOD_DEBUG_SPARSE"):
        # opt-in: surface silent gradient truncation (rows beyond the
        # bound are dropped by design; misconfigured bounds degrade
        # training with no other signal)
        touched = jnp.sum(mask)
        jax.lax.cond(
            touched > max_rows,
            lambda: jax.debug.print(
                "sparse_params: {} touched rows exceed max_rows={}; "
                "excess gradients dropped", touched, max_rows),
            lambda: None)
    (idx,) = jnp.nonzero(mask, size=max_rows, fill_value=rows)
    vals = jnp.take(g, idx, axis=0, mode="fill", fill_value=0)
    vals = C._scale(vals, prescale_factor)
    out = C.sparse_allreduce(vals, idx, dense_rows=rows, axis=axis, op=op)
    return C._scale(out, postscale_factor)


def _path_components(path) -> list:
    """Flattened-path entries as plain strings (dict keys, attr names,
    sequence indices)."""
    out = []
    for entry in path:
        for attr in ("key", "name", "idx"):
            if hasattr(entry, attr):
                out.append(str(getattr(entry, attr)))
                break
        else:
            out.append(str(entry))
    return out


def _match_sparse(path, sparse_params) -> Optional[int]:
    """max_rows for a leaf whose path has a component equal to a
    configured name (or whose full '/'-joined path equals one), else
    None.  Whole-component matching: a pattern 'emb' must not
    accidentally route a dense leaf named 'member' through the
    truncating sparse path."""
    if not sparse_params:
        return None
    comps = _path_components(path)
    joined = "/".join(comps)
    for pat, max_rows in sparse_params.items():
        if pat == joined or pat in comps:
            return int(max_rows)
    return None


def distributed_gradients(op: ReduceOp = Average,
                          axis: AxisSpec = GLOBAL_AXES,
                          mode: str = "shard_map",
                          compression=None,
                          prescale_factor: Optional[float] = None,
                          postscale_factor: Optional[float] = None,
                          sparse_params: Optional[dict] = None
                          ) -> optax.GradientTransformation:
    """optax transform that cross-replica-reduces gradients.

    The composable core of :func:`DistributedOptimizer`; usable standalone
    in any optax chain.

    ``sparse_params`` maps leaf-path component names (e.g.
    ``"embedding"``, or a full ``"encoder/embedding"`` path) to a
    ``max_rows`` bound; matching leaves are reduced through the
    row-sparse allgather path instead of the dense allreduce — the
    reference's IndexedSlices routing (``tensorflow/__init__.py:100-110``,
    ``sparse_as_dense`` being the knob that turns it *off* there; here
    dense is already the default and ``sparse_params`` is the opt-in).
    Requires ``mode='shard_map'``.
    """
    if sparse_params and mode != "shard_map":
        raise ValueError(
            "sparse_params requires mode='shard_map' (pjit autodiff "
            "reduces densely; the process plane exchanges whole tensors)")
    if sparse_params and op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("sparse_params supports op=Sum/Average")

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        if mode == "pjit":
            reduced = leaves  # XLA autodiff already reduced (see docstring)
        elif mode == "shard_map":
            sparse_rows: dict = {}
            if sparse_params:
                paths = jax.tree_util.tree_flatten_with_path(updates)[0]
                for i, (path, _) in enumerate(paths):
                    m = _match_sparse(path, sparse_params)
                    if m is not None:
                        sparse_rows[i] = m
            ins = [g for i, g in enumerate(leaves) if i not in sparse_rows]
            # Compression.int8 is a wire-*reduction* marker, not a
            # compressor: the shared-scale quantized psum runs inside
            # grouped_allreduce (see compression.Int8WireReduction)
            qbits = getattr(compression, "wire_reduce_bits", None)
            ctxs = None
            # the scope ``exchange`` of the compiled step, laid here so
            # that an optimizer chain outside DistributedTrainStep (or
            # inside its ``update``) is named too: codecs, the
            # collectives, what undoes them
            with jax.named_scope("exchange"):
                if compression is not None and qbits is None:
                    pairs = [compression.compress(g) for g in ins]
                    ins = [p[0] for p in pairs]
                    ctxs = [p[1] for p in pairs]
                dense = C.grouped_allreduce(
                    ins, op=op, axis=axis,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    quantized_bits=qbits)
                if ctxs is not None:
                    dense = [compression.decompress(r, c)
                             for r, c in zip(dense, ctxs)]
                dense_iter = iter(dense)
                reduced = [
                    _sparse_leaf_reduce(g, sparse_rows[i], op, axis,
                                        prescale_factor, postscale_factor)
                    if i in sparse_rows else next(dense_iter)
                    for i, g in enumerate(leaves)]
        elif mode == "process":
            from horovod_tpu.ops import eager

            handles = [
                eager.allreduce_async(g, op=op,
                                      prescale_factor=prescale_factor,
                                      postscale_factor=postscale_factor,
                                      compression=compression)
                for g in leaves]
            reduced = [eager.synchronize(h) for h in handles]
        else:
            raise ValueError(f"unknown mode {mode!r}")
        return jax.tree_util.tree_unflatten(treedef, reduced), state

    return optax.GradientTransformation(init_fn, update_fn)


class ShardedOptimizerState(NamedTuple):
    """State of :func:`sharded_distributed_update`: the wrapped
    optimizer's state over this rank's gradient shards — 1/N of the
    replicated-state footprint per rank.  On the plain (leaf-by-leaf)
    path ``inner`` is the optimizer's state over the caller's own tree
    with every leaf its ``1/N`` slab (beside the packed remainder
    group's shards, if any leaf cannot be cut); on the packed path,
    over ``{"b<bucket>/<dtype>": 1-D slice}``.

    ``residuals`` (``error_feedback=True`` only, else None) carries the
    per-group quantization residuals of the low-precision wire — fp32,
    full padded buffer length per group (each rank compensates its own
    pre-reduction contribution, which is full-length)."""

    inner: object
    residuals: Optional[object] = None

    def reset_residuals(self) -> "ShardedOptimizerState":
        """Zeroed-residual copy of this state — the hygiene hook for
        switching the exchange's ``reduction`` operator (or wire codec)
        mid-run (degrade/promote, autotune re-measure): an EF residual
        telescopes against ONE operator's reduction structure, so a
        residual accumulated under sum is pure noise injected into the
        first adasum step (and vice versa).  No-op when error feedback
        is off."""
        if self.residuals is None:
            return self
        return self._replace(
            residuals=jax.tree_util.tree_map(jnp.zeros_like,
                                             self.residuals))


def _static_world(axis: AxisSpec) -> int:
    """World size of ``axis`` as a static int — from the bound mesh
    axes when tracing inside shard_map, else from the runtime mesh
    (init-time use outside the mesh context)."""
    try:
        return int(C.axis_size(axis))
    except Exception:
        pass
    from horovod_tpu.runtime import state as _rt

    if _rt.is_initialized():
        mesh = _rt.global_state().mesh
        names = (axis,) if isinstance(axis, str) else tuple(axis)
        n = 1
        for a in names:
            n *= mesh.shape[a]
        return n
    raise RuntimeError(
        "sharded optimizer needs a bound mesh axis (inside shard_map) "
        "or an initialized runtime to size its shards; call hvd.init() "
        "first")


def _static_axis_sizes(axis: AxisSpec) -> Tuple[int, ...]:
    """Per-axis extents of ``axis``, static — bound mesh axes when
    tracing inside shard_map, else the runtime mesh (the same two
    sources as :func:`_static_world`, kept per-axis so the hierarchy
    decision can see the (dp_outer, dp_inner) factorization)."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    try:
        return tuple(int(C.axis_size(a)) for a in names)
    except Exception:
        pass
    from horovod_tpu.runtime import state as _rt

    if _rt.is_initialized():
        mesh = _rt.global_state().mesh
        return tuple(int(mesh.shape[a]) for a in names)
    raise RuntimeError(
        "hierarchy resolution needs a bound mesh axis (inside "
        "shard_map) or an initialized runtime; call hvd.init() first")


def _placeholder(x) -> jax.Array:
    """What stands at an uncuttable leaf's place in the plain path's
    tree of slabs (the leaf itself rides the remainder group)."""
    return jnp.zeros((0,), x.dtype)


def _nbytes(leaves) -> int:
    return sum(x.size * x.dtype.itemsize for x in leaves)


def wants_no_buffer(quantized_bits: Optional[int], reduction: str,
                    bucket_bytes: Optional[int],
                    fused_collectives: str) -> bool:
    """Whether a sharded exchange asks for nothing that needs a packed
    buffer by its nature: no wire codec (one scale a buffer, and error
    feedback's residual of the buffer's length), the plain sum (AdaSum
    combines pairs of blocks), no explicit bucket size (a caller asking
    for buffers of that size), and the tail tiling not asked for by
    name (``"auto"`` has no tail to tile where there is no buffer).
    With a one-level topology on top (the two-level and tree exchanges
    scatter blocks of a buffer level by level) the exchange runs leaf
    by leaf (:func:`sharded_distributed_update`)."""
    return (quantized_bits is None and reduction == "sum"
            and bucket_bytes is None and fused_collectives != "on")


def sharded_distributed_update(optimizer: optax.GradientTransformation,
                               op: ReduceOp = Average,
                               axis: AxisSpec = GLOBAL_AXES,
                               prescale_factor: Optional[float] = None,
                               postscale_factor: Optional[float] = None,
                               quantized_bits: Optional[int] = None,
                               bucket_bytes: Optional[int] = None,
                               world: Optional[int] = None,
                               hierarchy: str = "auto",
                               fused_collectives: str = "auto",
                               error_feedback: bool = False,
                               level_codecs: Optional[
                                   Dict[str, Optional[int]]] = None,
                               reduction: Optional[str] = None
                               ) -> optax.GradientTransformation:
    """ZeRO-style sharded rewrite of ``chain(distributed_gradients,
    optimizer)``: reduce-scatter the gradients, run ``optimizer`` on
    this rank's 1/N shard only, allgather the resulting updates.

    **Two forms, chosen by what the call asks for** (nothing else
    chooses: no argument, no knob).  *Leaf by leaf* — the plain
    exchange: a one-level topology (``hierarchy`` resolves to
    ``"flat"``), no wire codec (``quantized_bits is None``, so no
    error feedback), ``reduction == "sum"``, ``bucket_bytes is None``
    and ``fused_collectives`` not ``"on"`` by name.  Every gradient
    leaf is reduce-scattered, updated and gathered *in its own shape*
    along its first dimension the world divides
    (:func:`~horovod_tpu.ops.collectives.scatter_dimension`): no
    ravel, no concatenate, no buffer of the model or of a bucket for
    gradients, parameters or updates; the parameter slab is a
    ``dynamic_slice`` of the leaf; ``optimizer`` runs on the caller's
    tree of slabs, so its state is that tree of slabs; the gather
    returns the *update* in the leaf's shape.  Leaves that cannot be
    cut (no dimension the world divides) ride one small packed
    remainder group.  *Packed* — every other request, each because it
    needs a buffer by its nature (:func:`wants_no_buffer`): the
    gradients are concatenated per (bucket, dtype) group and the
    rank owns a 1-D slice of each group's buffer, as described below.
    The two share nothing but :class:`ShardedOptimizerState` and the
    remainder group.  Which one a step compiled is on its
    ``train_step.lower`` / ``train_step.compile`` spans:
    ``exchange_leaf_ops``, ``exchange_packed_leaves``,
    ``exchange_packed_bytes``.

    ``hierarchy`` selects the exchange topology.  ``"flat"`` is the
    single-scope PR-1 exchange over the linearized ``axis`` tuple;
    ``"two_level"`` reduce-scatters within each ICI slice first and
    runs the cross-slice (DCN) phase on the 1/n_inner shards
    (:func:`horovod_tpu.ops.collectives.hierarchical_reducescatter`),
    requiring ``axis`` to name ``(dp_outer, dp_inner)`` mesh axes;
    ``"auto"`` (default) consults the axis factorization and picks
    two_level exactly when both extents exceed 1
    (:func:`horovod_tpu.runtime.topology.resolve_hierarchy`).  With
    ``quantized_bits``, the two-level form scopes the int8 wire codec
    to the DCN hop only — ICI hops stay full precision.

    ``"tree"`` generalizes to the N-level exchange: ``axis`` names the
    mesh axes outermost-first (cluster > pod > slice > chip), phase ℓ
    reduce-scatters the block surviving the inner phases over level
    ℓ's axis (:func:`horovod_tpu.ops.collectives.tree_reducescatter`),
    and ``level_codecs`` (``{axis_name: wire_bits|None}``, the parsed
    ``HOROVOD_EXCHANGE_LEVEL_CODECS`` grammar) places the codec per
    level; without it ``quantized_bits`` rides the outermost hop only,
    exactly the two-level convention.  A 2-axis tree IS two_level and
    a 1-axis tree IS flat — the degeneracies the parity pins hold.

    ``error_feedback=True`` (requires ``quantized_bits``) carries the
    codec's per-group rounding residual in the optimizer state and adds
    it back to the next step's pre-quantization buffer
    (:func:`horovod_tpu.ops.collectives.ef_quantized_reducescatter`),
    telescoping the wire's bias away.  In the flat topology EF wraps
    the single quantized reduce-scatter; in the two-level topology it
    additionally turns ON the ICI-hop codec (``quantize_inner``) — the
    compensated int8/fp8 ICI wire stays numerically pinned to the fp32
    path, which uncompensated quantization there would not.

    Numerically equivalent to allreduce-then-update for *elementwise*
    optimizers (SGD, momentum, Adam/AdamW, RMSProp, …): their update
    of element ``i`` depends only on the gradient/parameter history of
    element ``i``, so sharding the flat buffer commutes with the math
    (pinned by ``tests/test_optimizer.py``).  Transforms that couple
    elements globally (``clip_by_global_norm``, factored second
    moments) would see shard-local statistics — compose those *before*
    this wrapper or keep the replicated path.

    What it buys (the reduce-scatter decomposition of allreduce):

    * optimizer state is shard-sized — 1/N memory per rank;
    * optimizer math runs on 1/N elements — 1/N update FLOPs;
    * the wire carries the same ``2·(N-1)/N·B`` as a ring allreduce,
      but split into two phases — and, leaf by leaf, into one pair a
      leaf, each depending on that leaf's gradient alone.  What the
      chip read on the 871M LM over four v5e (PERF.md section 6, PR
      33): XLA:TPU compiles most per-leaf reduce-scatters to a fusion
      of an all-reduce and a slice and issues the all-gathers in steps
      unasked; with the options ``DistributedTrainStep`` lays under
      this step (:mod:`horovod_tpu.optim.exchange_overlap`) the
      exchange is in flight for 179 ms of a 308 ms step and alone on
      the device for 20 of them, where the packed monolith stood
      exposed for 94 ms of 602 (and its packing cost 120 more).
      Packed, ``bucket_bytes`` chunks the exchange in reverse-layer
      order so a bucket need not wait for the last gradient
      (arXiv:2305.06942's fused compute-collective argument): 370 ms
      at 64 MiB buckets on the same job.

    ``fused_collectives`` (``"auto"|"on"|"off"``,
    ``HOROVOD_FUSED_COLLECTIVES``) enables the tile-granular
    final-bucket exchange of the packed form: the LAST bucket — whose
    wire no remaining backward work can hide — splits into
    independent sub-collectives the scheduler overlaps with the
    shard-update math
    (:func:`horovod_tpu.ops.collectives._tiled_psum_scatter`,
    docs/fused_kernels.md).  Numerics are identical; ``"auto"``
    resolves on only on TPU
    (:func:`horovod_tpu.ops.pallas_kernels.resolve_fused_collectives`)
    and only where there is a buffer with a tail to tile: leaf by leaf
    it is off, and ``"on"`` by name keeps the packed form.

    ``reduction`` selects the exchange's combine operator
    (``"sum"`` | ``"adasum"``; None resolves config >
    ``HOROVOD_EXCHANGE_REDUCTION`` > ``"sum"``).  ``"adasum"`` swaps
    the OUTERMOST topology level's combine for AdaSum adaptive
    summation (arXiv 2006.02924) — plain RS within ICI where replicas
    barely diverge, the adaptive rule on the DCN hop where they
    diverge most — enabling 2-4x larger global batches at the
    small-batch loss trajectory (docs/adasum.md).  Orthogonal to
    hierarchy, codec, and EF; a flat (single-level) topology has no
    outer hop, so adasum there degenerates to the bit-identical plain
    sum.

    ``params`` passed to ``update`` are sliced to matching shards, so
    parameter-coupled rules (weight decay) see co-located values.
    State caveat (shared with the delta-Adasum form): each rank's
    state covers only its shard, so a host read captures rank 0's
    shard — checkpoint/restore of sharded state must go through the
    exchange-aware helpers (``Checkpointer.save_sharded`` /
    ``restore_sharded``, which re-shard slabs along the dimension
    they were cut on and 1-D group slices by their padded length:
    docs/warmstart.md), not raw rank-0 convention.
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("sharded_distributed_update supports "
                         "op=Sum/Average")
    if hierarchy not in TOPOLOGY_MODES:
        raise ValueError(
            f"hierarchy must be one of {TOPOLOGY_MODES}, got "
            f"{hierarchy!r}")
    if error_feedback and quantized_bits is None:
        raise ValueError(
            "error_feedback compensates the quantized wire's rounding; "
            "pass quantized_bits=8 (a wire-reduction compression) to "
            "enable it")
    reduction = C._resolve_reduction(reduction)
    axes_names = (axis,) if isinstance(axis, str) else tuple(axis)
    if hierarchy == "two_level" and len(axes_names) != 2:
        raise ValueError(
            "hierarchy='two_level' needs a 2-axis (dp_outer, dp_inner) "
            f"axis spec, got {axes_names}")
    from horovod_tpu.ops.pallas_kernels import resolve_fused_collectives

    fused_tail = resolve_fused_collectives(fused_collectives)
    unpacked = wants_no_buffer(quantized_bits, reduction, bucket_bytes,
                               fused_collectives)

    def _leafwise() -> bool:
        """Whether this call runs the plain exchange, leaf by leaf:
        nothing asked for needs a buffer and the topology is one level.
        Decided from what init and update both see — the arguments and,
        for ``"auto"`` over several axes alone, their extents (a mode
        asked for by name has its number of levels whatever they are,
        so an init outside any mesh still knows its layout)."""
        if not unpacked:
            return False
        if hierarchy == "flat" or len(axes_names) == 1:
            return True
        if hierarchy != "auto":
            return False
        return resolve_topology(hierarchy, _static_axis_sizes(axis),
                                axis_names=axes_names).mode == "flat"

    def _leaf_plan(leaves):
        """Per leaf the dimension it is cut along (None: it cannot be),
        the indices of those that cannot, and the packed plan of that
        remainder (None where every leaf can)."""
        n = world if world is not None else _static_world(axis)
        dims = [C.scatter_dimension(x.shape, n) for x in leaves]
        rest = [i for i, d in enumerate(dims) if d is None]
        spec = C.make_fusion_spec([leaves[i] for i in rest], n) \
            if rest else None
        return n, dims, rest, spec

    def _slab_tree(treedef, slabs, rest_shards):
        """What the wrapped optimizer sees on the plain path: the
        caller's tree with every leaf its slab (an uncuttable leaf an
        empty placeholder), beside the remainder's shards if any."""
        tree = jax.tree_util.tree_unflatten(treedef, slabs)
        return (tree, rest_shards) if rest_shards is not None else tree

    def leaf_init(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        n, dims, _, spec = _leaf_plan(leaves)
        slabs = [
            _placeholder(x) if d is None else
            jnp.zeros(x.shape[:d] + (x.shape[d] // n,) + x.shape[d + 1:],
                      x.dtype)
            for x, d in zip(leaves, dims)]
        rest_shards = None if spec is None else {
            g.key: jnp.zeros((g.shard,), jnp.dtype(g.dtype))
            for g in spec.groups}
        return ShardedOptimizerState(inner=optimizer.init(
            _slab_tree(treedef, slabs, rest_shards)))

    def leaf_update(updates, state, params):
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        _, dims, rest, spec = _leaf_plan(leaves)
        # ``exchange/scatter``: the reduce-scatter and what prepares it
        # (pad, pack, prescale, the parameters' slabs); the shard-local
        # update between the two stays under the caller's ``update``;
        # ``exchange/gather``: the all-gather and what undoes it
        with jax.named_scope("exchange/scatter"):
            g_slabs = [
                _placeholder(g) if d is None else C.leaf_reducescatter(
                    g, d, op=op, axis=axis,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor)
                for g, d in zip(leaves, dims)]
            g_rest = None
            if rest:
                g_rest, _ = C.grouped_reducescatter(
                    [leaves[i] for i in rest], op=op, axis=axis,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor, spec=spec)
            p_tree = None
            if params is not None:
                p_leaves = jax.tree_util.tree_leaves(params)
                p_slabs = [_placeholder(p) if d is None
                           else C.leaf_slab(p, d, axis)
                           for p, d in zip(p_leaves, dims)]
                p_rest = C.local_fusion_shards(
                    [p_leaves[i] for i in rest], spec, axis=axis) \
                    if rest else None
                p_tree = _slab_tree(treedef, p_slabs, p_rest)
        upd, inner = optimizer.update(
            _slab_tree(treedef, g_slabs, g_rest), state.inner, p_tree)
        u_tree, u_rest = upd if rest else (upd, None)
        with jax.named_scope("exchange/gather"):
            out = [None if d is None else C.leaf_allgather(u, d, axis)
                   for u, d in zip(jax.tree_util.tree_leaves(u_tree), dims)]
            if rest:
                for i, u in zip(rest, C.grouped_allgather(u_rest, spec,
                                                          axis=axis)):
                    out[i] = u
        telemetry.annotate(
            exchange_leaf_ops=len(leaves) - len(rest),
            exchange_packed_leaves=len(rest),
            exchange_packed_bytes=_nbytes(leaves[i] for i in rest))
        return jax.tree_util.tree_unflatten(treedef, out), \
            ShardedOptimizerState(inner=inner)

    def _spec(leaves):
        # ``world`` pins the shard sizing when init runs outside any
        # mesh context against a non-runtime mesh (DistributedTrainStep
        # passes its own mesh's size); otherwise derive it
        return C.make_fusion_spec(
            leaves, world if world is not None else _static_world(axis),
            bucket_bytes)

    def init_fn(params):
        if _leafwise():
            return leaf_init(params)
        leaves = jax.tree_util.tree_leaves(params)
        spec = _spec(leaves)
        template = {g.key: jnp.zeros((g.shard,), jnp.dtype(g.dtype))
                    for g in spec.groups}
        residuals = None
        if error_feedback:
            # full padded length per group: each rank compensates its
            # own pre-reduction contribution (only floating groups ride
            # the quantized wire)
            residuals = {
                g.key: jnp.zeros((g.padded,), jnp.float32)
                for g in spec.groups
                if jnp.issubdtype(jnp.dtype(g.dtype), jnp.floating)}
        return ShardedOptimizerState(inner=optimizer.init(template),
                                     residuals=residuals)

    def update_fn(updates, state, params=None):
        if _leafwise():
            return leaf_update(updates, state, params)
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        # resolved at trace time: inside shard_map the axis extents are
        # static, so the branch compiles away and the program contains
        # exactly one exchange topology
        topo = resolve_topology(hierarchy, _static_axis_sizes(axis),
                                axis_names=axes_names,
                                wire_bits=quantized_bits,
                                level_codecs=level_codecs)
        mode = topo.mode
        residuals = state.residuals if error_feedback else None
        # the scopes as leaf_update lays them
        with jax.named_scope("exchange/scatter"):
            if mode == "tree":
                levels = [C.ExchangeLevel(lv.axis_spec, lv.wire_bits)
                          for lv in topo.effective().levels]
                if residuals is not None \
                        and levels[0].quantized_bits is None:
                    # EF turns on the innermost codec — the tree twin of
                    # quantize_inner (the residual pins that hop)
                    levels[0] = C.ExchangeLevel(levels[0].axis,
                                                quantized_bits)
                if residuals is not None:
                    shards, spec, residuals = C.tree_reducescatter(
                        leaves, levels, op=op,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        bucket_bytes=bucket_bytes,
                        fused_tail=fused_tail,
                        residuals=residuals,
                        reduction=reduction)
                else:
                    shards, spec = C.tree_reducescatter(
                        leaves, levels, op=op,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        bucket_bytes=bucket_bytes,
                        fused_tail=fused_tail,
                        reduction=reduction)
                # shard ownership is row-major over the levels
                # innermost-FIRST — the N-level generalization of
                # exchange_index_axes
                own_axes = C.tree_index_axes(levels)
            elif mode == "two_level":
                outer, inner_ax = axes_names
                if residuals is not None:
                    # EF turns on the ICI codec too — the residual pins it
                    shards, spec, residuals = C.hierarchical_reducescatter(
                        leaves, op=op, outer_axis=outer, inner_axis=inner_ax,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        quantized_bits=quantized_bits,
                        bucket_bytes=bucket_bytes,
                        fused_tail=fused_tail,
                        quantize_inner=True, inner_residuals=residuals,
                        reduction=reduction)
                else:
                    shards, spec = C.hierarchical_reducescatter(
                        leaves, op=op, outer_axis=outer, inner_axis=inner_ax,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        quantized_bits=quantized_bits,
                        bucket_bytes=bucket_bytes,
                        fused_tail=fused_tail,
                        reduction=reduction)
                # shard ownership is row-major over (inner, outer) — the
                # param slices and the reassembly must use that linearization
                own_axes = C.exchange_index_axes(outer, inner_ax)
            else:
                if residuals is not None:
                    shards, spec, residuals = C.grouped_reducescatter(
                        leaves, op=op, axis=axis,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        quantized_bits=quantized_bits,
                        bucket_bytes=bucket_bytes,
                        fused_tail=fused_tail,
                        residuals=residuals)
                else:
                    shards, spec = C.grouped_reducescatter(
                        leaves, op=op, axis=axis,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        quantized_bits=quantized_bits,
                        bucket_bytes=bucket_bytes,
                        fused_tail=fused_tail)
                own_axes = axis
            p_shards = None
            if params is not None:
                p_leaves = jax.tree_util.tree_leaves(params)
                p_shards = C.local_fusion_shards(p_leaves, spec,
                                                 axis=own_axes)
        upd_shards, inner = optimizer.update(shards, state.inner,
                                             p_shards)
        with jax.named_scope("exchange/gather"):
            out = C.grouped_allgather(upd_shards, spec, axis=own_axes)
        telemetry.annotate(
            exchange_leaf_ops=0, exchange_packed_leaves=len(leaves),
            exchange_packed_bytes=_nbytes(leaves))
        return jax.tree_util.tree_unflatten(treedef, out), \
            ShardedOptimizerState(inner=inner,
                                  residuals=residuals
                                  if error_feedback else None)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         named_parameters=None,
                         op: ReduceOp = Average,
                         axis: AxisSpec = GLOBAL_AXES,
                         mode: str = "shard_map",
                         compression=None,
                         backward_passes_per_step: int = 1,
                         prescale_factor: Optional[float] = None,
                         postscale_factor: Optional[float] = None,
                         sparse_params: Optional[dict] = None,
                         gradient_predivide_factor: float = 1.0,
                         shard_optimizer_states: bool = False,
                         exchange_bucket_bytes: Optional[int] = None,
                         hierarchy: str = "auto",
                         fused_collectives: str = "auto",
                         error_feedback: bool = False,
                         level_codecs: Optional[
                             Dict[str, Optional[int]]] = None,
                         reduction: Optional[str] = None
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer so each update uses cross-replica-reduced
    gradients (reference ``DistributedOptimizer`` factory,
    ``torch/optimizer.py:381``, ``tensorflow/__init__.py:356``).

    ``named_parameters`` is accepted for reference-signature parity (JAX
    pytrees carry structure; names are not needed).
    ``backward_passes_per_step`` accumulates N micro-batch gradients
    locally before one reduction+step — note the reduction lives *inside*
    MultiSteps, so skipped micro-steps do no communication, matching the
    reference's delayed-allreduce semantics (``torch/optimizer.py``
    backward_passes_per_step counting).

    ``shard_optimizer_states=True`` replaces allreduce-then-update with
    the ZeRO-style reduce-scatter → shard-local update → allgather
    exchange (:func:`sharded_distributed_update`): same parameters
    within dtype tolerance, 1/N optimizer memory and update FLOPs per
    rank.  Asked for plainly on a one-level topology it runs leaf by
    leaf, each leaf in its own shape and no packed buffer; a codec,
    error feedback, a two-level or tree topology, AdaSum, an explicit
    ``exchange_bucket_bytes`` or ``fused_collectives="on"`` keep the
    packed form (see there).
    ``exchange_bucket_bytes`` chunks the packed exchange into
    reverse-layer-order buckets for earlier overlap, and ``hierarchy``
    selects its topology — ``"auto"`` (default) runs the two-level
    ICI-then-DCN exchange whenever the dp axes factor into
    ``(dp_outer, dp_inner)`` extents both > 1, ``"flat"``/``"two_level"``
    force a mode (see :func:`sharded_distributed_update`).  Requires
    ``mode='shard_map'`` and an elementwise ``optimizer`` (see the
    sharded transform's docstring).  ``error_feedback=True`` (requires
    a wire-reduction ``compression``) carries the codec's rounding
    residual in the sharded state so the low-precision wire stays
    numerically pinned to the fp32 path (see
    :func:`sharded_distributed_update`).  ``reduction="adasum"`` puts
    the AdaSum combine on the exchange's outermost topology level —
    the large-batch scale-out operator (docs/adasum.md); requires
    ``shard_optimizer_states=True``.
    """
    del named_parameters
    if exchange_bucket_bytes is not None and not shard_optimizer_states:
        raise ValueError(
            "exchange_bucket_bytes buckets the sharded exchange; pass "
            "shard_optimizer_states=True to enable it")
    if hierarchy != "auto" and not shard_optimizer_states:
        raise ValueError(
            "hierarchy selects the sharded exchange topology; pass "
            "shard_optimizer_states=True to enable it")
    if level_codecs is not None and not shard_optimizer_states:
        raise ValueError(
            "level_codecs places wire codecs on the sharded exchange's "
            "tree levels; pass shard_optimizer_states=True to enable it")
    if fused_collectives != "auto" and not shard_optimizer_states:
        raise ValueError(
            "fused_collectives schedules the sharded exchange's final "
            "bucket; pass shard_optimizer_states=True to enable it")
    if reduction not in (None, "sum") and not shard_optimizer_states:
        raise ValueError(
            "reduction selects the sharded exchange's combine operator; "
            "pass shard_optimizer_states=True to enable it (the "
            "replicated path's adasum is DistributedAdasumOptimizer)")
    if shard_optimizer_states:
        if mode != "shard_map":
            raise ValueError(
                "shard_optimizer_states requires mode='shard_map' (the "
                "exchange is explicit per-device code; pjit autodiff "
                "already reduced the gradients densely)")
        if sparse_params:
            raise ValueError(
                "shard_optimizer_states is incompatible with "
                "sparse_params: sparse leaves bypass the fused flat "
                "buffer the shard slicing is defined over")
        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            raise ValueError(
                "shard_optimizer_states supports op=Sum/Average")
        qbits = getattr(compression, "wire_reduce_bits", None)
        if compression is not None and qbits is None:
            raise ValueError(
                "shard_optimizer_states supports only wire-reduction "
                "compression (Compression.int8); compressor-style "
                "codecs would decompress before the shard slicing")
    if error_feedback and not shard_optimizer_states:
        raise ValueError(
            "error_feedback carries the sharded exchange's quantization "
            "residual; pass shard_optimizer_states=True to enable it")
    if gradient_predivide_factor != 1.0:
        # reference semantics (torch/optimizer.py:119-123): split the
        # averaging across the sum — grads scale by 1/f before and f/size
        # after (our Average already applies the 1/size)
        if op != Average:
            raise ValueError(
                "gradient_predivide_factor requires op=Average")
        if prescale_factor is not None or postscale_factor is not None:
            raise ValueError(
                "pass either gradient_predivide_factor or explicit "
                "prescale/postscale factors, not both")
        prescale_factor = 1.0 / gradient_predivide_factor
        postscale_factor = gradient_predivide_factor
    if shard_optimizer_states:
        chained = sharded_distributed_update(
            optimizer, op=op, axis=axis,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            quantized_bits=qbits,
            bucket_bytes=exchange_bucket_bytes,
            hierarchy=hierarchy,
            fused_collectives=fused_collectives,
            error_feedback=error_feedback,
            level_codecs=level_codecs,
            reduction=reduction)
        if backward_passes_per_step > 1:
            return optax.MultiSteps(
                chained, every_k_schedule=backward_passes_per_step)
        return chained
    chained = optax.chain(
        distributed_gradients(op=op, axis=axis, mode=mode,
                              compression=compression,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor,
                              sparse_params=sparse_params),
        optimizer,
    )
    if backward_passes_per_step > 1:
        return optax.MultiSteps(chained,
                                every_k_schedule=backward_passes_per_step)
    return chained


def adasum_updates(axis: AxisSpec = GLOBAL_AXES,
                   mode: str = "shard_map",
                   compression=None) -> optax.GradientTransformation:
    """optax transform that Adasum-reduces *updates* (weight deltas).

    The composable core of :func:`DistributedAdasumOptimizer`: placed
    *after* the local optimizer in an optax chain, it sees exactly the
    per-rank weight delta (optax updates are ``new - old``), which is the
    quantity the Adasum paper reduces.  Per-leaf coefficients match the
    reference's per-layer dot/norm treatment.  A thin, eagerly-validated
    facade over :func:`distributed_gradients` with ``op=Adasum`` — optax
    transforms don't care whether the pytree holds gradients or deltas.
    """

    if mode not in ("shard_map", "process"):
        # pjit's autodiff-inserted mean cannot express the adaptive rule,
        # so there is no identity-transform shortcut the way
        # distributed_gradients has
        raise ValueError(
            f"adasum_updates supports mode='shard_map' or 'process', got "
            f"{mode!r} (Adasum cannot be pjit's implicit mean reduction)")
    return distributed_gradients(op=ReduceOp.ADASUM, axis=axis, mode=mode,
                                 compression=compression)


def DistributedAdasumOptimizer(optimizer: optax.GradientTransformation,
                               named_parameters=None,
                               axis: AxisSpec = GLOBAL_AXES,
                               mode: str = "shard_map",
                               compression=None,
                               backward_passes_per_step: int = 1
                               ) -> optax.GradientTransformation:
    """Adasum in its *delta-optimizer* form (reference
    ``_DistributedAdasumOptimizer``, ``torch/optimizer.py:210-380``;
    TF variant ``tensorflow/__init__.py:334-506``).

    ``op=Adasum`` on raw gradients is only correct for plain SGD: for any
    stateful optimizer (momentum, Adam) the reference instead applies the
    *local* optimizer step first and Adasum-reduces the resulting weight
    delta::

        start  = params                      # stash
        local  = step(optimizer, grads)      # per-rank state update
        delta  = local - start
        params = start + adasum(delta)       # reduce the delta, not grads

    In optax the update returned by ``optimizer.update`` *is* that delta,
    so the whole dance is ``chain(optimizer, adasum_updates(...))`` — the
    reduction moves to the other side of the optimizer compared with
    :func:`DistributedOptimizer`.  Optimizer state (momenta, EMAs) evolves
    from local gradients on every rank, exactly as the reference's
    per-parameter local ``step()`` does.

    Hierarchical dispatch over the (dcn, ici) mesh averages deltas within
    ici and Adasums across dcn (``adasum_gpu_operations.cc:38``).

    Note the state semantics this implies: because momenta evolve from
    *local* gradients, optimizer state is per-rank, not replicated.
    Host reads and checkpoints capture rank 0's (device 0's) state — the
    reference's rank-0-checkpoint convention — and restore follows the
    broadcast-restore pattern (every rank resumes from rank 0's state).
    """
    del named_parameters  # JAX pytrees carry structure; parity-only arg
    chained = optax.chain(
        optimizer,
        adasum_updates(axis=axis, mode=mode, compression=compression),
    )
    if backward_passes_per_step > 1:
        return optax.MultiSteps(chained,
                                every_k_schedule=backward_passes_per_step)
    return chained


class DistributedGradientTape:
    """Eager-style gradient wrapper (reference ``DistributedGradientTape``,
    ``tensorflow/__init__.py:508-572``).

    Wraps a JAX gradient function; calling ``.gradient`` computes local
    gradients then reduces them across worker processes with overlapped
    async allreduces::

        tape = hvd.DistributedGradientTape(jax.grad(loss_fn))
        grads = tape.gradient(params, batch)
    """

    def __init__(self, grad_fn, op: ReduceOp = Average, compression=None,
                 prescale_factor: Optional[float] = None,
                 postscale_factor: Optional[float] = None):
        self._grad_fn = grad_fn
        self._op = op
        self._compression = compression
        self._prescale = prescale_factor
        self._postscale = postscale_factor

    def __call__(self, *args, **kwargs):
        return self.gradient(*args, **kwargs)

    def gradient(self, *args, **kwargs):
        from horovod_tpu.ops import eager

        grads = self._grad_fn(*args, **kwargs)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        handles = [
            eager.allreduce_async(g, op=self._op,
                                  compression=self._compression,
                                  prescale_factor=self._prescale,
                                  postscale_factor=self._postscale)
            for g in leaves]
        reduced = [eager.synchronize(h) for h in handles]
        return jax.tree_util.tree_unflatten(treedef, reduced)
