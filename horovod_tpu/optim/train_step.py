"""High-level jitted SPMD training step — the framework's hot path.

The reference never owns the training loop (except Spark estimators); its
value is making the user's loop distributed with ~5 changed lines
(``README.rst`` usage recipe).  The TPU equivalent of those 5 lines is one
object: ``DistributedTrainStep`` compiles the user's ``loss_fn`` +
optimizer into a single pjit program over the runtime mesh with the batch
sharded along (dcn, ici) and parameters replicated.  Inside one XLA
program the gradient psum is inserted by autodiff and overlapped with the
backward pass by the compiler — the role of the reference's background
thread + fusion buffer + NCCL streams, with zero host round-trips.

Design notes for the MXU/HBM (see repo guidance):

* a single compiled step keeps matmuls batched and fusible; nothing
  escapes to host between microbatches;
* ``donate_argnums`` on (params, opt_state) makes updates in-place in HBM;
* optional ``jax.checkpoint`` on the loss for rematerialization;
* bf16 compute with fp32 params is the user's choice inside ``loss_fn`` —
  compression hooks apply to the gradient wire format in shard_map mode.
"""

from __future__ import annotations

import contextlib
import os
from functools import partial
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import telemetry
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.collectives import Average, ReduceOp, Sum
from horovod_tpu.runtime import compile_cache, state
from horovod_tpu.runtime.topology import GLOBAL_AXES

AxisSpec = Union[str, Sequence[str]]


def _sumsq(tree):
    """fp32 sum of squares over every leaf (the global-norm reduction
    the guard computes in-graph)."""
    s = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        s = s + jnp.sum(jnp.square(jnp.asarray(leaf, jnp.float32)))
    return s


def _guard_select(ok, new_params, new_opt, params, opt_state):
    """Keep the update only when the guard predicate holds; otherwise
    keep the pre-step state — in-graph, so donation can't lose the
    clean copy."""
    sel = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
    return (jax.tree_util.tree_map(sel, new_params, params),
            jax.tree_util.tree_map(sel, new_opt, opt_state))


class DistributedTrainStep:
    """Compiled data-parallel training step.

    ::

        step = DistributedTrainStep(loss_fn, optax.sgd(0.01 * hvd.size()))
        params, opt_state = step.init(params)
        params, opt_state, loss = step(params, opt_state, batch)

    ``loss_fn(params, batch) -> scalar`` must compute the *mean* loss over
    its batch shard; global averaging across shards then follows from the
    sharded-batch mean (XLA inserts the collective during autodiff).

    ``mode="shard_map"`` lowers through explicit per-device code with the
    gradient reduction done by
    :func:`horovod_tpu.ops.collectives.grouped_allreduce` — useful when the
    user wants Adasum (``op=Adasum``), compression, or explicit control.
    ``op=None`` skips the gradient reduction entirely for optimizers that
    own their distribution, e.g. the delta-form
    :func:`~horovod_tpu.optim.DistributedAdasumOptimizer`.
    """

    def __init__(self,
                 loss_fn: Callable,
                 optimizer: optax.GradientTransformation,
                 mesh=None,
                 mode: str = "pjit",
                 op: Optional[ReduceOp] = Average,
                 compression=None,
                 remat: Union[bool, str] = False,
                 data_axes: AxisSpec = GLOBAL_AXES,
                 donate: bool = True,
                 donate_batch: bool = False,
                 steps_per_call: int = 1,
                 compiler_options: Optional[dict] = None,
                 sparse_params: Optional[dict] = None,
                 fsdp_axis: Optional[str] = None,
                 fsdp_min_weight_size: Optional[int] = None,
                 shard_optimizer_states: bool = False,
                 exchange_bucket_bytes: Optional[int] = None,
                 hierarchy: str = "auto",
                 fused_collectives: str = "auto",
                 error_feedback: bool = False,
                 plan=None,
                 guard=None,
                 reduction: Optional[str] = None):
        """``steps_per_call > 1`` scans that many optimizer steps inside
        the one compiled program (the Keras ``steps_per_execution``
        knob): one dispatch amortizes per-call host/launch overhead,
        and the batch is reused for every scanned step, so pass fresh
        data per call.
        ``compiler_options`` are XLA backend flags forwarded to the
        compile.  The plain replicated data-parallel step on TPUs lays
        an option set of its own under them, key by key, so that its
        gradient all-reduce runs beside the same step's matmuls and
        update (:mod:`horovod_tpu.optim.exchange_overlap`,
        docs/overlap.md "The replicated step"); the caller's keys win.

        ``fsdp_axis`` turns on fully-sharded data parallelism (pjit mode
        only): parameters — and, by jit propagation, optimizer state —
        are *placed* sharded along that mesh axis instead of replicated,
        and GSPMD inserts the all-gather-on-use / reduce-scatter-on-grad
        collectives ZeRO-3 schedules by hand (see
        :mod:`horovod_tpu.parallel.fsdp`).  Typically ``"ici"`` on the
        runtime mesh so gathers ride the fast interconnect while the
        batch stays sharded over (dcn, ici).

        ``shard_optimizer_states=True`` (shard_map mode) swaps the
        monolithic post-backward allreduce for the ZeRO-style
        reduce-scatter → shard-local optimizer update → allgather
        exchange (:func:`horovod_tpu.optim.sharded_distributed_update`):
        numerically equivalent parameters, 1/N optimizer memory and
        update FLOPs per rank.  On a one-level topology with no codec,
        the plain sum, no explicit bucket size and
        ``fused_collectives`` not ``"on"`` it runs leaf by leaf: every
        gradient reduce-scattered, updated and gathered in its own
        shape, no buffer of the model anywhere, the state a tree of
        slabs; on TPUs the step then lays the four options that issue
        those collectives in steps
        (:mod:`horovod_tpu.optim.exchange_overlap`; on the 871M LM
        over four v5e chips 308 ms a step, 20 of them exchange alone,
        11.0 GB a chip: PERF.md sections 5-6, PR 33).  Any of the
        others keeps the packed exchange, where
        ``exchange_bucket_bytes`` splits it
        into reverse-layer-order buckets for earlier overlap (measured
        by ``utils/overlap_probe.py``).

        ``donate_batch=True`` adds the batch to the donated argument
        set — the input slot of a *pipeline-fed* step
        (:mod:`horovod_tpu.data`): every call receives a fresh batch
        whose device buffers nothing else references, so the caller may
        hand over ownership and XLA is free to alias the buffers into
        outputs instead of holding live input and results side by side
        (when no output matches, it logs the unused donation and runs
        normally).  Leave it off when a batch is reused across calls
        (the synthetic-bench pattern) — donation invalidates the
        caller's arrays after the call.

        ``fused_collectives`` (``"auto"|"on"|"off"``,
        ``HOROVOD_FUSED_COLLECTIVES``) schedules the packed sharded
        exchange's FINAL bucket tile-granularly — the one exchange no
        remaining backward work can hide — as independent
        sub-collectives the scheduler overlaps with the shard-update
        math (docs/fused_kernels.md).  ``"auto"`` enables on TPU only,
        and only where the exchange has a buffer: leaf by leaf there
        is no tail to tile and :attr:`fused_collectives` reads
        ``"off"``; ``"on"`` by name keeps the packed exchange.
        Numerics are identical either way.

        ``guard`` attaches the numerics guardian
        (:class:`horovod_tpu.guard.TrainingGuard` or anything exposing
        ``current_limit()``/``observe()``): the compiled step takes one
        extra traced scalar — the spike limit — computes the global
        gradient norm, and where-selects the *pre-step* ``(params,
        opt_state)`` whenever the norm is non-finite or above the
        limit, so a poisoned update is never applied even with donated
        buffers.  The limit is a runtime value, so per-step threshold
        changes never recompile.  Requires ``steps_per_call=1`` (each
        optimizer step must be individually observable).  In shard_map
        pre-reduction paths (``shard_optimizer_states`` or ``op=None``)
        the guarded norm is the root-sum-square over all device-local
        gradients — device-consistent via one scalar allreduce — rather
        than the norm of the reduced gradient; the guardian's EMA
        baseline adapts to whichever statistic the mode produces.

        ``hierarchy`` picks the sharded exchange's topology:
        ``"auto"`` (default) resolves against the data-axes
        factorization — the two-level ICI-then-DCN exchange whenever
        both ``(dp_outer, dp_inner)`` extents exceed 1, flat otherwise
        (:func:`horovod_tpu.runtime.topology.resolve_hierarchy`);
        ``"flat"``/``"two_level"`` force a mode.  When unset here, the
        runtime config's ``HOROVOD_EXCHANGE_HIERARCHY`` /
        ``HOROVOD_EXCHANGE_BUCKET_BYTES`` env knobs supply the
        defaults (docs/overlap.md).

        ``error_feedback=True`` (sharded exchange + wire-reduction
        compression only) carries the per-bucket quantization residual
        across steps and additionally quantizes the intra-slice (ICI)
        reduce-scatter hop: each rank re-adds last step's local
        rounding error before quantizing, so the int8/fp8 wire stays
        numerically pinned to the fp32 path over a trajectory instead
        of accumulating rounding bias (docs/parallelism.md).

        ``plan`` (a :class:`~horovod_tpu.parallel.plan.ShardingPlan`
        or its ``HOROVOD_PLAN`` grammar string; falls back to the env
        knob) is the declarative parallelism source of truth: it
        builds the mesh (DCN-outer/ICI-inner ``AXIS_ORDER``) when no
        ``mesh`` is given, scopes the batch sharding and the gradient
        exchange to its data axes (dp/fsdp — plus ``sp`` under
        ``shard_map``, where the batch's token dim shards over the sp
        axis and the token-mean loss makes sp data-axis math for the
        reduction; tp/ep stay out of the exchange scope),
        and turns ``fsdp>1`` into ``fsdp_axis`` placement under pjit.
        Pipeline plans (``pp>1``) are rejected here — pipelines run
        through :mod:`horovod_tpu.parallel.pipeline`."""
        from horovod_tpu.parallel.plan import ShardingPlan, as_plan

        plan = as_plan(plan)
        if plan is None and state.is_initialized():
            cfg_plan = getattr(state.global_state().config, "plan", None)
            if cfg_plan:
                plan = ShardingPlan.from_string(cfg_plan)
        if plan is not None:
            if mesh is None:
                plan = plan.resolve(len(jax.devices()))
                mesh = plan.build_mesh()
            else:
                plan = plan.resolve(mesh.size)
                if not plan.matches_mesh(mesh):
                    raise ValueError(
                        f"plan {plan.to_string()} does not match the "
                        f"given mesh {dict(mesh.shape)}: pass one "
                        f"source of truth (the plan builds its own "
                        f"mesh when mesh=None)")
            if plan.pp > 1:
                raise ValueError(
                    f"plan {plan.to_string()} has pp>1: pipeline "
                    "parallelism runs through parallel.pipeline "
                    "(gpipe / interleaved_1f1b inside shard_map), not "
                    "the train step — the step compiles "
                    "dp/fsdp/tp/ep/sp plans")
            blocked_model_axes = tuple(
                a for a in plan.model_axes if a != "sp")
            if mode == "shard_map" and blocked_model_axes:
                raise ValueError(
                    f"plan {plan.to_string()} has model axes "
                    f"{blocked_model_axes}: mode='shard_map' compiles "
                    "data plans (dp/fsdp) plus sequence parallelism "
                    "(sp — the batch's token dim shards over the sp "
                    "axis and the model's ring/ulysses attention owns "
                    "the exchange) — tp/ep plans need mode='pjit', "
                    "where GSPMD places the shardings the model's "
                    "modules declare")
            norm_axes = (data_axes,) if isinstance(data_axes, str) \
                else tuple(data_axes)
            if norm_axes == tuple(GLOBAL_AXES):
                data_axes = plan.data_axes
            elif norm_axes != plan.data_axes:
                raise ValueError(
                    f"data_axes {norm_axes} conflicts with plan "
                    f"{plan.to_string()} (data axes "
                    f"{plan.data_axes}): the plan owns the exchange "
                    "scope — drop the explicit data_axes")
            if mode == "pjit" and plan.fsdp > 1 and fsdp_axis is None:
                fsdp_axis = "fsdp"
        self._plan = plan
        self._mesh = mesh or state.global_state().mesh
        self._mode = mode
        self._optimizer = optimizer
        self._op = op
        if shard_optimizer_states:
            if mode != "shard_map":
                raise ValueError(
                    "shard_optimizer_states requires mode='shard_map' "
                    "(the explicit exchange; under pjit use fsdp_axis, "
                    "where GSPMD inserts the sharded collectives)")
            if op is None or op not in (C.ReduceOp.SUM,
                                        C.ReduceOp.AVERAGE):
                raise ValueError(
                    "shard_optimizer_states performs the gradient "
                    "reduction itself and supports op=Sum/Average")
            if sparse_params:
                raise ValueError(
                    "shard_optimizer_states is incompatible with "
                    "sparse_params (sparse leaves bypass the fused "
                    "flat buffer the shard slicing is defined over)")
        elif exchange_bucket_bytes is not None:
            raise ValueError(
                "exchange_bucket_bytes buckets the sharded exchange; "
                "pass shard_optimizer_states=True to enable it")
        elif hierarchy != "auto":
            raise ValueError(
                "hierarchy selects the sharded exchange topology; pass "
                "shard_optimizer_states=True to enable it")
        elif fused_collectives != "auto":
            raise ValueError(
                "fused_collectives schedules the sharded exchange's "
                "final bucket; pass shard_optimizer_states=True to "
                "enable it")
        elif reduction not in (None, "sum"):
            raise ValueError(
                "reduction selects the sharded exchange's combine "
                "operator; pass shard_optimizer_states=True to enable "
                "it (the replicated path's adasum is op=Adasum / "
                "DistributedAdasumOptimizer)")
        if error_feedback:
            if not shard_optimizer_states:
                raise ValueError(
                    "error_feedback carries the sharded exchange's "
                    "quantization residual; pass "
                    "shard_optimizer_states=True to enable it")
            if compression is None:
                raise ValueError(
                    "error_feedback compensates quantization rounding; "
                    "it needs a wire-reduction compression "
                    "(Compression.int8)")
        self._error_feedback = bool(error_feedback)
        level_codecs = None
        if shard_optimizer_states and state.is_initialized():
            # env-contract defaults (HOROVOD_EXCHANGE_*): explicit
            # arguments rule; unset knobs fall back to runtime config
            cfg = state.global_state().config
            if exchange_bucket_bytes is None:
                exchange_bucket_bytes = cfg.exchange_bucket_bytes
            if hierarchy == "auto" and cfg.exchange_hierarchy:
                hierarchy = cfg.exchange_hierarchy
            if fused_collectives == "auto" and \
                    getattr(cfg, "fused_collectives", "auto") != "auto":
                fused_collectives = cfg.fused_collectives
            if getattr(cfg, "exchange_level_codecs", None):
                from horovod_tpu.runtime.topology import parse_level_codecs

                level_codecs = parse_level_codecs(
                    cfg.exchange_level_codecs)
        self._level_codecs = level_codecs
        # reduction operator of the sharded exchange: explicit arg >
        # runtime config > HOROVOD_EXCHANGE_REDUCTION env > plain sum.
        # The env var is read directly (not only via the init-time
        # config snapshot) so a knob set after hvd.init() still reaches
        # the step.  None when no sharded exchange is active: the knob
        # has nothing to steer there.
        if shard_optimizer_states:
            if reduction is None and state.is_initialized():
                cfg_red = getattr(state.global_state().config,
                                  "exchange_reduction", "sum")
                if cfg_red and cfg_red != "sum":
                    reduction = cfg_red
            if reduction is None:
                env_red = os.environ.get("HOROVOD_EXCHANGE_REDUCTION")
                if env_red:
                    reduction = env_red.lower()
            self._reduction = C._resolve_reduction(reduction)
        else:
            self._reduction = None
        self._hierarchy = hierarchy
        # the mode the compiled exchange will actually run ("auto" made
        # static against the platform) — the value bench.py emits as
        # fused_collectives; the sharded exchange settles it below,
        # where it knows whether there is a buffer with a tail to tile
        self._fused_collectives = "off"
        self._shard_opt = shard_optimizer_states
        if fsdp_axis is not None and mode != "pjit":
            raise ValueError(
                "fsdp_axis requires mode='pjit' (GSPMD inserts the "
                "gather/reduce-scatter collectives; shard_map mode "
                "manages per-device values by hand)")
        if fsdp_axis is not None and \
                fsdp_axis not in self._mesh.shape:
            raise ValueError(
                f"fsdp_axis {fsdp_axis!r} is not an axis of the mesh "
                f"{tuple(self._mesh.shape)}")
        if fsdp_min_weight_size is not None and fsdp_axis is None:
            raise ValueError(
                "fsdp_min_weight_size has no effect without fsdp_axis")
        self._fsdp_axis = fsdp_axis
        self._fsdp_min = fsdp_min_weight_size
        self._data_axes = tuple(data_axes) if not isinstance(data_axes, str) \
            else (data_axes,)
        # sp>1 under shard_map: the batch's token dim (dim 1) shards
        # over the sp axis — the model's ring/ulysses attention owns
        # the sequence exchange, and because the loss is a token mean,
        # sp joins the gradient/loss reduction scope exactly like a
        # data axis (average of per-shard token means = global mean)
        self._sp = int(plan.sp) if plan is not None else 1
        self._sp_axis = "sp" if (mode == "shard_map" and
                                 self._sp > 1) else None
        # remat accepts the legacy bool or a policy string (none|dots|
        # full|offload); the resolved policy includes the
        # HOROVOD_REMAT_POLICY env knob, which steers the *models'*
        # per-block remat (memory/remat.py, docs/memory.md).  The
        # loss-fn wrap itself only happens when
        # the caller asked for it: an env-driven model already remats
        # per block, and checkpointing the whole loss on top would just
        # replay the forward twice.
        from horovod_tpu.memory.remat import remat_fn, \
            resolve_remat_policy

        self._remat_policy = resolve_remat_policy(remat=remat)
        if remat:
            loss_fn = remat_fn(loss_fn, self._remat_policy)
        self._loss_fn = loss_fn
        if steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {steps_per_call}")
        self._steps_per_call = int(steps_per_call)
        self._guard = guard
        if guard is not None and self._steps_per_call != 1:
            raise ValueError(
                "guard= requires steps_per_call=1: the guardian must "
                "observe (and be able to suppress) every optimizer step "
                "individually — a scanned multi-step program would apply "
                "k-1 updates before the host sees the first norm")
        # sp joins the reduction scope (token-mean losses make it
        # data-axis math); the batch spec already shards tokens
        axes = self._data_axes + (
            (self._sp_axis,) if self._sp_axis is not None else ())
        # the sharded exchange's topology and form, made static against
        # this mesh: whether it runs leaf by leaf (the plain exchange:
        # one level, nothing asked for that needs a packed buffer) and,
        # where it keeps its buffers, whether their tail is tiled
        leafwise = False
        if shard_optimizer_states:
            from horovod_tpu.ops.pallas_kernels import (
                resolve_fused_collectives,
            )
            from horovod_tpu.optim.optimizer import wants_no_buffer
            from horovod_tpu.runtime.topology import resolve_topology

            qbits = getattr(compression, "wire_reduce_bits", None)
            if compression is not None and qbits is None:
                raise ValueError(
                    "shard_optimizer_states supports only "
                    "wire-reduction compression (Compression.int8)")
            # the mode the compiled step will actually run (the "auto"
            # decision made static) — what bench.py emits as
            # exchange_hierarchy, and what the exchange is built with,
            # so that its init knows the topology outside any mesh
            # context as well
            self._hierarchy = resolve_topology(
                hierarchy, [self._mesh.shape[a] for a in axes],
                axis_names=axes).mode
            leafwise = self._hierarchy == "flat" and wants_no_buffer(
                qbits, self._reduction, exchange_bucket_bytes,
                fused_collectives)
            # leaf by leaf there is no buffer, so no tail for "auto"
            # to tile: it goes down as it came and reads "off"
            if not leafwise:
                fused_collectives = self._fused_collectives = (
                    "on" if resolve_fused_collectives(fused_collectives)
                    else "off")
        # the caller's options over the step's own, which exist only
        # where the step is a plain data-parallel one on TPUs
        from horovod_tpu.optim import exchange_overlap

        laid = exchange_overlap.observed(
            self._mesh, mode, self._data_axes, fsdp_axis,
            leafwise=leafwise)
        if laid:
            compiler_options = {**exchange_overlap.options(mode),
                                **(compiler_options or {})}
        # the train_step.compile span's account of the exchange
        self._describe_exchange = partial(
            exchange_overlap.span_attrs,
            extent=exchange_overlap.exchange_extent(
                self._mesh, self._data_axes),
            laid=laid)
        self._compiler_options = dict(compiler_options) \
            if compiler_options is not None else None
        self._donate_batch = bool(donate_batch)
        # the donated argument set: (params, opt_state) in-place in HBM,
        # plus the batch slot when the feed guarantees fresh buffers
        donated = ((0, 1) if donate else ()) + \
            ((2,) if donate_batch else ())

        repl = NamedSharding(self._mesh, P())
        batch_spec = (P(self._data_axes, self._sp_axis)
                      if self._sp_axis is not None
                      else P(self._data_axes))
        batch_sharding = NamedSharding(self._mesh, batch_spec)

        if sparse_params and mode != "shard_map":
            raise ValueError(
                "sparse_params requires mode='shard_map' (pjit autodiff "
                "reduces every gradient densely)")
        if op is None and mode != "shard_map":
            raise ValueError(
                "op=None (gradients stay local; the optimizer chain owns "
                "the reduction, e.g. DistributedAdasumOptimizer) requires "
                "mode='shard_map' — pjit autodiff would mean-reduce the "
                "gradients behind the optimizer's back")
        if op is None and sparse_params:
            raise ValueError(
                "op=None leaves gradients local, so train-step "
                "sparse_params would never route anything; pass "
                "sparse handling to the distributing optimizer instead")
        if op is None and compression is not None:
            raise ValueError(
                "op=None leaves gradients local, so a train-step "
                "compression would never run; pass compression to the "
                "distributing optimizer (e.g. DistributedAdasumOptimizer) "
                "instead")
        if mode == "pjit" and (op != Average or compression is not None):
            # pjit autodiff performs the (mean) gradient reduction itself;
            # custom reductions/wire formats need the explicit path.
            raise ValueError(
                "mode='pjit' performs a plain mean gradient reduction; use "
                "mode='shard_map' for op=Adasum/Sum or compression")
        # The phases of the compiled step run under named scopes whose
        # names are a contract (docs/metrics.md "Scopes inside the
        # compiled step"): loss_fn, exchange, guard, update.  A scope is
        # metadata: the lowered program is the same with and without.
        def loss_and_grads(params, batch):
            with jax.named_scope("loss_fn"):
                return jax.value_and_grad(self._loss_fn)(params, batch)

        def multi(step_fn):
            """steps_per_call > 1: scan k optimizer steps into the one
            program — one dispatch, k updates, last loss returned."""
            if self._steps_per_call == 1:
                return step_fn
            k = self._steps_per_call

            def stepped(params, opt_state, batch):
                def body(carry, _):
                    p, o, _loss = step_fn(carry[0], carry[1], batch)
                    return (p, o), _loss

                (params, opt_state), losses = jax.lax.scan(
                    body, (params, opt_state), None, length=k)
                return params, opt_state, losses[-1]

            return stepped

        if mode == "pjit":
            # GSPMD inserts this mode's all-reduces after tracing: they
            # carry the weight gradient's path and no scope reaches them
            def step(params, opt_state, batch):
                telemetry.annotate(step_scopes="loss_fn,update")
                loss, grads = loss_and_grads(params, batch)
                with jax.named_scope("update"):
                    updates, opt_state = self._optimizer.update(
                        grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return params, opt_state, loss

            def guarded_step(params, opt_state, batch, limit):
                telemetry.annotate(step_scopes="loss_fn,guard,update")
                loss, grads = loss_and_grads(params, batch)
                with jax.named_scope("guard"):
                    gnorm = jnp.sqrt(_sumsq(grads))
                    ok = jnp.isfinite(gnorm) & (gnorm <= limit)
                with jax.named_scope("update"):
                    updates, new_opt = self._optimizer.update(
                        grads, opt_state, params)
                    new_params = optax.apply_updates(params, updates)
                with jax.named_scope("guard"):
                    params, opt_state = _guard_select(
                        ok, new_params, new_opt, params, opt_state)
                return params, opt_state, loss, gnorm

            if self._fsdp_axis is not None:
                # params/opt arrive committed with their FSDP placements
                # (init) and GSPMD propagates them through the step,
                # inserting gather/reduce-scatter; the batch keeps its
                # data-axis constraint so data parallelism can't silently
                # degrade to replicated compute on a raw batch
                if guard is not None:
                    self._step = jax.jit(
                        guarded_step,
                        in_shardings=(None, None, batch_sharding, None),
                        donate_argnums=donated)
                else:
                    self._step = jax.jit(
                        multi(step),
                        in_shardings=(None, None, batch_sharding),
                        donate_argnums=donated)
            elif guard is not None:
                self._step = jax.jit(
                    guarded_step,
                    in_shardings=(repl, repl, batch_sharding, repl),
                    out_shardings=(repl, repl, repl, repl),
                    donate_argnums=donated)
            else:
                self._step = jax.jit(
                    multi(step),
                    in_shardings=(repl, repl, batch_sharding),
                    out_shardings=(repl, repl, repl),
                    donate_argnums=donated)
        elif mode == "shard_map":
            shard_map = jax.shard_map

            if shard_optimizer_states:
                from horovod_tpu.optim.optimizer import (
                    sharded_distributed_update,
                )

                # the sharded exchange owns the reduction AND the
                # optimizer: RS -> shard-local update -> AG of updates
                world = 1
                for a in axes:
                    world *= self._mesh.shape[a]
                self._optimizer = sharded_distributed_update(
                    optimizer, op=op, axis=axes,
                    quantized_bits=qbits,
                    bucket_bytes=exchange_bucket_bytes,
                    world=world,
                    hierarchy=self._hierarchy,
                    fused_collectives=fused_collectives,
                    error_feedback=self._error_feedback,
                    level_codecs=self._level_codecs,
                    reduction=self._reduction)
            elif op is not None:
                from horovod_tpu.optim.optimizer import distributed_gradients

                reducer = distributed_gradients(
                    op=op, axis=axes, mode="shard_map",
                    compression=compression, sparse_params=sparse_params)

            # the reducer lays ``exchange`` itself, and the sharded
            # exchange its ``exchange/scatter`` / ``exchange/gather``
            # inside ``update`` (optim/optimizer.py)
            def per_device(params, opt_state, batch):
                telemetry.annotate(step_scopes="loss_fn,exchange,update")
                loss, grads = loss_and_grads(params, batch)
                if self._op is not None and not self._shard_opt:
                    grads, _ = reducer.update(grads, optax.EmptyState())
                # op=None: gradients stay local — the optimizer chain owns
                # the cross-shard reduction (the delta-Adasum form, where
                # hvd.DistributedAdasumOptimizer reduces *updates*)
                with jax.named_scope("update"):
                    updates, opt_state = self._optimizer.update(
                        grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                with jax.named_scope("exchange"):
                    loss = C.allreduce(loss, op=Average, axis=axes)
                return params, opt_state, loss

            def per_device_guarded(params, opt_state, batch, limit):
                telemetry.annotate(
                    step_scopes="loss_fn,exchange,guard,update")
                loss, grads = loss_and_grads(params, batch)
                if self._op is not None and not self._shard_opt:
                    # reducer already made grads identical on every
                    # device: the local norm IS the global norm
                    grads, _ = reducer.update(grads, optax.EmptyState())
                    with jax.named_scope("guard"):
                        gnorm = jnp.sqrt(_sumsq(grads))
                else:
                    # pre-reduction grads (the sharded exchange or the
                    # delta-form optimizer owns the reduction): one
                    # scalar allreduce makes the verdict — and therefore
                    # the select — identical on every device
                    with jax.named_scope("guard"):
                        sumsq = _sumsq(grads)
                    with jax.named_scope("exchange"):
                        sumsq = C.allreduce(sumsq, op=Sum, axis=axes)
                    with jax.named_scope("guard"):
                        gnorm = jnp.sqrt(sumsq)
                with jax.named_scope("guard"):
                    ok = jnp.isfinite(gnorm) & (gnorm <= limit)
                with jax.named_scope("update"):
                    updates, new_opt = self._optimizer.update(
                        grads, opt_state, params)
                    new_params = optax.apply_updates(params, updates)
                with jax.named_scope("guard"):
                    params, opt_state = _guard_select(
                        ok, new_params, new_opt, params, opt_state)
                with jax.named_scope("exchange"):
                    loss = C.allreduce(loss, op=Average, axis=axes)
                return params, opt_state, loss, gnorm

            # out_specs=P() with check_vma=False: params come out
            # genuinely replicated (the reducer or the delta-form
            # optimizer chain makes every shard's update identical), but
            # with op=None the *optimizer state* (e.g. Adasum-wrapped
            # momenta) is per-rank by construction — and with
            # shard_optimizer_states=True deliberately so: each rank
            # stores only its 1/N state shard (the ZeRO memory saving:
            # slabs in leaf shape, or slices of packed buffers); the
            # shard-shaped leaves ride the P() boundary as
            # per-device values.  Host reads and
            # checkpoints of that state then capture device 0's copy —
            # deliberately matching the reference's rank-0-checkpoint
            # semantics (save on rank 0, broadcast on restore); a
            # reshard of a restored checkpoint replicates rank 0's
            # momenta, which is exactly what broadcast-restore does.
            if guard is not None:
                smapped = shard_map(
                    per_device_guarded, mesh=self._mesh,
                    in_specs=(P(), P(), batch_spec, P()),
                    out_specs=(P(), P(), P(), P()),
                    check_vma=False)
                self._step = jax.jit(smapped, donate_argnums=donated)
            else:
                smapped = shard_map(
                    per_device, mesh=self._mesh,
                    in_specs=(P(), P(), batch_spec),
                    out_specs=(P(), P(), P()),
                    check_vma=False)
                self._step = jax.jit(
                    multi(smapped), donate_argnums=donated)
        else:
            raise ValueError(f"unknown mode {mode!r}")

        self._batch_sharding = batch_sharding
        self._replicated = repl
        self._compiled_cache: dict = {}      # insertion-ordered LRU
        # cache_capacity bounds the in-memory executable LRU (the
        # response-cache capacity knob made real)
        if state.is_initialized():
            self._compiled_cache_max = \
                state.global_state().config.cache_capacity
        else:
            self._compiled_cache_max = self._COMPILED_CACHE_MAX
        self._compile_cache_hit: Optional[bool] = None
        # telemetry handles (docs/metrics.md): cached here so the
        # per-call cost is one enabled-branch when metrics are off
        self._tel_steps = telemetry.counter(
            "hvd_steps_total", "optimizer steps executed")
        self._tel_step_seconds = telemetry.histogram(
            "hvd_step_seconds",
            "host wall time per train-step dispatch call")
        self._tel_cache_hits = telemetry.counter(
            "hvd_compile_cache_hits_total",
            "in-memory executable-cache hits")
        self._tel_cache_misses = telemetry.counter(
            "hvd_compile_cache_misses_total",
            "in-memory executable-cache misses")
        self._calls = 0     # ordinal of the next call: its spans' seq

    _COMPILED_CACHE_MAX = 16

    @property
    def batch_sharding(self):
        """The ``NamedSharding`` this step expects its batch in — what
        an input pipeline's ``place`` callable targets when it issues
        ``jax.device_put`` ahead of the step (docs/data.md)."""
        return self._batch_sharding

    @property
    def donates_batch(self) -> bool:
        """Whether the batch argument is donated (the pipeline-fed
        input slot; each call must then receive fresh buffers)."""
        return self._donate_batch

    @property
    def plan(self):
        """The resolved :class:`~horovod_tpu.parallel.plan.ShardingPlan`
        this step was compiled for (None when built from raw
        mesh/data_axes arguments) — ``bench.py`` emits its canonical
        string as the ``plan`` BENCH field."""
        return self._plan

    @property
    def exchange_hierarchy(self):
        """The exchange topology this step runs: ``"two_level"``/
        ``"flat"`` once resolved against the mesh (sharded exchange),
        the raw knob (``"auto"``) when no sharded exchange is active."""
        return self._hierarchy

    @property
    def fused_collectives(self) -> str:
        """The resolved final-bucket schedule: ``"on"`` when the
        sharded exchange runs the tile-granular fused tail, ``"off"``
        otherwise (docs/fused_kernels.md)."""
        return self._fused_collectives

    @property
    def reduction(self) -> Optional[str]:
        """The sharded exchange's combine operator (``"sum"`` |
        ``"adasum"``) once resolved (explicit argument > runtime config
        > ``HOROVOD_EXCHANGE_REDUCTION``); ``None`` when no sharded
        exchange is active (docs/adasum.md); ``bench.py`` emits it as
        the ``reduction`` BENCH field."""
        return self._reduction

    @property
    def remat_policy(self) -> str:
        """The resolved remat policy (``none|dots|full|offload``) this
        step was built under — explicit ``remat=`` argument or the
        ``HOROVOD_REMAT_POLICY`` knob (memory/remat.py, docs/memory.md);
        ``bench.py --hbm-budget`` emits it as the ``remat_policy`` BENCH
        field."""
        return self._remat_policy

    @property
    def compile_cache_hit(self) -> Optional[bool]:
        """Whether this step's most recent XLA compile was served from
        the persistent compilation cache (``True``), compiled
        (``False``), or has not happened / the cache is disabled
        (``None``).  ``bench.py`` emits this as the ``cache_hit`` BENCH
        field."""
        return self._compile_cache_hit

    def init(self, params):
        """Place params on the mesh replicated and build optimizer state.

        Accepts leaves that are already *cross-process* arrays — e.g.
        the output of ``broadcast_variables``, whose eager plane places
        one replica per process.  ``device_put`` of such an array onto
        the full mesh is an illegal cross-host reshard (the device sets
        differ) whenever processes own more than one device, so
        fully-replicated cross-process leaves are first dropped to their
        local host copy.
        """
        def localize(x):
            if isinstance(x, jax.Array) and \
                    not x.sharding.is_fully_addressable:
                if not x.is_fully_replicated:
                    raise ValueError(
                        "DistributedTrainStep.init expects replicated "
                        f"params; got a cross-process array sharded as "
                        f"{x.sharding}")
                return np.asarray(x)       # local copy of the replica
            return x

        params = jax.tree_util.tree_map(localize, params)
        if self._fsdp_axis is not None:
            from horovod_tpu.parallel import fsdp as _fsdp

            kw = {} if self._fsdp_min is None else \
                {"min_weight_size": self._fsdp_min}
            params = _fsdp.shard_params(params, self._mesh,
                                        self._fsdp_axis, **kw)
            # optimizer state gets the same placement rule: mu/nu carry
            # their parameter's shape so they shard exactly as it does;
            # scalars/counters come out replicated on the mesh (an
            # unconstrained jit would leave them single-device, which a
            # later mesh-wide step rejects)
            shapes = jax.eval_shape(self._optimizer.init, params)
            out_sh = _fsdp.sharding_specs(shapes, self._mesh,
                                          self._fsdp_axis, **kw)
            opt_state = jax.jit(self._optimizer.init,
                                out_shardings=out_sh)(params)
            return params, opt_state
        params = jax.device_put(params, self._replicated)
        opt_state = jax.device_put(self._optimizer.init(params),
                                   self._replicated)
        return params, opt_state

    def shard_batch(self, batch):
        """Place a host batch onto the mesh sharded along the data axis.

        ``batch`` is the *global* batch, identical on every process (the
        reference's data-parallel contract: each worker reads the full
        shuffled stream and consumes its slice).  Multi-process, each
        process materializes only the rows its addressable devices own
        (``make_array_from_callback``) — no cross-process value
        broadcast/compare and no redundant full-batch transfer, which
        ``device_put`` onto a partially-addressable sharding would do."""
        if jax.process_count() == 1:
            return jax.device_put(batch, self._batch_sharding)
        sharding = self._batch_sharding

        def to_global(arr):
            if isinstance(arr, jax.Array) and \
                    not arr.sharding.is_fully_addressable:
                # already global (spans other processes): keep
                # device_put's idempotent semantics.  Fully-addressable
                # arrays — including ones spread over this process's
                # local devices — take the host path below, which works
                # for any local layout.
                return jax.device_put(arr, sharding)
            # host path: feed each addressable shard straight from the
            # numpy buffer — no extra device round-trips (callers should
            # pass host arrays; a single-device jax.Array costs one D2H)
            if not isinstance(arr, np.ndarray):
                arr = np.asarray(arr)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])

        return jax.tree_util.tree_map(to_global, batch)

    def shard_local_batch(self, batch):
        """Place per-process rows onto the mesh as one global batch.

        The streaming-reader contract (petastorm analogue): each process
        contributes only the rows *it* read — its shard — rather than
        slicing an identical global batch as :meth:`shard_batch` does.
        Every process must pass the same number of rows per call.
        """
        if jax.process_count() == 1:
            return jax.device_put(batch, self._batch_sharding)
        sharding = self._batch_sharding

        def to_global(arr):
            if not isinstance(arr, np.ndarray):
                arr = np.asarray(arr)
            return jax.make_array_from_process_local_data(sharding, arr)

        return jax.tree_util.tree_map(to_global, batch)

    def _ambient_mesh(self):
        """pjit mode traces and runs with its mesh ambient
        (``jax.set_mesh``), so the model sees it: the tp modules
        constrain their kernels onto it, and Mosaic kernels — which
        GSPMD cannot partition — find the axes to ``shard_map`` over
        (models/transformer.py).  shard_map mode binds the mesh
        itself."""
        if self._mode == "pjit":
            return jax.set_mesh(self._mesh)
        return contextlib.nullcontext()

    def compiled_text(self, params, opt_state, batch) -> str:
        """Optimized-HLO dump of the step for these arguments — the
        artifact the collective-fusion guard tests and the
        ``docs/scaling.md`` bytes-on-wire model inspect (see
        :mod:`horovod_tpu.utils.hlo`).  The text of the executable
        that runs them: nothing is compiled where the step has already
        been called on such arguments."""
        args = (params, opt_state, batch)
        if self._guard is not None:
            args += (np.float32(np.inf),)
        with self._ambient_mesh():
            return self._executable_for(args).as_text()

    def _record_step_telemetry(self, seconds: float) -> None:
        """Per-call telemetry: step count/duration and the run-context
        step for log/trace correlation."""
        self._tel_step_seconds.observe(seconds)
        self._tel_steps.inc(self._steps_per_call)
        telemetry.run_context().advance_step(self._steps_per_call)

    def _guard_unpack(self, out, limit):
        """Guarded steps return ``(params, opt_state, loss, gnorm)``:
        surface the norm to the guardian (which may raise per policy)
        and hand the caller the usual 3-tuple.  The device→host read of
        the norm scalar is the enabled-path cost ``bench.py --chaos``
        reports as guard overhead."""
        params, opt_state, loss, gnorm = out
        self._guard.observe(float(gnorm), limit=float(limit))
        return params, opt_state, loss

    def __call__(self, params, opt_state, batch):
        with telemetry.span("train_step.call", seq=self._calls) as call:
            self._calls += 1
            with self._ambient_mesh():
                return self._dispatch(call, params, opt_state, batch)

    def _dispatch(self, call, params, opt_state, batch):
        with telemetry.span("train_step.prepare"):
            if self._guard is not None:
                # the limit rides as a traced runtime scalar: threshold
                # drift as the EMA baseline tightens never recompiles
                limit = np.float32(self._guard.current_limit())
                args = (params, opt_state, batch, limit)
            else:
                limit = None
                args = (params, opt_state, batch)
            run = self._executable_for(args)
        with telemetry.span("train_step.launch") as launch:
            out = run(*args)
        if telemetry.enabled():
            self._record_step_telemetry(launch.end - call.start)
        if limit is not None:
            return self._guard_unpack(out, limit)
        return out

    def _executable_for(self, args):
        """The executable for ``args``: the one place the step is
        lowered and compiled (per-compile XLA options need
        lower-once-compile-with-options; JAX's persistent cache makes
        the compile a load on a warm start).  The in-memory key covers
        shardings too — an executable compiled for one input layout
        must not be fed same-shape differently-sharded arrays — and the
        cache is LRU-bounded (Config.cache_capacity) so varying batch
        signatures don't accumulate executables for the process
        lifetime."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = (treedef,
               tuple((np.shape(l), str(getattr(l, "dtype",
                                               type(l).__name__)),
                      repr(getattr(l, "sharding", None)))
                     for l in leaves))
        st = state.global_state() if state.is_initialized() else None
        compiled = self._compiled_cache.pop(key, None)
        if compiled is None:
            self._tel_cache_misses.inc()
            if st is not None:
                st.cache_stats["misses"] += 1
            with telemetry.span("train_step.lower") as lowering:
                lowered = self._step.lower(*args)
            with telemetry.span("train_step.compile") as compiling:
                served = compile_cache.cache_hits()
                compiled = lowered.compile(
                    compiler_options=self._compiler_options)
                hit = compile_cache.cache_hits() > served
                # with what the traced program said of itself
                # (telemetry.annotate)
                compiling.attrs = {"hit": hit, **(lowering.attrs or {}),
                                   **self._describe_exchange(compiled)}
            self._compile_cache_hit = \
                hit if compile_cache.active() else None
        else:
            self._tel_cache_hits.inc()
            if st is not None:
                st.cache_stats["hits"] += 1
        self._compiled_cache[key] = compiled     # reinsert = most recent
        while len(self._compiled_cache) > self._compiled_cache_max:
            self._compiled_cache.pop(next(iter(self._compiled_cache)))
        return compiled


def join_step(grads, has_data, axis: AxisSpec = GLOBAL_AXES):
    """Ragged-data gradient reduction: the in-graph JoinOp.

    The reference's ``hvd.join()`` makes joined (out-of-data) ranks
    contribute zero tensors while others finish
    (``collective_operations.h:259 JoinOp``, zero synthesis in
    ``controller.cc:263-274``).  SPMD formulation: every shard always
    participates; shards whose ``has_data`` flag is False contribute zeros
    and the average divides by the count of contributing shards only.

    Call inside ``shard_map``: ``grads = join_step(grads, has_data)``.
    """
    flag = jnp.asarray(has_data, jnp.float32)
    n = C.allreduce(flag, op=Sum, axis=axis)
    inv = jnp.where(n > 0, 1.0 / jnp.maximum(n, 1.0), 0.0)
    leaves, td = jax.tree_util.tree_flatten(grads)
    masked = [jnp.where(flag > 0, g, jnp.zeros_like(g)) for g in leaves]
    summed = C.grouped_allreduce(masked, op=Sum, axis=axis)
    out = [(s.astype(jnp.float32) * inv).astype(s.dtype) for s in summed]
    return jax.tree_util.tree_unflatten(td, out)
