"""Expert parallelism: top-1-routed MoE over the ``ep`` mesh axis, and
dropless top-k routing over the experts one rank of a wider layout holds.

Extension beyond the reference (SURVEY §2.3: EP absent; the
variable-split ``alltoall`` it ships — ``operations.cc:979`` — is
precisely the dispatch primitive).  TPU-first formulation: static
capacity buckets (no dynamic shapes under jit) — each shard scatters
its tokens into an ``(experts, capacity, d)`` dispatch buffer, one
``all_to_all`` moves expert slots to the shards that own them, expert
FFNs run as one batched matmul (MXU-friendly), and the inverse
``all_to_all`` brings results home for the gate-weighted combine.
Tokens beyond an expert's capacity are dropped (contribute zero), the
standard Switch-Transformer policy.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.parallel.mesh import AXIS_EP


def top1_routing(scores: jax.Array, capacity: int):
    """Greedy top-1 assignment with per-expert capacity.

    Args:
      scores: (tokens, num_experts) gate logits.
      capacity: max tokens per expert on this shard's batch.

    Returns:
      (expert_idx, slot, keep, gate): chosen expert, position inside its
      capacity bucket, whether the token fit, and its softmax gate weight.
    """
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    one_hot = jax.nn.one_hot(expert_idx, scores.shape[-1], dtype=jnp.int32)
    slot = (jnp.cumsum(one_hot, axis=0) - 1)
    slot = jnp.take_along_axis(slot, expert_idx[:, None], axis=1)[:, 0]
    keep = slot < capacity
    return expert_idx, slot, keep, gate


def expert_parallel_ffn(x: jax.Array, gate_kernel: jax.Array,
                        expert_fn: Callable, num_experts_total: int,
                        capacity_factor: float = 1.25,
                        axis: str = AXIS_EP,
                        scores: Optional[jax.Array] = None,
                        fused: bool = False,
                        interpret: bool = False):
    """Mixture-of-experts FFN with experts sharded over ``axis``.

    Call inside ``shard_map``.  Args:
      x: (tokens_local, d) this shard's tokens.
      gate_kernel: (d, num_experts_total) router weights (replicated).
      expert_fn: ``f(local_expert_params_selector) -> (E_local, C_world,
        d) -> (E_local, C_world, d)`` — actually invoked as
        ``expert_fn(buffers)`` where ``buffers`` is (E_local, world*C, d)
        (unfused) or one (E_local, C, d) source tile at a time (fused);
        must apply this shard's local experts batched over dim 0 and be
        token-wise (each slot independent) so both schedules agree.
      num_experts_total: E; must divide by the axis size.
      capacity_factor: per-expert capacity = ceil(cf * tokens/E).
      fused: route the dispatch/combine through the tile-fused
        ``a2a ⊗ expert-matmul`` ring
        (:func:`~horovod_tpu.ops.pallas_kernels.expert_alltoall_ffn`)
        instead of two boundary-wide ``all_to_all``\\ s — identical
        numerics (forward and grads), overlapped wire.  Resolve the
        ``"auto"|"on"|"off"`` knob with
        :func:`~horovod_tpu.ops.pallas_kernels.resolve_fused_collectives`
        before calling.

    Returns:
      (tokens_local, d) gate-weighted expert outputs (dropped tokens get
      zeros) and the fraction of dropped tokens (scalar, for aux losses).
    """
    world = lax.axis_size(axis)
    if num_experts_total % world != 0:
        raise ValueError(
            f"num_experts_total={num_experts_total} not divisible by "
            f"'{axis}' size {world}")
    e_local = num_experts_total // world
    t, d = x.shape
    capacity = int(max(1, -(-capacity_factor * t // num_experts_total)))

    # router in fp32 regardless of compute dtype: near-tie tokens
    # argmax differently in bf16 (measured ~0.2%), which would make
    # the dispatched routing diverge from fp32-side accounting (aux
    # losses) and from local-mode execution.  Callers that already
    # computed fp32 scores (e.g. for the Switch aux loss) pass them in
    # — the DISPATCHED routing and the accounted routing must be the
    # same routing, and the gate matmul runs once.
    if scores is None:
        scores = x.astype(jnp.float32) @ gate_kernel.astype(jnp.float32)
    expert_idx, slot, keep, gate = top1_routing(scores, capacity)

    # scatter tokens into (E, C, d) dispatch buckets
    dispatch = jnp.zeros((num_experts_total, capacity, d), x.dtype)
    safe_slot = jnp.where(keep, slot, 0)
    dispatch = dispatch.at[expert_idx, safe_slot].add(
        jnp.where(keep[:, None], x, 0.0))

    # (E, C, d) -> (world, E_local, C, d); dim 0 is the destination
    # shard.  The dispatch/combine exchange (two alltoalls, or the fused
    # ppermute ring that streams one tile per hop while the previous
    # tile's expert matmul computes) lives in ops.pallas_kernels.
    from horovod_tpu.ops.pallas_kernels import expert_alltoall_ffn
    dispatch = dispatch.reshape(world, e_local, capacity, d)
    combined = expert_alltoall_ffn(dispatch, expert_fn, axis,
                                   fused=fused, interpret=interpret)
    combined = combined.reshape(num_experts_total, capacity, d)

    # gather each token's result from its (expert, slot) and weight by gate
    y = combined[expert_idx, safe_slot]
    y = jnp.where(keep[:, None], y * gate[:, None].astype(y.dtype), 0.0)
    drop_fraction = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return y, drop_fraction


# ---------------------------------------------------------------------------
# dropless top-k routing over the experts one rank holds
# ---------------------------------------------------------------------------

def topk_routing(scores: jax.Array, bias: jax.Array, top_k: int,
                 scale: float = 1.0):
    """Sigmoid-scored top-k choice with a selection bias.

    Args:
      scores: (tokens, num_experts) router logits.
      bias: (num_experts,) added to the scores for the *choice* only
        (the load-balancing bias); the weights come from the scores.
      top_k: experts a token.
      scale: the routed scaling factor.

    Returns:
      (expert_idx, weights): (tokens, top_k) chosen experts and
      ``s_i / sum_chosen(s) * scale`` in fp32, ``s = sigmoid(scores)``.
    """
    s = jax.nn.sigmoid(scores.astype(jnp.float32))
    _, expert_idx = lax.top_k(s + lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(s, expert_idx, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return expert_idx, weights


def held_assignments(expert_idx: jax.Array, held: tuple):
    """Sort a step's assignments by the held expert they land on.

    ``expert_idx``: (tokens, top_k) over all experts; ``held`` the
    half-open range ``(lo, hi)`` of expert ids this rank holds.

    Returns ``(order, group_sizes)``: ``order`` (tokens * top_k,) lists
    the flat assignments, those of held expert ``lo`` first, then
    ``lo + 1`` ..., those of absent experts last; ``group_sizes``
    (hi - lo,) counts the assignments of each held expert.  The first
    ``group_sizes.sum()`` entries of ``order`` are what this rank
    computes: every one of them, whatever the imbalance.
    """
    lo, hi = held
    local = _flat_held(expert_idx, held)
    order = jnp.argsort(local, stable=True)
    group_sizes = jnp.sum(
        local[:, None] == jnp.arange(hi - lo, dtype=local.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    return order, group_sizes


def _flat_held(expert_idx: jax.Array, held: tuple) -> jax.Array:
    """The held expert (``0 .. hi - lo - 1``) each flat assignment lands
    on; ``hi - lo`` where it lands on none."""
    lo, hi = held
    flat = expert_idx.reshape(-1)
    return jnp.where((flat >= lo) & (flat < hi), flat - lo, hi - lo)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine_rows(static, out, w, token_of, real, starts, pos, wt):
    """``sum over a token's real rows of w[r] * out[r]`` by
    :func:`~horovod_tpu.ops.pallas_kernels.moe_row_sum` (``wt`` holds
    ``w`` by token and run); backward a gather and a row-wise product."""
    from horovod_tpu.ops.pallas_kernels import moe_row_sum

    top_k, interpret = static
    return moe_row_sum(out, starts, pos, wt, top_k=top_k,
                       interpret=interpret)


def _combine_rows_fwd(static, out, w, token_of, real, starts, pos, wt):
    return (_combine_rows(static, out, w, token_of, real, starts, pos, wt),
            (out, w, token_of, real))


def _combine_rows_bwd(static, residuals, dy):
    out, w, token_of, real = residuals
    # select, never multiply: the rows past the last group hold whatever
    # the grouped matmul left there
    of_row = jnp.where(real[:, None], dy[token_of], 0)
    d_w = jnp.sum(jnp.where(real[:, None], out, 0).astype(jnp.float32)
                  * of_row.astype(jnp.float32), axis=-1)
    return (of_row * w[:, None].astype(of_row.dtype), d_w.astype(w.dtype),
            None, None, None, None, None)


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch_rows(static, x, token_of, real, starts, pos):
    """The gather ``x[token_of]`` with nothing in the rows past the last
    group; backward the rows-to-tokens sum at weight one."""
    return jnp.where(real[:, None], x[token_of], 0)


def _dispatch_rows_fwd(static, x, token_of, real, starts, pos):
    return _dispatch_rows(static, x, token_of, real, starts, pos), \
        (starts, pos)


def _dispatch_rows_bwd(static, residuals, d_rows):
    from horovod_tpu.ops.pallas_kernels import moe_row_sum

    top_k, interpret = static
    starts, pos = residuals
    return (moe_row_sum(d_rows, starts, pos, pos >= 0, top_k=top_k,
                        interpret=interpret), None, None, None, None)


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


def kept_in_pieces(x: jax.Array, rows: int) -> jax.Array:
    """``x`` named ``"grouped_matmul"``
    (``jax.ad_checkpoint.checkpoint_name``) as pieces of ``rows`` rows
    each, joined again: its row count is a multiple of ``rows``, and one
    piece is ``x`` itself, with no copy.

    Branches of a ``lax.switch`` inside a rematerialised region share a
    kept value's residual slot only where shape and dtype agree (JAX's
    cond partial evaluation); every slot a branch does not fill it fills
    with zeros.  Kept whole, buffers of three sizes hold the sum of
    their rows; kept in pieces of one size, the largest's."""
    if x.shape[0] == rows:
        return checkpoint_name(x, "grouped_matmul")
    return jnp.concatenate([checkpoint_name(x[i:i + rows], "grouped_matmul")
                            for i in range(0, x.shape[0], rows)])


def held_expert_ffn(x: jax.Array, expert_idx: jax.Array,
                    weights: jax.Array, held: tuple, grouped_fn: Callable,
                    expert_params, interpret: bool = False):
    """What the experts ``held`` add to each token: dropless.

    The rank routes over all experts and computes its own experts' part
    only.  Assignments that land on a held expert are gathered, sorted
    by expert, into a buffer of static size of which the first
    ``group_sizes.sum()`` rows are real;
    ``grouped_fn(expert_params, rows, group_sizes) -> rows`` applies
    expert ``g`` to its run of rows (a grouped matmul, whose work
    follows ``group_sizes``: it need not touch the rows past the last
    group, and what it leaves there is never read) and the results are
    added into their tokens, weighted.  Assignments to absent experts
    add nothing: their part is another rank's.

    Nothing is dropped at any imbalance: the largest buffer holds every
    assignment of the step, ``tokens * top_k`` rows.  What is of a
    buffer's size and not of the load's is memory traffic — the forward
    gathers, the untaken buffers' zeros and the elementwise work between
    the matmuls — so the same program is traced at one row a token, two,
    and ``top_k``, and a step takes, by ``lax.switch`` on the count that
    landed, the smallest buffer that holds it.  Each is rematerialised,
    so that the backward pass keeps no other buffer's residuals: it runs
    the chosen one again, but for the results ``grouped_fn`` names
    ``"grouped_matmul"`` (``jax.ad_checkpoint.checkpoint_name``), which
    are kept.  Named in pieces of ``tokens`` rows (:func:`kept_in_pieces`;
    every buffer is a whole number of them), the three buffers' kept
    results share one set of slots, ``tokens * top_k`` rows, and the
    taken buffer fills with zeros only the pieces it does not fill.

    **The sum of rows into their tokens** — the combine, and the
    transpose of the dispatch's gather — is
    :func:`~horovod_tpu.ops.pallas_kernels.moe_row_sum` where
    :func:`~horovod_tpu.ops.pallas_kernels.moe_row_sum_runs_kernel` says
    so (a TPU, or ``interpret``; shapes that tile): one pass over the
    rows that landed, added in fp32 and rounded once, no scatter.  It
    leans on :func:`held_assignments`' *stable* sort: inside an expert's
    run the rows ascend by token and no token repeats, so a tile of
    tokens owns one contiguous stretch of each run
    (:func:`~horovod_tpu.ops.pallas_kernels.moe_row_sum_plan`).  Each
    side is a ``jax.custom_vjp`` whose residuals are alive anyway
    (``token_of``, ``w``, ``real``, the plan's integers, the grouped
    matmul's kept result), so autodiff cannot put a scatter-add over
    repeating indices back.  Elsewhere the ``jax.numpy`` lines below run
    as they did: XLA's scatter-add, which rounds to the rows' type after
    every row it adds (a tenth of HBM's pace at 4,096–8,192 rows of
    3,584 on a v5e: PERF.md, PR 39).

    Args:
      x: (tokens, d).
      expert_idx, weights: (tokens, top_k), from :func:`topk_routing`.
      held: ``(lo, hi)`` expert ids held here.
      interpret: run the Pallas kernels interpreted (CPU test plumbing).

    Returns (tokens, d_out): ``sum_k weights[t, k] * expert_k(x[t])``
    over the chosen experts that are held.
    """
    from horovod_tpu.ops.pallas_kernels import (
        moe_row_sum_plan,
        moe_row_sum_runs_kernel,
        moe_row_sum_tile,
    )

    tokens, top_k = expert_idx.shape
    caps = sorted({tokens * min(m, top_k) for m in (1, 2, top_k)})
    static = (top_k, interpret)
    shape = (tokens, x.shape[-1], top_k, held[1] - held[0])
    kernel = moe_row_sum_runs_kernel(*shape, x.dtype, interpret)

    def part(cap, x, weights, params, order, group_sizes, *plan):
        with jax.named_scope("dispatch"):
            picked = order[:cap]
            token_of = picked // top_k
            real = jnp.arange(cap) < jnp.sum(group_sizes)
            if kernel:
                starts, pos, wt = plan
                rows = _dispatch_rows(static, x, token_of, real, starts, pos)
            else:
                # select, never multiply: in the backward pass the rows
                # past the last group hold whatever the grouped matmul
                # left there
                rows = jnp.where(real[:, None], x[token_of], 0)
        with jax.named_scope("experts"):
            out = grouped_fn(params, rows, group_sizes)
        with jax.named_scope("combine"):
            w = jnp.where(real, weights.reshape(-1)[picked], 0.0)
            if kernel:
                return _combine_rows(static, out, w, token_of, real, starts,
                                     pos, wt)
            out = jnp.where(real[:, None], out, 0) \
                * w[:, None].astype(out.dtype)
            return jnp.zeros((tokens, out.shape[-1]), out.dtype) \
                .at[token_of].add(out)

    with jax.named_scope("dispatch"):
        order, group_sizes = held_assignments(expert_idx, held)
        tier = jnp.sum(jnp.sum(group_sizes)
                       > jnp.asarray(caps[:-1], jnp.int32), dtype=jnp.int32)
        plan = ()
        if kernel:
            plan = moe_row_sum_plan(
                _flat_held(expert_idx, held), group_sizes,
                lax.stop_gradient(weights),
                moe_row_sum_tile(*shape, x.dtype.itemsize))
    keep = jax.checkpoint_policies.save_only_these_names("grouped_matmul")
    return lax.switch(
        tier, [jax.checkpoint(functools.partial(part, cap), policy=keep)
               for cap in caps],
        x, weights, expert_params, order, group_sizes, *plan)
