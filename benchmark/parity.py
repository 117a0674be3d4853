"""The comparison behind ``correct``: one step of the cell's layout
against the configuration's plain reference.

Outside the measured window, at the cell's widths with the depth cut as
the configuration's ``parity`` group says: the same global batch goes
(a) through ``DistributedTrainStep`` exactly as the cell builds it —
mode, mesh, kernels, compute type — with ``optax.sgd(1.0)``, so that
parameters before minus parameters after *is* the gradient the step
applied, and (b) through ``jax.value_and_grad`` of the plain float32
reference on one device.  Loss, gradient norm and gradient (relative L2
distance) must agree within the tolerances the configuration states,
with their reason.  Whole trees go through one ``jit`` each: leaf by
leaf, every distinct shape would compile a program of its own in every
run.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


@jax.jit
def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


@jax.jit
def _difference(a, b):
    return jax.tree_util.tree_map(jnp.subtract, a, b)


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@jax.jit
def _sumsq(tree):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree_util.tree_leaves(tree))


@jax.jit
def _sumsq_diff(a, b, b_scale):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32) - b_scale * y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def check(hvd, case, train_step_kwargs: dict, chips: int, seed: int) -> dict:
    one = jax.devices()[0]
    t0 = time.perf_counter()
    rows = case.rows_per_chip * chips
    batch = case.sample(np.random.default_rng([seed, 2]), rows)

    variables = jax.jit(case.init)(jax.random.PRNGKey(seed))
    step = hvd.DistributedTrainStep(case.loss_fn, optax.sgd(1.0),
                                    **train_step_kwargs)
    params, opt_state = step.init(variables)
    del variables
    # the step donates its parameters: keep a copy, on one device, for
    # the reference and for the difference
    before = jax.device_put(_copy(params), one)
    after, _, loss = step(params, opt_state, step.shard_batch(batch))
    grads = _difference(before, jax.device_put(after, one))
    del after, params, opt_state
    loss = float(loss)
    t1 = time.perf_counter()

    reference = jax.jit(jax.value_and_grad(case.reference_loss))
    chunk = case.reference_chunk_rows or rows
    ref_loss, ref_grads = 0.0, None
    for lo in range(0, rows, chunk):
        part = jax.device_put(jax.tree_util.tree_map(
            lambda a: a[lo:lo + chunk], batch), one)
        value, g = reference(before, part)
        ref_loss += float(value) * chunk / rows
        ref_grads = g if ref_grads is None else _add(ref_grads, g)
    share = chunk / rows          # equal chunks: the mean of their means

    norm = math.sqrt(float(_sumsq(grads)))
    ref_norm = share * math.sqrt(float(_sumsq(ref_grads)))
    out = {
        "loss": loss, "reference_loss": ref_loss,
        "grad_norm": norm, "reference_grad_norm": ref_norm,
        "grad_rel_l2": math.sqrt(float(
            _sumsq_diff(grads, ref_grads, share))) / ref_norm,
        "step_s": t1 - t0, "reference_s": time.perf_counter() - t1,
        "limits": {"loss_rtol": case.loss_rtol,
                   "grad_norm_rtol": case.grad_norm_rtol,
                   "grad_rel_l2": case.grad_rel_l2},
    }
    out["ok"] = bool(
        math.isfinite(loss) and ref_norm > 0
        and abs(loss - ref_loss) <= case.loss_rtol * abs(ref_loss)
        and abs(norm - ref_norm) <= case.grad_norm_rtol * ref_norm
        and out["grad_rel_l2"] <= case.grad_rel_l2)
    return out
