"""Remat policy compiler — the per-block rematerialization tiers.

The boolean ``remat`` flag on the model configs (and on
``DistributedTrainStep``) collapses a whole design space into on/off:
*what* gets saved across the forward/backward boundary is exactly the
activation high-water the HBM planner (``memory/planner.py``,
docs/memory.md) trades against recompute time.  This module promotes
the flag into a small closed policy vocabulary:

====================  =====================================================
policy                what the backward pass may read without recompute
====================  =====================================================
``none``              everything — no remat, peak activations, no overhead
``dots``              matmul outputs only (``jax.checkpoint_policies.
                      dots_saveable``) — the classic "recompute the cheap
                      elementwise ops" middle tier
``full``              nothing — every block replays its forward
``offload``           matmul outputs, streamed to pinned host memory
                      (``offload_dot_with_no_batch_dims``) instead of HBM;
                      falls back to ``dots`` where the backend has no
                      pinned-host space (CPU XLA)
====================  =====================================================

Resolution precedence (:func:`resolve_remat_policy`): an explicit
policy string beats the ``HOROVOD_REMAT_POLICY`` env knob beats the
legacy boolean (``True`` → ``full``, the exact behavior the flag had)
beats ``none``.  ``DistributedTrainStep.remat_policy`` reports the
resolved policy.

JAX/flax are imported lazily so the policy *names* stay usable from
the stdlib-only analysis layer (``analysis/cost_model.py`` duplicates
the vocabulary by value, like ``PLAN_GRAMMAR_KEYS``).
"""

from __future__ import annotations

import os
from typing import Optional, Union

#: Closed policy vocabulary, cheapest-memory last.  Mirrored by value
#: in ``analysis/cost_model.REMAT_POLICIES`` (stdlib-only module).
REMAT_POLICIES = ("none", "dots", "full", "offload")

ENV_REMAT_POLICY = "HOROVOD_REMAT_POLICY"


def validate_policy(policy: str) -> str:
    if policy not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {policy!r}: expected one of "
            f"{', '.join(REMAT_POLICIES)} (HOROVOD_REMAT_POLICY / "
            f"remat_policy; docs/memory.md)")
    return policy


def resolve_remat_policy(policy: Optional[str] = None,
                         remat: Union[bool, str, None] = None) -> str:
    """Resolve the effective policy for one model / train step.

    Precedence: explicit ``policy`` > ``HOROVOD_REMAT_POLICY`` env >
    the legacy boolean ``remat`` (``True`` → ``"full"``, preserving
    what the flag always meant) > ``"none"``.  A string passed through
    the legacy ``remat`` slot counts as explicit — it is how
    ``DistributedTrainStep(remat="dots")`` spells a policy.
    """
    if isinstance(remat, str) and policy is None:
        policy = remat
        remat = None
    if policy is not None:
        return validate_policy(policy)
    env = os.environ.get(ENV_REMAT_POLICY)
    if env:
        return validate_policy(env.strip())
    return "full" if remat else "none"


def checkpoint_policy(policy: str):
    """The ``jax.checkpoint_policies`` value for a tier, or None when
    the tier needs no policy argument (``none`` — no checkpointing at
    all — and ``full`` — save nothing, jax.checkpoint's default).

    ``offload`` asks for matmul outputs in pinned host memory; where
    the backend lacks the pinned space (CPU XLA) the policy still
    constructs and XLA's host-memory lowering decides.
    """
    import jax

    validate_policy(policy)
    if policy in ("none", "full"):
        return None
    cp = jax.checkpoint_policies
    if policy == "offload":
        return cp.offload_dot_with_no_batch_dims("device", "pinned_host")
    return cp.dots_saveable


def remat_block(block_cls, policy: str):
    """Wrap a flax module class per policy — the drop-in replacement
    for the models' ``nn.remat(Block, static_argnums=())`` sites.
    ``none`` returns the class untouched."""
    import flax.linen as nn

    if validate_policy(policy) == "none":
        return block_cls
    cp = checkpoint_policy(policy)
    if cp is None:
        return nn.remat(block_cls, static_argnums=())
    return nn.remat(block_cls, static_argnums=(), policy=cp)


def remat_fn(fn, policy: str):
    """Wrap a plain function (the train step's ``loss_fn``) per
    policy — the drop-in replacement for ``jax.checkpoint(loss_fn) if
    remat else loss_fn``."""
    import jax

    if validate_policy(policy) == "none":
        return fn
    cp = checkpoint_policy(policy)
    if cp is None:
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=cp)
