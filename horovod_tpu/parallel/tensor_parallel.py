"""Megatron-style tensor parallelism, TPU-idiomatic.

Column-parallel Dense shards the output features over the ``tp`` axis
(no communication in forward); row-parallel Dense shards the input
features and finishes with one ``psum``.  The classic pairing — column
then row around a pointwise nonlinearity — costs exactly one psum per
MLP block and one per attention block.

Two API levels:

* **pjit/GSPMD path** (idiomatic default): flax modules whose kernels
  carry ``nn.with_partitioning`` metadata; under ``pjit`` over a mesh
  with a ``tp`` axis XLA inserts the collectives automatically, and the
  psum materializes as a fused reduce-scatter/all-gather where profitable.
* **shard_map path** (explicit control): plain functions taking local
  shards, for use inside ``shard_map`` where the collective placement is
  hand-written (the Horovod-style explicit mode).

Extension beyond the reference: SURVEY §2.3 — no model partitioning
exists anywhere in Horovod; TP here rides the same mesh machinery as
everything else.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from horovod_tpu.parallel.mesh import AXIS_TP
from horovod_tpu.utils import logging as hvd_logging

Dtype = Any
AxisSpec = Union[str, Sequence[str]]

# one-time flag: a partitioned module running with no constrainable
# ambient mesh silently computes fully replicated (see _constrain);
# warn on the first occurrence only — the condition repeats every
# trace and per-layer spam would bury the signal
_warned_no_ambient_mesh = False


# ---------------------------------------------------------------------------
# pjit/GSPMD modules — sharding by annotation
# ---------------------------------------------------------------------------

def _constrainable_axes() -> Optional[set]:
    """Mesh axis names a sharding constraint may legally name, or None
    when no mesh is ambient.

    The ambient mesh is the one ``jax.set_mesh(mesh)`` installs —
    ``DistributedTrainStep`` enters it around its pjit step — and the
    one ``shard_map`` binds, where every axis is Manual: constraints
    are illegal there (values are already per-shard), so Manual axes
    are excluded."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return None
    return set(am.axis_names) - set(am.manual_axes)


def param_value(param):
    """The raw array of a param fetched with ``unbox=False``.

    flax's own unboxing applies the boxed partition spec as a sharding
    constraint whenever a mesh is ambient — including inside
    ``shard_map``, where the axes are Manual (or absent: the runtime
    ``(dcn, ici)`` mesh has no ``tp``) and the constraint raises.  The
    modules below therefore unbox without it and apply the constraint
    themselves (:func:`_constrain`), only where it is legal — so the
    params ``model.init`` returns run under every step mode as they
    are."""
    if isinstance(param, nn.Partitioned):
        return param.unbox(apply_constraint=False)
    return param


def _constrain(x, *spec):
    """Pin a partition spec on a value inside the module.

    flax's ``nn.with_partitioning`` only *boxes* metadata onto the
    param tree — nothing applies it during ``apply``, so without this
    constraint a jit over a tp mesh is free to replicate the kernels
    and the "tensor-parallel" module silently computes fully
    replicated (measured: the compiled module had zero collectives).
    The constraint is skipped ONLY when no ambient mesh exists, the
    mesh lacks the requested axis, or the axis is Manual (shard_map
    body — constraining there is illegal); real sharding errors on a
    live mesh — e.g. features not divisible by the axis size — must
    propagate, not silently replicate."""
    mesh_axes = _constrainable_axes()
    wanted = {s for s in spec if isinstance(s, str)}
    if mesh_axes is None:
        global _warned_no_ambient_mesh
        if not _warned_no_ambient_mesh:
            _warned_no_ambient_mesh = True
            hvd_logging.warning(
                "tensor-parallel module executed with no ambient mesh: "
                "kernel sharding constraints for axes %s were skipped, "
                "so the module computes fully REPLICATED (no tensor "
                "parallelism). Run it under `jax.set_mesh(mesh)` over "
                "a mesh carrying those axes (DistributedTrainStep's "
                "pjit mode does), or inside shard_map with hand-placed "
                "collectives.", sorted(wanted))
        return x
    if not wanted <= mesh_axes:
        return x
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))


class ColumnParallelDense(nn.Module):
    """Dense with output features sharded over ``axis`` (kernel partition
    spec ``(None, axis)``).  Forward needs no collective; pair with
    :class:`RowParallelDense` to close the block with one psum.

    **Ambient-mesh requirement**: the sharding constraints that make
    the module actually tensor-parallel only apply when it executes
    under an ambient mesh carrying ``axis`` — ``jax.set_mesh(mesh)``
    around the jitted ``apply`` (see
    :func:`horovod_tpu.parallel.mesh.make_parallel_mesh`;
    ``DistributedTrainStep`` enters its own mesh in pjit mode).  With no
    ambient mesh the module still computes correct values but fully
    replicated, and a one-time warning is logged.  Inside ``shard_map``
    the axes are Manual and constraints are skipped by design — use the
    explicit :func:`column_parallel_dense` there."""

    features: int
    axis: str = AXIS_TP
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        kernel = param_value(self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (None, self.axis)),
            (x.shape[-1], self.features), unbox=False))
        kernel = _constrain(jnp.asarray(kernel, self.dtype),
                            None, self.axis)
        y = jnp.dot(x.astype(self.dtype), kernel)
        if self.use_bias:
            bias = param_value(self.param(
                "bias", nn.with_partitioning(self.bias_init, (self.axis,)),
                (self.features,), unbox=False))
            y = y + _constrain(jnp.asarray(bias, self.dtype), self.axis)
        return y


class RowParallelDense(nn.Module):
    """Dense with input features sharded over ``axis`` (kernel partition
    spec ``(axis, None)``); the partial products are summed by XLA's
    inserted collective under pjit.  Bias is added after the reduction.

    Same **ambient-mesh requirement** as :class:`ColumnParallelDense`:
    without a ``jax.set_mesh`` context carrying ``axis``
    the constraints are skipped (one-time warning) and the module runs
    replicated; inside ``shard_map`` use the explicit
    :func:`row_parallel_dense` instead."""

    features: int
    axis: str = AXIS_TP
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        kernel = param_value(self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (self.axis, None)),
            (x.shape[-1], self.features), unbox=False))
        kernel = _constrain(jnp.asarray(kernel, self.dtype),
                            self.axis, None)
        y = jnp.dot(x.astype(self.dtype), kernel)
        if self.use_bias:
            bias = param_value(self.param(
                "bias", nn.with_partitioning(self.bias_init, (None,)),
                (self.features,), unbox=False))
            y = y + jnp.asarray(bias, self.dtype)
        return y


# ---------------------------------------------------------------------------
# shard_map functions — explicit local shards + hand-placed psum
# ---------------------------------------------------------------------------

def column_parallel_dense(x: jax.Array, kernel: jax.Array,
                          bias: Optional[jax.Array] = None) -> jax.Array:
    """Local shard of a column-parallel matmul: ``kernel`` is this shard's
    ``(in, out_local)`` slice; output stays feature-sharded."""
    y = jnp.dot(x, kernel)
    return y + bias if bias is not None else y


def row_parallel_dense(x: jax.Array, kernel: jax.Array,
                       bias: Optional[jax.Array] = None,
                       axis: AxisSpec = AXIS_TP) -> jax.Array:
    """Local shard of a row-parallel matmul closed by a psum: ``x`` is
    feature-sharded ``(…, in_local)``, ``kernel`` the matching
    ``(in_local, out)`` slice; output is replicated over ``axis``."""
    y = lax.psum(jnp.dot(x, kernel), axis)
    return y + bias if bias is not None else y


# ---------------------------------------------------------------------------
# tile-fused sequence-parallel boundary layers (docs/fused_kernels.md)
# ---------------------------------------------------------------------------
#
# The classic column→row pairing above closes each block with one
# boundary-wide psum — a serial collective no compute hides.  The
# Megatron-SP restructuring replaces it with a reduce-scatter over
# tokens at the row boundary and an all-gather over tokens at the next
# column boundary, and the tile-fused kernels
# (ops/pallas_kernels.matmul_reducescatter / allgather_matmul) overlap
# each boundary's wire with the matmul itself — tile k's exchange rides
# under tile k+1's MXU compute, so no full-width serial collective
# remains at either boundary (the HLO guard pins ring permutes, zero
# all-reduces).  Token layout contract: rows are RANK-MAJOR flattened
# tokens — the gather concatenates rank chunks along dim 0 and the
# scatter hands rank r rows [r·m/world, (r+1)·m/world); callers holding
# (batch, seq, d) natural layout transpose chunks accordingly
# (models/transformer.fused_tp_apply shows the idiom).

def column_parallel_dense_ag(x: jax.Array, kernel: jax.Array,
                             bias: Optional[jax.Array] = None,
                             axis: str = AXIS_TP,
                             fused: bool = True,
                             interpret: bool = False) -> jax.Array:
    """Column-parallel Dense over a token-sharded input: gathers the
    ``(m_local, in)`` rank-major row shard across ``axis`` *inside* the
    matmul (:func:`~horovod_tpu.ops.pallas_kernels.allgather_matmul`)
    and applies this rank's ``(in, out_local)`` column shard; returns
    the full-token ``(world·m_local, out_local)`` activation."""
    from horovod_tpu.ops.pallas_kernels import allgather_matmul

    y = allgather_matmul(x, kernel, axis, fused=fused,
                         interpret=interpret)
    return y + bias if bias is not None else y


def row_parallel_dense_rs(x: jax.Array, kernel: jax.Array,
                          bias: Optional[jax.Array] = None,
                          axis: str = AXIS_TP,
                          fused: bool = True,
                          interpret: bool = False) -> jax.Array:
    """Row-parallel Dense closed by a tile-fused reduce-scatter over
    tokens: ``x`` is the full-token feature-sharded ``(m, in_local)``
    activation (rows rank-major), ``kernel`` this rank's
    ``(in_local, out)`` row slice; returns this rank's reduced
    ``(m/world, out)`` token block
    (:func:`~horovod_tpu.ops.pallas_kernels.matmul_reducescatter`).
    The bias (full ``(out,)``) is added after the reduction, on the
    owned token block only."""
    from horovod_tpu.ops.pallas_kernels import matmul_reducescatter

    y = matmul_reducescatter(x, kernel, axis, fused=fused,
                             interpret=interpret)
    return y + bias if bias is not None else y
