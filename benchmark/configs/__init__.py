"""Configurations: ``<name>.json`` holds the sizes as run, ``<name>.py``
builds the training job's pieces from them and holds the plain
reference.  A module here provides::

    build(config, job, chips, seed) -> Built
    parity_case(config, job, chips, seed) -> ParityCase
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional


@dataclasses.dataclass(frozen=True)
class Built:
    """What the loop needs of a configuration."""

    init: Callable            # PRNG key -> variables, jittable
    loss_fn: Callable         # (params, batch) -> mean loss of the shard
    optimizer: object         # optax.GradientTransformation
    sample: Callable          # (numpy Generator, n) -> n host samples
    units_per_sample: int     # tokens a sequence, 1 an image
    flops_per_unit: float     # forward + backward, from shapes
    # text every Mosaic call of the compiled step must hold (its
    # per-chip operand); None where the configuration lists no kernel
    kernel_operand: Optional[str] = None
    # per-chip cost of the kernels a step runs, for their roofline
    # share: {kernel: {"flops": ..., "bytes": ...}}
    kernel_cost: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class ParityCase:
    """One step of the cell's layout at its widths, depth cut, against
    the plain reference on the same global batch."""

    init: Callable            # PRNG key -> variables of the cut model
    loss_fn: Callable         # the cell's loss on the cut model
    reference_loss: Callable  # (unboxed params, batch) -> fp32 loss, plain
    sample: Callable
    rows_per_chip: int
    loss_rtol: float
    grad_norm_rtol: float
    grad_rel_l2: float
    # rows the reference takes at a time where they are independent and
    # the whole batch would not fit one chip; None = the whole batch
    reference_chunk_rows: Optional[int] = None
