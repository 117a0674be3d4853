"""Multi-axis parallelism mesh factory.

The runtime's (dcn, ici) mesh (``runtime/topology.py``) models the
reference's CROSS×LOCAL communicator split (``common.h:113-117``) and is
all data parallelism needs.  Model parallelism needs finer axes.  This
factory builds an N-D ``jax.sharding.Mesh`` whose axis order encodes the
hardware hierarchy: the outermost axes change slowest across the device
list (cheap, infrequent collectives — dp, pp ride DCN), the innermost
axes map to ICI neighbors (tp does per-layer collectives and needs the
fastest links) — the "How to Scale Your Model" mesh recipe.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DP = "dp"       # data parallel: gradient psum once per step
AXIS_PP = "pp"       # pipeline stages: p2p activations between neighbors
AXIS_FSDP = "fsdp"   # fully-sharded dp: param all-gather + grad reduce-scatter
AXIS_EP = "ep"       # expert parallel: all_to_all token dispatch
AXIS_SP = "sp"       # sequence/context parallel: ring ppermute / all_to_all
AXIS_TP = "tp"       # tensor parallel: psum per transformer block

# outermost (slowest-varying, DCN-tolerant) → innermost (ICI neighbors)
AXIS_ORDER = (AXIS_DP, AXIS_PP, AXIS_FSDP, AXIS_EP, AXIS_SP, AXIS_TP)


def make_parallel_mesh(dp: Optional[int] = None, pp: int = 1, fsdp: int = 1,
                       ep: int = 1, sp: int = 1, tp: int = 1,
                       devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a mesh with the requested parallel degrees.

    ``dp=None`` absorbs whatever device count the other axes leave over.
    Axes of extent 1 are kept in the mesh (size-1 collectives are free and
    sharding specs stay uniform across configurations).

    ::

        mesh = make_parallel_mesh(tp=4, sp=2)      # dp fills the rest
        with jax.set_mesh(mesh):
            ...
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    fixed = pp * fsdp * ep * sp * tp
    if dp is None:
        if n % fixed != 0:
            raise ValueError(
                f"cannot infer dp: {n} devices not divisible by "
                f"pp*fsdp*ep*sp*tp={fixed}")
        dp = n // fixed
    total = dp * fixed
    if total != n:
        raise ValueError(
            f"mesh {dp}x{pp}x{fsdp}x{ep}x{sp}x{tp}={total} does not cover "
            f"{n} devices")
    shape = dict(zip(AXIS_ORDER, (dp, pp, fsdp, ep, sp, tp)))
    dev_array = np.asarray(devices).reshape(tuple(shape.values()))
    return Mesh(dev_array, AXIS_ORDER)
