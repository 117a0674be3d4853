"""Declarative parallelism plans: one object, four consumers.

ROADMAP item 3 — the sharding-plan compiler.  A :class:`ShardingPlan`
names the parallel degree of every mesh axis (``dp``/``pp``/``fsdp``/
``ep``/``sp``/``tp``, the :data:`~horovod_tpu.parallel.mesh.AXIS_ORDER`
axes) plus the interleaved-1F1B virtual-stage count, parsed from the
``HOROVOD_PLAN`` grammar::

    HOROVOD_PLAN="dp=4,tp=2"          # 4-way data x 2-way tensor
    HOROVOD_PLAN="dp=2,pp=2,v=2"      # pipeline, 2 virtual stages/rank
    HOROVOD_PLAN="fsdp=8"             # pure ZeRO placement

The same plan object is the single source of truth for:

* ``optim/train_step.py`` — ``DistributedTrainStep(plan=...)`` builds
  the mesh from the plan and shards the batch over :attr:`data_axes`;
* ``ops/collectives.py`` — the ZeRO gradient exchange (RS → shard
  update → AG) runs only over the plan's data axes, never the model
  axes;
* ``checkpoint.py`` — sharded save/restore records the plan and
  reshards across *plan* changes (the data extent — including ``sp``,
  which shards activations, never parameters — may change; the
  pp/ep/tp factorization must not);
* ``parallel/mesh.py`` — :meth:`build_mesh` lays the plan out
  DCN-outer/ICI-inner per ``AXIS_ORDER``.

The module body is stdlib-only (JAX is imported lazily inside
:meth:`build_mesh`) so the plan grammar is usable from the analysis
layer's cost model and CLI without a device runtime.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple, Union

#: Mesh axes in DCN-outer → ICI-inner order.  Mirrors
#: ``parallel/mesh.AXIS_ORDER`` by value (that module imports JAX at
#: module scope; this one must not).
PLAN_AXES = ("dp", "pp", "fsdp", "ep", "sp", "tp")

#: Grammar keys: the six mesh axes plus ``v`` (interleaved-1F1B virtual
#: stages per pipeline rank, ``parallel/pipeline.interleaved_1f1b``).
PLAN_KEYS = PLAN_AXES + ("v",)

ENV_PLAN = "HOROVOD_PLAN"


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """One parallelism plan: per-axis extents + pipeline schedule.

    ``dp=None`` means "absorb whatever device count the other axes
    leave over" — resolved against a concrete device count by
    :meth:`resolve` (or implicitly by :meth:`build_mesh`).
    """

    dp: Optional[int] = None
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    virtual_stages: int = 1

    def __post_init__(self):
        for ax in PLAN_AXES:
            v = getattr(self, ax)
            if ax == "dp" and v is None:
                continue
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"plan axis {ax} must be a positive int, got {v!r}")
        if not isinstance(self.virtual_stages, int) \
                or self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be a positive int, got "
                f"{self.virtual_stages!r}")
        if self.virtual_stages > 1 and self.pp == 1:
            raise ValueError(
                f"v={self.virtual_stages} needs a pipeline axis: "
                f"virtual stages interleave over pp ranks, but pp=1")

    # -- construction -------------------------------------------------------

    @classmethod
    def from_string(cls, text: str) -> "ShardingPlan":
        """Parse the ``HOROVOD_PLAN`` grammar: comma-separated
        ``axis=extent`` pairs, axes from :data:`PLAN_KEYS`."""
        if not isinstance(text, str) or not text.strip():
            raise ValueError(
                "empty plan: expected comma-separated axis=extent "
                f"pairs over {', '.join(PLAN_KEYS)} "
                f"(e.g. \"dp=4,tp=2\")")
        seen: Dict[str, int] = {}
        for item in text.split(","):
            item = item.strip()
            if not item:
                continue
            key, sep, val = item.partition("=")
            key = key.strip()
            if not sep or key not in PLAN_KEYS:
                raise ValueError(
                    f"bad plan term {item!r}: expected axis=extent "
                    f"with axis in {', '.join(PLAN_KEYS)}")
            if key in seen:
                raise ValueError(f"duplicate plan axis {key!r} in "
                                 f"{text!r}")
            try:
                extent = int(val.strip())
            except ValueError:
                raise ValueError(
                    f"bad plan extent {val.strip()!r} for axis "
                    f"{key!r}: expected a positive int") from None
            seen[key] = extent
        kwargs = {("virtual_stages" if k == "v" else k): v
                  for k, v in seen.items()}
        return cls(**kwargs)

    @classmethod
    def from_env(cls) -> Optional["ShardingPlan"]:
        """The ``HOROVOD_PLAN`` plan, or None when the knob is unset."""
        text = os.environ.get(ENV_PLAN)
        return cls.from_string(text) if text else None

    def resolve(self, n_devices: int) -> "ShardingPlan":
        """Concrete plan for ``n_devices``: infer ``dp`` when unset,
        verify the factorization covers the device count exactly."""
        fixed = self.pp * self.fsdp * self.ep * self.sp * self.tp
        dp = self.dp
        if dp is None:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"cannot infer dp: {n_devices} devices not "
                    f"divisible by pp*fsdp*ep*sp*tp={fixed}")
            dp = n_devices // fixed
        if dp * fixed != n_devices:
            raise ValueError(
                f"plan {self.to_string(allow_unresolved=True)} covers "
                f"{dp * fixed} devices, not {n_devices}")
        return dataclasses.replace(self, dp=dp)

    # -- views --------------------------------------------------------------

    def to_string(self, allow_unresolved: bool = False) -> str:
        """Canonical plan string — the checkpoint /
        perf-gate-comparability representation.  ``dp`` is always
        emitted (so ``parse(to_string())`` round-trips exactly); other
        axes appear only at extent > 1, in :data:`PLAN_AXES` order."""
        if self.dp is None and not allow_unresolved:
            raise ValueError(
                "plan has dp=None (unresolved): call resolve(n_devices) "
                "before using the canonical string")
        parts = [f"dp={'?' if self.dp is None else self.dp}"]
        parts += [f"{ax}={getattr(self, ax)}" for ax in PLAN_AXES[1:]
                  if getattr(self, ax) > 1]
        if self.virtual_stages > 1:
            parts.append(f"v={self.virtual_stages}")
        return ",".join(parts)

    @property
    def total(self) -> int:
        """Device count the plan covers (requires a resolved ``dp``)."""
        if self.dp is None:
            raise ValueError("plan has dp=None: call resolve(n_devices)")
        return self.dp * self.pp * self.fsdp * self.ep * self.sp * self.tp

    @property
    def extents(self) -> Dict[str, int]:
        """Axis → extent in ``AXIS_ORDER`` (dp may be None)."""
        return {ax: getattr(self, ax) for ax in PLAN_AXES}

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Axes the gradient exchange (and batch sharding) rides: the
        replica axes dp/fsdp at extent > 1; plain ``("dp",)`` for a
        fully model-parallel plan (a size-1 exchange is free and the
        sharding specs stay uniform)."""
        axes = tuple(ax for ax in ("dp", "fsdp")
                     if (getattr(self, ax) or 1) > 1)
        return axes or ("dp",)

    @property
    def model_axes(self) -> Tuple[str, ...]:
        """Model-parallel axes at extent > 1 (pp/ep/sp/tp).

        ``sp`` is deliberately here even though it shards activations
        rather than parameters: a live job cannot change its sequence
        factorization (the ring's exchange schedule and the batch's
        token sharding are compiled in), so degrade transitions must
        keep the sp extent — only checkpoint resharding, where the
        job restarts anyway, treats sp as data extent
        (``checkpoint._check_plan_reshard``)."""
        return tuple(ax for ax in ("pp", "ep", "sp", "tp")
                     if getattr(self, ax) > 1)

    @property
    def model_extent(self) -> int:
        """Product of the model-parallel extents — the load-bearing
        factor a degrade transition must never change (checkpoint
        resharding only covers the data extent; docs/elastic.md)."""
        return self.pp * self.ep * self.sp * self.tp

    def degrade_candidates(self, n_devices: int
                           ) -> Tuple["ShardingPlan", ...]:
        """Feasible plans for ``n_devices`` surviving devices, keeping
        every model-parallel extent (and the pipeline schedule) fixed.

        Only the data extents move: ``dp' <= dp`` and ``fsdp' <= fsdp``
        with ``dp' * fsdp' * model_extent <= n_devices``.  Ordered
        best-first: largest surviving world wins, and among equal
        worlds the plan that shrinks ``dp`` (cheap — replicas are
        interchangeable) is preferred over one that shrinks ``fsdp``
        (re-slices every parameter shard).  Empty when even
        ``dp=1,fsdp=1`` does not fit — the model extent itself needs
        the lost capacity, so the caller must wait for it to return
        rather than degrade (docs/elastic.md wait-vs-shrink table).
        """
        if self.dp is None:
            raise ValueError(
                "plan has dp=None (unresolved): call resolve(n_devices) "
                "before enumerating degrade candidates")
        model = self.model_extent
        out = []
        for dp in range(1, self.dp + 1):
            for fsdp in range(1, self.fsdp + 1):
                if dp * fsdp * model <= int(n_devices):
                    out.append(dataclasses.replace(self, dp=dp,
                                                   fsdp=fsdp))
        out.sort(key=lambda p: (-p.total, self.fsdp - p.fsdp,
                                self.dp - p.dp))
        return tuple(out)

    # -- consumers ----------------------------------------------------------

    def build_mesh(self, devices=None):
        """Lay the plan out as a ``jax.sharding.Mesh`` via
        :func:`~horovod_tpu.parallel.mesh.make_parallel_mesh` —
        DCN-tolerant axes outermost, ICI-hungry axes innermost
        (``AXIS_ORDER``)."""
        from horovod_tpu.parallel.mesh import make_parallel_mesh

        return make_parallel_mesh(dp=self.dp, pp=self.pp, fsdp=self.fsdp,
                                  ep=self.ep, sp=self.sp, tp=self.tp,
                                  devices=devices)

    def matches_mesh(self, mesh) -> bool:
        """True when ``mesh`` carries exactly this plan's factorization
        (every plan axis present at the plan's extent)."""
        shape = dict(mesh.shape)
        return all(shape.get(ax) == getattr(self, ax)
                   for ax in PLAN_AXES)


def candidate_plans(n_devices: int,
                    axes: Tuple[str, ...] = ("dp", "fsdp", "tp")
                    ) -> Tuple["ShardingPlan", ...]:
    """Every exact factorization of ``n_devices`` over ``axes`` —
    the enumeration the HBM planner (``memory/planner.py``) and the
    budget-aware autotune walk.

    Deterministic order: dp-heaviest first (the pure-data plan is the
    presumptive speed winner; the budget search then works toward the
    sharded-parameter end), then lexicographic on the remaining
    extents.  ``axes`` must be plan axes; ``n_devices`` must be >= 1.
    """
    n = int(n_devices)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    for ax in axes:
        if ax not in PLAN_AXES:
            raise ValueError(
                f"unknown plan axis {ax!r}: expected one of "
                f"{', '.join(PLAN_AXES)}")
    out = []

    def factor(remaining: int, idx: int, extents: Dict[str, int]):
        if idx == len(axes) - 1:
            out.append(ShardingPlan(**{**extents, axes[idx]: remaining}))
            return
        d = 1
        while d <= remaining:
            if remaining % d == 0:
                factor(remaining // d, idx + 1,
                       {**extents, axes[idx]: d})
            d += 1
        return

    factor(n, 0, {})
    out.sort(key=lambda p: tuple(-getattr(p, ax) if ax == "dp"
                                 else getattr(p, ax) for ax in axes))
    return tuple(out)


PlanLike = Union[str, ShardingPlan]


def as_plan(plan: Optional[PlanLike]) -> Optional[ShardingPlan]:
    """Coerce a plan argument: a grammar string parses, a
    :class:`ShardingPlan` passes through, None stays None."""
    if plan is None or isinstance(plan, ShardingPlan):
        return plan
    if isinstance(plan, str):
        return ShardingPlan.from_string(plan)
    raise TypeError(
        f"plan must be a ShardingPlan or a HOROVOD_PLAN string, got "
        f"{type(plan).__name__}")
