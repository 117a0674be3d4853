"""Eager host-level collectives with Horovod's API shape.

The reference's user surface (``horovod/torch/mpi_ops.py``,
``tensorflow/mpi_ops.py``) is *eager per-tensor*: each call enqueues one
named tensor into the C++ background loop which negotiates, fuses and
executes (``operations.cc:840-1068``).  The TPU replacement keeps the
call shape — ``allreduce``/``allreduce_async``/``synchronize``/``poll``,
named tensors, pre/postscale, Average/Sum/Adasum — but the machinery
underneath is re-rooted:

* *world* = JAX processes (one multi-chip host process each).  Tensors are
  lifted into a global array sharded over a one-device-per-process "proc"
  mesh and reduced by a jitted SPMD computation; XLA runs the collective
  over ICI/DCN.  With a single process the ops reduce to local scaling.
* *async* = JAX's dispatch-and-return execution: a handle wraps the
  not-yet-materialized output array — the role the reference's handle
  manager plays for torch (``torch/handle_manager.{h,cc}``,
  ``mpi_ops.py:590-627 poll/synchronize``).
* *fusion* = the :class:`~horovod_tpu.ops.bucketing.Bucketer`: async
  submissions accumulate and flush as one grouped collective per dtype
  (see ``bucketing.py`` for the fusion-buffer mapping).

In-jit training code should use ``horovod_tpu.ops.collectives`` directly;
this module is for host-side orchestration (metric averaging, parameter
broadcast, object exchange) and API familiarity.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops.collectives import Adasum, Average, ReduceOp, Sum
from horovod_tpu.ops import adasum as adasum_mod
from horovod_tpu.runtime import state
from horovod_tpu.utils import logging as hvd_logging
from horovod_tpu.utils import timeline as tl

# Reference error text: common.h:163 DUPLICATE_NAME_ERROR
_DUPLICATE_NAME_ERROR = (
    "Requested to collect a tensor with the same name as another tensor "
    "that is currently being processed. If you want to request another "
    "tensor, use a different tensor name.")


# Collective failures raise HorovodInternalError; elastic mode catches it
# and restores state (reference ``common/exceptions.py:18``).
from horovod_tpu.exceptions import HorovodInternalError  # noqa: E402


_lock = threading.Lock()
_in_flight: dict = {}
_name_counter = 0
_proc_mesh: Optional[Mesh] = None
# Global negotiation-cycle counter.  Every eager collective performs exactly
# one `_negotiate` round, and negotiation rounds are themselves collectives,
# so the counter advances in lock-step on every process — it is the global
# "tick" the reference's background loop provides implicitly.  join() records
# the tick at which each process joined; the max identifies the exact last
# joiner (the reference controller knows this from request arrival order).
_cycle = 0


def _next_name(prefix: str) -> str:
    global _name_counter
    with _lock:
        _name_counter += 1
        return f"{prefix}.noname.{_name_counter}"


def process_mesh() -> Mesh:
    """One-device-per-process mesh: the eager ops' communicator.

    The analogue of the reference's GLOBAL communicator over worker
    processes (``common.h:113``)."""
    global _proc_mesh
    if _proc_mesh is None:
        by_proc: dict = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        devs = [by_proc[p] for p in sorted(by_proc)]
        _proc_mesh = Mesh(np.array(devs), ("proc",))
    return _proc_mesh


def _reset_mesh_cache() -> None:
    """Drop every cache that captures the proc mesh — called on elastic
    world resize; stale jitted fns would pin the old world's devices."""
    global _proc_mesh, _cycle
    _proc_mesh = None
    _cycle = 0
    _validated_signatures.clear()
    _desc_cache.clear()
    _reducer_cache.clear()
    _motion_cache.clear()
    from horovod_tpu.ops import op_manager

    # HOST-plane KV keys carry a per-call counter that must restart in
    # lock-step with the new world (a fresh process starts at zero)
    op_manager.reset_host_plane()
    # timeline-aggregation upload keys carry the same kind of SPMD-
    # ordered counter: surviving processes must restart it so it stays
    # aligned with freshly-joined workers (which start at zero)
    from horovod_tpu.utils import timeline as _tl

    _tl._aggregate_seq = 0


_validated_signatures: set = set()
# digest → descriptor, populated at validation time on every process so a
# later join() can replay previously-seen collectives without re-paying
# the payload exchange (see _negotiate)
_desc_cache: dict = {}

# Reference join-incompatibility error texts (``controller.cc:487-497,569``).
_JOIN_UNSUPPORTED = {
    "allgather": "Allgather is not supported with Join at this time. "
                 "Specify sparse_as_dense=True if using DistributedOptimizer",
    "alltoall": "Alltoall is not supported with Join at this time.",
    "broadcast": "Broadcast is not supported with Join at this time.",
}
# Allreduce sub-ops a joined rank can zero-fill.  Zeros are the identity for
# SUM; AVERAGE is sum + postscale 1/world_size in the reference
# (``operations.cc:851-854``) so joined zeros lower the mean exactly as they
# do there; Adasum's pairwise combine is zero-safe (coefficients fall back to
# 1 on zero norms, ``adasum.py:_combine``).  MIN/MAX/PRODUCT have no zero
# identity — mirroring the reference's op whitelist they error under join.
_JOIN_ZERO_OPS = (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.ADASUM)


def _join_bad_op_error(op_name: str) -> str:
    """One shared message for active and joined ranks — the error-cycle
    contract is that every rank raises the identical error."""
    return (f"Allreduce op {op_name} is not supported with Join: zero "
            f"contributions from joined ranks have no identity under "
            f"{op_name}.")


class _Negotiation:
    """Outcome of one controller cycle."""

    __slots__ = ("all_joined", "last_rank", "joined", "desc")

    def __init__(self, all_joined, last_rank, joined, desc):
        self.all_joined = all_joined
        self.last_rank = last_rank
        self.joined = joined      # process indices currently in join()
        self.desc = desc          # agreed collective descriptor (dict)


def _negotiate(desc: Optional[dict], join_cycle: int = -1) -> _Negotiation:
    """One negotiation cycle — controller-lite with Join support.

    The reference's coordinator gathers per-rank Requests each cycle,
    validates dtype/shape/op agreement, counts JOIN requests, and turns
    mismatches into descriptive error responses delivered on every rank
    (``ComputeResponseList`` ``controller.cc:63``, ``ConstructResponse``
    ``controller.cc:380``, JOIN counting ``controller.cc:220-223``).  The
    SPMD replacement is a fixed-shape host-metadata allgather per cycle:

      ``[is_join, join_cycle, payload_len, sha256(payload) as 4 words]``

    * all processes joined → everyone leaves join(); the exact last rank
      is the one with the highest join tick (ties → highest rank), the
      same answer the reference reads off request arrival order.
    * a mix of joined and active processes → one extra variable-size
      payload exchange so joined ranks learn the collective's descriptor
      and can contribute zero tensors (``tensor_queue.cc``
      ``GetTensorEntriesFromResponse`` synthesizes zero entries;
      ``controller.cc:263-274``).  Only allreduce-family ops support
      this; others raise the reference's error text
      (``controller.cc:487-497,569``).
    * digest mismatch among active processes → HorovodInternalError on
      all of them, naming the divergent processes.

    The fixed head exchange runs unconditionally — a joined process
    blocked in its service loop must observe every cycle, so there is no
    skip-the-wire fast path (the reference pays the same: its cache-hit
    path still does 2 bitwise-AND + 1 bitwise-OR cross-rank syncs,
    ``controller.cc:133-164``).

    On cache invalidation (deliberate design difference): the reference
    stall inspector invalidates cached responses of stalled tensors so
    they renegotiate (``stall_inspector.h:73-81`` +
    ``response_cache.cc``).  Here the caches are *cross-process wire
    state* — ``need_payload`` is computed from cache membership on every
    process independently, which is only sound because all processes
    mutate the caches at identical cycles.  A stall-triggered,
    one-sided invalidation would desynchronize that decision and
    misalign the payload exchange (deadlock), so stalls are surfaced
    through the stall inspector's warnings/shutdown and the timeline's
    NEGOTIATE events instead of cache eviction; the only evictions are
    the deterministic size-bound clear below and the world-reset clear
    in ``_reset_mesh_cache``.
    """
    global _cycle
    mesh = process_mesh()
    nproc = mesh.devices.size
    _cycle += 1
    import hashlib
    import pickle

    # Bounded caches.  The length is identical on every process at any
    # aligned cycle (all processes run identical collective sequences),
    # so the clear fires at the same cycle everywhere — a prerequisite
    # for using cache membership in wire-shape decisions below.
    if len(_validated_signatures) > 8192:
        _validated_signatures.clear()
        _desc_cache.clear()

    if desc is None:
        payload = b""
        head = np.zeros((7,), np.int64)
        head[0], head[1] = 1, join_cycle
    else:
        payload = pickle.dumps(desc, protocol=4)
        digest = hashlib.sha256(payload).digest()
        head = np.empty((7,), np.int64)
        head[0], head[1], head[2] = 0, -1, len(payload)
        head[3:] = np.frombuffer(digest, np.int64)[:4]

    heads = _allgather_host_metadata(head)  # (nproc, 7)
    joined = [p for p in range(nproc) if heads[p, 0]]
    active = [p for p in range(nproc) if not heads[p, 0]]

    if not active:
        ticks = heads[:, 1]
        last = max(range(nproc), key=lambda p: (int(ticks[p]), p))
        return _Negotiation(True, int(last), joined, None)

    ref = active[0]
    ref_digest = heads[ref, 3:].tobytes()
    seen = ref_digest in _validated_signatures

    # Payload exchange only when a joined rank may be missing the
    # descriptor.  Every process — active or joined — records
    # digest→descriptor at validation time, and all processes execute
    # identical collective sequences, so the caches are identical and
    # the skip decision is computable everywhere from shared data (no
    # collective misalignment).  A previously-validated descriptor thus
    # costs only the fixed head exchange even mid-join.
    need_payload = bool(joined) and not seen
    shared_desc = desc
    if need_payload:
        maxlen = int(heads[:, 2].max())
        wire_len = ((maxlen + 7) // 8) * 8
        raw = np.zeros((wire_len,), np.uint8)
        raw[:len(payload)] = np.frombuffer(payload, np.uint8)
        allp = _allgather_host_metadata(raw.view(np.int64))
        if desc is None:
            shared_desc = pickle.loads(
                allp[ref].tobytes()[:int(heads[ref, 2])])
    elif desc is None:
        shared_desc = _desc_cache.get(ref_digest)
        if shared_desc is None:  # pragma: no cover - invariant violation
            raise HorovodInternalError(
                "internal: joined process has no cached descriptor for a "
                "previously-validated collective — negotiation caches "
                "desynchronized across processes.")

    bad = [p for p in active
           if not (heads[p, 2:] == heads[ref, 2:]).all()]
    if desc is None:
        # Joined rank: when active ranks disagree they all raise and
        # stop issuing collectives — re-entering the head exchange would
        # block forever.  The mismatch is computable right here from the
        # gathered heads (the same data the active ranks used), so raise
        # the error on this rank too: the reference controller delivers
        # the error response on every rank (``controller.cc:380``).
        if bad:
            raise HorovodInternalError(
                f"Mismatched collective across processes while this "
                f"process (rank {jax.process_index()}) was in join(): "
                f"process(es) {bad} disagree with process {ref} on the "
                f"name/dtype/shape/op for this collective slot. All "
                f"processes must issue identical collectives in "
                f"identical order.")
        if not seen:
            _validated_signatures.add(ref_digest)
            _desc_cache[ref_digest] = shared_desc
        return _Negotiation(False, -1, joined, shared_desc)
    if bad:
        raise HorovodInternalError(
            f"Mismatched {desc.get('kind')} across processes: process "
            f"{jax.process_index()} submitted [{desc.get('sig')}] but "
            f"process(es) {bad} disagree with process {ref} on the "
            f"name/dtype/shape/op for this collective slot. All processes "
            f"must issue identical collectives in identical order.")

    if not seen:
        _validated_signatures.add(ref_digest)
        _desc_cache[ref_digest] = desc
    st = state.global_state() if state.is_initialized() else None
    if st:
        st.cache_stats["hits" if seen else "misses"] += 1
        # negotiation-phase observability: the reference timeline records
        # NEGOTIATE_* phases per tensor (controller.cc:845-857); here one
        # instant per cycle carrying the cache outcome and join count
        if st.timeline is not None:
            st.timeline.instant(
                tl.NEGOTIATE, {"kind": desc.get("kind"),
                               "cache": "hit" if seen else "miss",
                               "cycle": _cycle, "joined": len(joined)})

    if joined:
        kind = desc.get("kind")
        if kind in _JOIN_UNSUPPORTED:
            raise HorovodInternalError(_JOIN_UNSUPPORTED[kind])
        if kind == "allreduce" and \
                ReduceOp[desc["op"]] not in _JOIN_ZERO_OPS:
            raise HorovodInternalError(_join_bad_op_error(desc["op"]))
    return _Negotiation(False, -1, joined, shared_desc)


def _localize(tensor) -> jax.Array:
    """Intake normalization: a previous eager collective returns an array
    replicated over the *global* proc mesh; feeding it straight into the
    next collective (the natural training loop: ``w -= lr *
    allreduce(grad(w))``) must work.  Such arrays span non-addressable
    devices, which ``device_put``/``np.asarray`` reject — take the local
    replica.  Only *replicated* arrays get this shortcut: truncating a
    genuinely sharded array to its shard 0 would silently reduce a
    fragment."""
    if isinstance(tensor, jax.Array) and \
            len(tensor.sharding.device_set) > 1:
        if tensor.sharding.is_fully_replicated:
            return jnp.asarray(tensor.addressable_data(0))
        if not tensor.is_fully_addressable:
            raise HorovodInternalError(
                "eager collectives take per-process local tensors (or "
                "replicated results of previous eager collectives); got "
                "a globally-sharded array — gather it first, or use the "
                "in-jit horovod_tpu.ops.collectives inside your step.")
        # fully-addressable sharded input: jnp.asarray gathers it
    return jnp.asarray(tensor)


def _lift(tensor: jax.Array) -> jax.Array:
    """Lift this process's tensor into a (nproc, ...) global array sharded
    one-row-per-process."""
    mesh = process_mesh()
    nproc = mesh.devices.size
    local = jnp.asarray(tensor)[None]
    sharding = NamedSharding(mesh, P("proc", *([None] * tensor.ndim)))
    if nproc == 1:
        return jax.device_put(local, sharding)
    my_dev = mesh.devices.flat[jax.process_index()]
    return jax.make_array_from_single_device_arrays(
        (nproc,) + tuple(tensor.shape), sharding,
        [jax.device_put(local, my_dev)])


def _replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


_reducer_cache: dict = {}


def _reduce_global(garr, op: ReduceOp, prescale, postscale, nproc: int,
                   segments: tuple = ()):
    """jit-compiled reduction over the proc mesh with replicated output;
    compiled once per (op, scales, segments) — jax.jit memoizes per
    shape/dtype (the response-cache analogue, ``response_cache.{h,cc}``).

    ``segments`` (tuple of flat lengths) marks per-tensor boundaries inside
    a fused buffer; only Adasum consumes it — its dot/norm coefficients are
    per layer, never over the whole fusion buffer (reference
    ``ComputeDotAndNormSqrds`` walks the tensor table per entry).
    """
    mesh = process_mesh()
    key = (id(mesh), op, prescale, postscale, nproc, segments)
    fn = _reducer_cache.get(key)
    st = state.global_state() if state.is_initialized() else None
    if fn is None:
        fn = jax.jit(
            partial(_reduce_impl, op=op, prescale=prescale,
                    postscale=postscale, nproc=nproc, segments=segments),
            out_shardings=_replicated(mesh))
        _reducer_cache[key] = fn
        if st:
            st.cache_stats["misses"] += 1
    elif st:
        st.cache_stats["hits"] += 1
    return fn(garr)


def _adasum_tree(rows: list, xp=jnp):
    """Pairwise Adasum reduction tree; one combine formula for both data
    planes (``adasum_mod._combine`` is xp-generic)."""
    vals = list(rows)
    while len(vals) > 1:
        nxt = [adasum_mod._combine(vals[i], vals[i + 1], xp=xp)
               for i in range(0, len(vals) - 1, 2)]
        if len(vals) % 2:
            nxt.append(vals[-1])
        vals = nxt
    return vals[0]


def _reduce_stacked(x, *, op: ReduceOp, prescale, postscale, nproc: int,
                    segments: tuple = (), xp=jnp):
    """Reduce a stacked ``(nproc, n)`` array of per-process rows — the
    single source of truth for op/scale numerics, shared by the XLA
    plane (``xp=jnp``, under jit) and the HOST plane (``xp=np``) so the
    two planes cannot drift."""
    # 0.0 is a legal scale factor (reference accepts arbitrary doubles), so
    # test against None, not truthiness
    scaled = prescale is not None or postscale is not None
    dtype = x.dtype
    if scaled and dtype.name in ("float16", "bfloat16"):
        x = x.astype(xp.float32)
    if prescale is not None:
        x = x * prescale
    if op == ReduceOp.ADASUM:
        if segments:
            outs, off = [], 0
            for seg in segments:
                rows = [x[i, off:off + seg] for i in range(nproc)]
                outs.append(_adasum_tree(rows, xp=xp))
                off += seg
            y = xp.concatenate(outs) if len(outs) > 1 else outs[0]
        else:
            y = _adasum_tree([x[i] for i in range(nproc)], xp=xp)
    elif op == ReduceOp.AVERAGE:
        y = xp.mean(x, axis=0)
    elif op == ReduceOp.SUM:
        y = xp.sum(x, axis=0)
    elif op == ReduceOp.MIN:
        y = xp.min(x, axis=0)
    elif op == ReduceOp.MAX:
        y = xp.max(x, axis=0)
    elif op == ReduceOp.PRODUCT:
        y = xp.prod(x, axis=0)
    else:
        raise ValueError(f"unsupported op {op}")
    if postscale is not None:
        y = y * postscale
    return y.astype(dtype)


def _reduce_impl(garr, *, op: ReduceOp, prescale, postscale, nproc: int,
                 segments: tuple = ()):
    return _reduce_stacked(garr, op=op, prescale=prescale,
                           postscale=postscale, nproc=nproc,
                           segments=segments, xp=jnp)


class Handle:
    """Async collective handle (reference torch handle model:
    ``allreduce_async_`` returns an int handle resolved by
    ``synchronize()``, ``torch/mpi_ops.py:606``)."""

    def __init__(self, name: str):
        self.name = name
        self._result = None
        self._done = threading.Event()
        self._error: Optional[Exception] = None

    def _fulfill(self, result) -> None:
        self._result = result
        self._done.set()
        st = state.global_state() if state.is_initialized() else None
        if st and st.stall_inspector:
            st.stall_inspector.record_complete(self.name)
        with _lock:
            _in_flight.pop(self.name, None)

    def _fail(self, err: Exception) -> None:
        self._error = err
        self._done.set()
        st = state.global_state() if state.is_initialized() else None
        if st and st.stall_inspector:
            st.stall_inspector.record_complete(self.name)
        with _lock:
            _in_flight.pop(self.name, None)


def _register(name: str, handle: Handle) -> None:
    with _lock:
        if name in _in_flight:
            raise HorovodInternalError(_DUPLICATE_NAME_ERROR + f" (name={name})")
        _in_flight[name] = handle
    st = state.global_state() if state.is_initialized() else None
    if st and st.stall_inspector:
        st.stall_inspector.record_dispatch(name)


def _timeline():
    st = state.global_state() if state.is_initialized() else None
    return st.timeline if st else None


# ---------------------------------------------------------------------------
# public eager ops
# ---------------------------------------------------------------------------

def allreduce(tensor, average: Optional[bool] = None, name: Optional[str] = None,
              op: Optional[ReduceOp] = None,
              prescale_factor: Optional[float] = None,
              postscale_factor: Optional[float] = None,
              compression=None):
    """Synchronous allreduce across worker processes (reference
    ``horovod/torch/mpi_ops.py:allreduce`` / ``tensorflow/__init__.py:52``)."""
    h = allreduce_async(tensor, average=average, name=name, op=op,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        compression=compression)
    return synchronize(h)


def allreduce_async(tensor, average: Optional[bool] = None,
                    name: Optional[str] = None, op: Optional[ReduceOp] = None,
                    prescale_factor: Optional[float] = None,
                    postscale_factor: Optional[float] = None,
                    compression=None) -> Handle:
    from horovod_tpu.ops.bucketing import global_bucketer

    if op is None:
        op = Average if (average is None or average) else Sum
    if compression is not None and not hasattr(compression, "compress"):
        # validate before the handle registers / spans open: a rejected
        # call must leave no in-flight handle, stall record, or span
        raise ValueError(
            "Compression.int8 is an in-jit wire reduction (shard_map "
            "mode); the eager plane exchanges whole tensors — use "
            "Compression.fp16/bf16 here")
    name = name or _next_name("allreduce")
    handle = Handle(name)
    _register(name, handle)
    # per-tensor negotiation phase (reference timeline.h:77-131: every
    # tensor walks NEGOTIATING → TOP_LEVEL; NegotiateStart fires when the
    # request enters the system).  The span opens here at enqueue and
    # closes in _dispatch_group once the cycle's negotiation agrees.
    tlobj = _timeline()
    if tlobj is not None:
        tlobj.start_activity(name, tl.NEGOTIATE)
    # remember which timeline (if any) holds the open NEGOTIATE span so the
    # flush-time close pairs B/E on the same file even if the timeline is
    # started/stopped between enqueue and flush
    handle._tl_neg = tlobj
    tensor = _localize(tensor)
    ctx = None
    if compression is not None:
        tensor, ctx = compression.compress(tensor)
    handle._decompress = (compression, ctx)
    global_bucketer().add(name, tensor, op, prescale_factor,
                          postscale_factor, handle)
    return handle


def _dispatch_group(entries) -> None:
    """Flush callback from the bucketer: one fused collective per flush.

    This is ``PerformOperation`` (``operations.cc:253``) re-rooted: instead
    of memcpy-into-fusion-buffer + NCCL, we concatenate flat tensors and
    run one jitted reduction over the proc mesh.
    """
    nproc = process_mesh().devices.size
    tlobj = _timeline()

    def _end_negotiate():
        # close each entry's NEGOTIATE span on the timeline it was opened
        # on at enqueue (None if the timeline was off then)
        for e in entries:
            t = getattr(e.handle, "_tl_neg", None)
            if t is not None:
                t.end_activity(e.name)
                e.handle._tl_neg = None

    xla_open = False
    try:
        e0 = entries[0]
        segments = tuple(int(e.tensor.size) for e in entries) \
            if e0.op == ReduceOp.ADASUM else ()
        total = int(sum(e.tensor.size for e in entries))
        if nproc > 1:
            # Descriptor carries exactly what a joined rank needs to
            # issue the identical jitted reduction with zero inputs:
            # flat length, dtype, op, scales, segments.  ``sig`` is the
            # human-readable slot signature for mismatch errors.
            _negotiate({
                "kind": "allreduce",
                "n": total,
                "dtype": str(e0.tensor.dtype),
                "op": e0.op.name,
                "pre": e0.prescale,
                "post": e0.postscale,
                "segments": segments,
                "sig": "; ".join(
                    f"{e.name}:{e.tensor.dtype}:{tuple(e.tensor.shape)}:"
                    f"{e.op.name}:{e.prescale}:{e.postscale}"
                    for e in entries),
            })
        # negotiation agreed: close each tensor's NEGOTIATE span and open
        # its dispatch span (reference NEGOTIATING → TOP_LEVEL → ACTIVITY
        # transition, timeline.h:77-131 + controller.cc:845-857)
        _end_negotiate()
        if tlobj is not None:
            for e in entries:
                tlobj.start_activity(e.name, tl.XLA_ALLREDUCE)
            xla_open = True
        # Always reduce the flattened concatenation — a single entry
        # too — so the compiled program depends only on (n, dtype, op,
        # scales, segments) and joined ranks can replay it exactly.
        from horovod_tpu.ops import op_manager

        flat = jnp.concatenate(
            [jnp.ravel(e.tensor) for e in entries]) \
            if len(entries) > 1 else jnp.ravel(e0.tensor)
        red = op_manager.active_op().reduce_rows(
            flat, e0.op, e0.prescale, e0.postscale, segments,
            nproc, jax.process_index())
        red = jnp.asarray(red)
        off = 0
        for e in entries:
            n = e.tensor.size
            e.handle._fulfill(red[off:off + n].reshape(e.tensor.shape))
            off += n
        if xla_open:
            for e in entries:
                tlobj.end_activity(e.name)
            xla_open = False
    except Exception as err:  # surface as HorovodInternalError for elastic
        _end_negotiate()
        if xla_open:
            for e in entries:
                tlobj.end_activity(e.name)
        for e in entries:
            e.handle._fail(HorovodInternalError(str(err)))


def synchronize(handle: Handle):
    """Block until the handle's collective completed and return the result
    (reference ``torch/mpi_ops.py:606``)."""
    from horovod_tpu.ops.bucketing import global_bucketer

    if not handle._done.is_set():
        global_bucketer().flush()
    handle._done.wait()
    if handle._error is not None:
        raise handle._error
    result = handle._result
    compression, ctx = getattr(handle, "_decompress", (None, None))
    if compression is not None:
        result = compression.decompress(result, ctx)
    return jax.block_until_ready(result)


def poll(handle: Handle) -> bool:
    """Non-blocking completion check (reference ``torch/mpi_ops.py:590``).

    Polling an undispatched handle drains the pending buckets first (the
    reference's background loop would have picked the tensor up within one
    cycle; with no background thread, the poll itself is the cycle edge —
    and a deterministic one, since it follows program order on every
    process)."""
    if not handle._done.is_set():
        from horovod_tpu.ops.bucketing import global_bucketer

        global_bucketer().flush()
    if not handle._done.is_set():
        return False
    r = handle._result
    try:
        return bool(r.is_ready()) if hasattr(r, "is_ready") else True
    except Exception:
        return True


_motion_cache: dict = {}


def _allgather_rows(garr):
    """O(data) data plane for eager allgather.

    ``lax.all_gather`` inside a shard_map over the proc mesh: each process
    wires out its own row once and receives the other ``nproc-1`` rows —
    total bytes on the wire per process = size of the gathered result, the
    same cost contract as the reference's ``MPI_Allgatherv``
    (``mpi_operations.cc:96``).  (A replicated ``out_shardings`` identity
    jit happens to lower to the same collective, but only by optimizer
    grace; this shape is the explicit, guaranteed form.)
    """
    mesh = process_mesh()
    key = ("ag", id(mesh))
    fn = _motion_cache.get(key)
    if fn is None:
        def ag(x):          # local block: (1, rows, ...)
            return jax.lax.all_gather(x, "proc", axis=0, tiled=True)

        fn = jax.jit(jax.shard_map(
            ag, mesh=mesh, in_specs=P("proc"), out_specs=P(),
            check_vma=False))
        _motion_cache[key] = fn
    return fn(garr)


def _alltoall_rows(garr):
    """O(data) data plane for eager alltoall.

    ``lax.all_to_all`` inside a shard_map over the proc mesh.  Input is the
    slot-packed global array ``(nproc_sender, nproc_dest, max_rows, ...)``
    sharded by sender; the collective routes slot ``d`` of each sender to
    process ``d``.  Wire cost per process: send ``(nproc-1) × max_rows``
    rows, receive the same — O(data), matching ``MPI_Alltoallv``
    (``mpi_operations.cc:392``).  The round-1 implementation replicated the
    whole slot tensor to every process (O(world²·max_rows) received per
    process); this is the fix for that scaling bug.

    Returns the global result ``(nproc_sender, nproc_dest, max_rows, ...)``
    sharded over the *destination* axis; callers read their own column via
    ``addressable_shards`` — no further cross-process movement.
    """
    mesh = process_mesh()
    key = ("a2a", id(mesh))
    fn = _motion_cache.get(key)
    if fn is None:
        def a2a(x):         # local block: (1, nproc, max_rows, ...)
            return jax.lax.all_to_all(x, "proc", split_axis=1,
                                      concat_axis=0)

        fn = jax.jit(jax.shard_map(
            a2a, mesh=mesh, in_specs=P("proc"),
            out_specs=P(None, "proc"), check_vma=False))
        _motion_cache[key] = fn
    return fn(garr)


def _fulfilled(name: str, value) -> Handle:
    """A pre-completed handle (the nproc==1 short-circuit of the async
    variants keeps the handle API shape)."""
    h = Handle(name)
    h._result = value
    h._done.set()
    return h


def allgather(tensor, name: Optional[str] = None):
    """Gather tensors from all processes, concatenated on dim 0; first dims
    may differ per process (reference ``EnqueueTensorAllgather``
    ``operations.cc:903``, recvcounts in ``mpi_operations.cc:96``)."""
    out, _ = allgather_with_sizes(tensor, name=name)
    return out


def allgather_async(tensor, name: Optional[str] = None) -> Handle:
    """Async ``allgather`` (reference ``allgather_async``,
    ``torch/mpi_ops.py:692``): the negotiation head runs inline — eager
    collectives must hit the wire in program order on every process —
    but the device computation and result fetch stay asynchronous until
    ``synchronize``."""
    handle, _ = _allgather_submit(tensor, name)
    return handle


def allgather_with_sizes(tensor, name: Optional[str] = None):
    """``allgather`` that also returns the negotiated per-process first-dim
    sizes as a host ``np.ndarray`` — callers exchanging variable payloads
    (``allgather_object``) reuse them instead of a second collective."""
    handle, sizes = _allgather_submit(tensor, name)
    return synchronize(handle), sizes


def _allgather_submit(tensor, name: Optional[str] = None):
    name = name or _next_name("allgather")
    tensor = _localize(tensor)
    mesh = process_mesh()
    nproc = mesh.devices.size
    if nproc == 1:
        return (_fulfilled(name, tensor),
                np.asarray([tensor.shape[0]], np.int64))
    handle = Handle(name)
    _register(name, handle)
    sizes = None
    try:
        # sequential NEGOTIATE -> XLA_* spans (docs/timeline.md contract;
        # matches _dispatch_group's transition) so the dispatch span never
        # absorbs negotiation wait
        with tl.activity(name, tl.NEGOTIATE):
            # first dims may differ per process; everything else must agree
            _negotiate({
                "kind": "allgather",
                "sig": f"{name}:{tensor.dtype}:{tuple(tensor.shape[1:])}",
            })
            # negotiate first-dim sizes (the controller's recvcount exchange)
            sizes = _allgather_host_metadata(
                np.asarray([tensor.shape[0]], np.int64)).reshape(nproc)
        with tl.activity(name, tl.XLA_ALLGATHER):
            max_rows = int(sizes.max())
            from horovod_tpu.ops import op_manager

            pad = jnp.zeros((max_rows,) + tensor.shape[1:], tensor.dtype)
            pad = pad.at[:tensor.shape[0]].set(tensor)
            rows = op_manager.active_op().allgather_padded(
                pad, nproc, jax.process_index())
            out = jnp.concatenate(
                [jnp.asarray(rows[p])[:int(sizes[p])]
                 for p in range(nproc)], axis=0)
            handle._fulfill(out)
    except Exception as err:
        handle._fail(HorovodInternalError(str(err)))
    return handle, sizes


def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    """Broadcast from ``root_rank`` process to all (reference
    ``EnqueueTensorBroadcast``, ``operations.cc:928``)."""
    return synchronize(broadcast_async(tensor, root_rank, name=name))


def broadcast_async(tensor, root_rank: int,
                    name: Optional[str] = None) -> Handle:
    """Async ``broadcast`` (reference ``broadcast_async``,
    ``torch/mpi_ops.py:755``); negotiation inline for program-order
    alignment, device work asynchronous until ``synchronize``."""
    name = name or _next_name("broadcast")
    tensor = _localize(tensor)
    mesh = process_mesh()
    nproc = mesh.devices.size
    if nproc == 1:
        return _fulfilled(name, tensor)
    handle = Handle(name)
    _register(name, handle)
    try:
        with tl.activity(name, tl.NEGOTIATE):
            _negotiate({
                "kind": "broadcast",
                "sig": f"{name}:{tensor.dtype}:{tuple(tensor.shape)}:"
                       f"{root_rank}",
            })
        with tl.activity(name, tl.XLA_BROADCAST):
            from horovod_tpu.ops import op_manager

            out = op_manager.active_op().bcast(
                tensor, root_rank, nproc, jax.process_index())
            handle._fulfill(jnp.asarray(out))
    except Exception as err:
        handle._fail(HorovodInternalError(str(err)))
    return handle


def alltoall(tensor, splits=None, name: Optional[str] = None):
    """Distribute slices of dim 0 to all processes (reference
    ``EnqueueTensorAlltoall``, ``operations.cc:979``).  ``splits[i]`` rows go
    to process i; uniform split when ``splits`` is None.  Returns the
    concatenation of slices received from every process."""
    return synchronize(alltoall_async(tensor, splits, name=name))


def alltoall_async(tensor, splits=None,
                   name: Optional[str] = None) -> Handle:
    """Async ``alltoall`` (reference ``alltoall_async``,
    ``torch/mpi_ops.py:812``); negotiation inline for program-order
    alignment, device work asynchronous until ``synchronize``."""
    name = name or _next_name("alltoall")
    tensor = _localize(tensor)
    mesh = process_mesh()
    nproc = mesh.devices.size
    if splits is None:
        if tensor.shape[0] % nproc != 0:
            raise ValueError(
                "tensor dim 0 not divisible by world size; pass splits")
        splits = np.full((nproc,), tensor.shape[0] // nproc, np.int64)
    splits = np.asarray(splits, np.int64)
    if splits.sum() != tensor.shape[0]:
        raise ValueError("splits must sum to tensor.shape[0]")
    if nproc == 1:
        return _fulfilled(name, tensor)
    handle = Handle(name)
    _register(name, handle)
    try:
        with tl.activity(name, tl.NEGOTIATE):
            _negotiate({
                "kind": "alltoall",
                "sig": f"{name}:{tensor.dtype}:{tuple(tensor.shape[1:])}",
            })
            all_splits = _allgather_host_metadata(splits)  # (nproc, nproc)
            all_splits = all_splits.reshape(nproc, nproc)
        with tl.activity(name, tl.XLA_ALLTOALL):
            max_rows = int(all_splits.max())
            me = jax.process_index()
            from horovod_tpu.ops import op_manager

            # slot-pack: slot d holds rows destined to process d
            slots = jnp.zeros((nproc, max_rows) + tensor.shape[1:],
                              tensor.dtype)
            off = 0
            for d in range(nproc):
                cnt = int(splits[d])
                if cnt:
                    slots = slots.at[d, :cnt].set(tensor[off:off + cnt])
                off += cnt
            cols = op_manager.active_op().alltoall_slots(slots, nproc, me)
            out = jnp.concatenate(
                [jnp.asarray(cols[src])[:int(all_splits[src, me])]
                 for src in range(nproc)], axis=0)
            handle._fulfill(out)
    except Exception as err:
        handle._fail(HorovodInternalError(str(err)))
    return handle


def _allgather_host_metadata(arr: np.ndarray) -> np.ndarray:
    """Tiny fixed-shape host metadata allgather over processes — the
    control-plane exchange (recvcounts / splits negotiation,
    ``mpi_controller.cc:164-231``).

    int64 payloads are exchanged as int32 word pairs: without
    ``jax_enable_x64`` jnp silently truncates int64 to int32, which would
    corrupt any value ≥ 2^31 (e.g. nanosecond timestamps)."""
    arr = np.ascontiguousarray(arr)
    mesh = process_mesh()
    nproc = mesh.devices.size
    if nproc == 1:
        return arr[None]
    from horovod_tpu.ops import op_manager

    return op_manager.active_op().metadata_allgather(
        arr, nproc, jax.process_index())


def _xla_metadata_allgather(arr: np.ndarray) -> np.ndarray:
    """XLA-plane implementation of the metadata allgather (called via
    ``op_manager.XlaOps``): replicated identity jit over the lifted
    array.  int64 payloads are exchanged as int32 word pairs — without
    ``jax_enable_x64`` jnp silently truncates int64 to int32, which
    would corrupt any value ≥ 2^31 (e.g. microsecond timestamps)."""
    mesh = process_mesh()
    nproc = mesh.devices.size
    is64 = arr.dtype == np.int64
    wire = arr.view(np.int32) if is64 else arr
    garr = _lift(jnp.asarray(wire))
    rep = jax.jit(lambda g: g, out_shardings=_replicated(mesh))(garr)
    out = np.ascontiguousarray(np.asarray(rep))
    if is64:
        out = out.view(np.int64)
    return out.reshape((nproc,) + arr.shape)


def barrier(name: Optional[str] = None) -> None:
    """Block until all processes arrive (reference
    ``MPIController::Barrier``, ``mpi_controller.cc:225``).

    The negotiation head exchange IS the barrier; routing it through
    ``_negotiate`` (rather than a bare metadata allgather) keeps the wire
    aligned when some processes sit in a ``join()`` service loop — they
    observe a ``barrier`` descriptor, contribute nothing, and keep
    cycling."""
    mesh = process_mesh()
    if mesh.devices.size == 1:
        return
    _negotiate({"kind": "barrier", "sig": "barrier"})


def join() -> int:
    """Uneven-data termination: joined processes keep servicing other
    ranks' collectives with zero contributions until every process joins
    (reference ``EnqueueJoin`` ``operations.cc:1044``; zero synthesis
    ``controller.cc:263-274`` + ``tensor_queue.cc
    GetTensorEntriesFromResponse``).  Returns the exact rank of the last
    process to join, from the globally-consistent negotiation tick at
    which each process entered join (ties broken toward the higher rank)
    — the answer the reference controller reads off request arrival
    order.

    While a process sits in this loop, other ranks may continue issuing
    ``allreduce`` (SUM/AVERAGE/ADASUM — the joined process replays the
    identical jitted reduction with a zero input, so AVERAGE still
    divides by the full world size, exactly like the reference's
    postscale-1/size) and ``barrier``.  ``allgather``/``broadcast``/
    ``alltoall`` from non-joined ranks raise the reference's
    "not supported with Join" errors — on those ranks AND out of this
    loop (the reference delivers error responses on every rank,
    ``controller.cc:380``; a fatally-erroring peer must not leave
    joined processes blocking forever).  The error cycle completes its
    wire exchanges everywhere before anyone raises, so ranks that catch
    the error stay aligned and may re-enter ``join()``.  Ragged
    *per-step* participation
    inside a jitted train step is handled by zero-masking instead (see
    ``horovod_tpu.optim.join_step``).
    """
    from horovod_tpu.ops.bucketing import global_bucketer

    global_bucketer().flush()
    mesh = process_mesh()
    nproc = mesh.devices.size
    if nproc == 1:
        return 0
    my_tick = _cycle
    while True:
        neg = _negotiate(None, join_cycle=my_tick)
        if neg.all_joined:
            return neg.last_rank
        d = neg.desc
        if d is None:  # pragma: no cover - _negotiate raises on mismatch
            continue
        kind = d.get("kind")
        # Active ranks raise on join-unsupported collectives and then
        # stop issuing cycles; raise the identical error here instead of
        # blocking forever in the next head exchange (reference delivers
        # error responses on every rank, ``controller.cc:487-497,569``).
        if kind in _JOIN_UNSUPPORTED:
            raise HorovodInternalError(_JOIN_UNSUPPORTED[kind])
        if kind == "allreduce":
            op = ReduceOp[d["op"]]
            if op not in _JOIN_ZERO_OPS:
                raise HorovodInternalError(_join_bad_op_error(d["op"]))
            from horovod_tpu.ops import op_manager

            zeros = jnp.zeros((d["n"],), jnp.dtype(d["dtype"]))
            op_manager.active_op().reduce_rows(
                zeros, op, d["pre"], d["post"], tuple(d["segments"]),
                nproc, jax.process_index())
        elif d.get("kind") == "hostsync":
            # elastic host-update sync: participate in the fixed 3-word
            # exchange with zeros ("nothing to report")
            _allgather_host_metadata(np.zeros((3,), np.int64))
        # barrier: the head exchange was the whole contribution; loop
        # straight back into the next cycle.  (Unsupported kinds raised
        # above — they never reach this point.)
