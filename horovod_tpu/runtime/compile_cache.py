"""Warm-start compile cache: persistent XLA cache + AOT executable store.

The steady-state hot loop never pays for compilation, but *time to
first step* does: the flagship bench models spend 42-51 s in XLA before
the first optimizer update, and an elastic restart re-pays the full
amount while the rest of the fleet idles (PERF_NOTES round 8).  The
reference framework has no analogue — its per-tensor negotiation plane
is interpreted — but the SPMD re-design moved the whole training step
into one compiled program, so compile latency became an operational
cost this module takes off the training clock.  Two layers:

1. **JAX persistent compilation cache** — every jit in the process
   (train step, eager collectives, init) reuses compiled artifacts
   across process restarts.  Where ``JAX_COMPILATION_CACHE_DIR`` is set
   JAX reads it itself and this module sets nothing; otherwise
   ``enable_persistent_cache()`` points ``jax_compilation_cache_dir``
   at ``<root>/xla`` under the fixed in-checkout root
   (:func:`default_dir`).  Wired by ``GlobalState.initialize()``
   (knobs: ``HOROVOD_COMPILE_CACHE=0`` disables,
   ``HOROVOD_COMPILE_CACHE_DIR`` relocates the root when the JAX
   variable is unset).

2. **AOT executable store** — :func:`aot_compile` lowers a jitted
   function once, keys the result by a content hash (see
   :func:`executable_key`) and serializes the compiled executable with
   ``jax.experimental.serialize_executable`` into ``<root>/aot/``.
   The next process start deserializes instead of compiling: seconds
   instead of the full XLA pipeline.  ``DistributedTrainStep`` routes
   its first compile through this path transparently, which is what
   makes ``bench.py`` warm runs and elastic-driver restarts cheap.

Key contract (invalidation): the hash covers the **lowered StableHLO
text** — so any change to the model config, loss, optimizer, mesh
shape, bucket schedule or steps_per_call changes the key by
construction — plus the fields that alter backend codegen without
changing the module: jax/jaxlib versions, platform, device kinds,
device count, process count, compiler options, and caller extras
(hierarchy/bucket knobs are passed explicitly for auditability even
though they also shape the HLO).  A stale entry can therefore never be
*loaded for* a program it wasn't compiled from; deserialization
failures (new jaxlib, corrupted file) degrade to a plain compile.

Disk entries are LRU-bounded by ``Config.cache_capacity``
(``HOROVOD_CACHE_CAPACITY``) — eviction is by mtime, and every load
touches its entry.  See docs/warmstart.md.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import tempfile
import threading
from typing import Any, Optional, Tuple

import jax

from horovod_tpu.utils import logging as hvd_logging

_AOT_SUFFIX = ".aotx"
_lock = threading.Lock()
# process-wide counters; mirrored into GlobalState.cache_stats when the
# runtime is initialized so hvd.cache_stats() / bench.py surface them
_stats = {"aot_disk_hits": 0, "aot_disk_misses": 0}

#: JAX's own cache-placement variable.  When set it is the cache root:
#: JAX keeps its persistent cache there by itself and the AOT store
#: takes a subdirectory, so a caller (the chip tool, a scheduler) can
#: place the whole cache from outside.
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """The default cache root: ``.compile_cache`` beside the package
    (git-ignored in a checkout).  A fixed path on purpose — the cache
    directory is part of JAX's cache key, so a root that moves between
    runs (``$HOME`` on a throw-away machine, a temp name) never hits."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".compile_cache")


def resolve_dir(config=None) -> Optional[str]:
    """The active cache root, or ``None`` when caching is disabled.

    Enablement: explicit ``config`` → the initialized runtime's config
    → the raw ``HOROVOD_COMPILE_CACHE`` knob (so the cache works before
    ``hvd.init()``, e.g. during elastic re-rendezvous).  Placement:
    ``JAX_COMPILATION_CACHE_DIR`` → ``HOROVOD_COMPILE_CACHE_DIR`` →
    :func:`default_dir`."""
    if config is None:
        from horovod_tpu.runtime import state as rt_state

        if rt_state.is_initialized():
            config = rt_state.global_state().config
    if config is not None:
        enabled = getattr(config, "compile_cache_enabled", True)
        override = getattr(config, "compile_cache_dir", None)
    else:
        enabled = os.environ.get("HOROVOD_COMPILE_CACHE", "").lower() \
            not in ("0", "false", "no", "off")
        override = os.environ.get("HOROVOD_COMPILE_CACHE_DIR")
    if not enabled:
        return None
    return os.environ.get(ENV_JAX_CACHE_DIR) or override or default_dir()


def enable_persistent_cache(config=None) -> Optional[str]:
    """Make sure JAX's persistent compilation cache lives under the
    cache root; returns the root, or ``None`` when disabled.

    With ``JAX_COMPILATION_CACHE_DIR`` set there is nothing to do: JAX
    reads the variable itself.  Otherwise the cache goes to
    ``<root>/xla``.  Idempotent, and safe to re-run after an elastic
    reset (the config value survives ``clear_backends``)."""
    root = resolve_dir(config)
    if root is None or os.environ.get(ENV_JAX_CACHE_DIR):
        return root
    xla_dir = os.path.join(root, "xla")
    try:
        os.makedirs(xla_dir, exist_ok=True)
    except OSError as e:
        hvd_logging.warning(
            "compile_cache: persistent XLA cache unavailable (%s)", e)
        return None
    jax.config.update("jax_compilation_cache_dir", xla_dir)
    return root


def stats() -> dict:
    """Disk-store counters: ``{"aot_disk_hits": n, "aot_disk_misses": n}``."""
    with _lock:
        return dict(_stats)


def _bump(hit: bool) -> None:
    from horovod_tpu import telemetry
    from horovod_tpu.runtime import state as rt_state

    with _lock:
        _stats["aot_disk_hits" if hit else "aot_disk_misses"] += 1
    telemetry.counter(
        "hvd_aot_disk_hits_total" if hit else "hvd_aot_disk_misses_total",
        "persistent AOT executable store hits" if hit
        else "persistent AOT executable store misses").inc()
    if rt_state.is_initialized():
        cs = rt_state.global_state().cache_stats
        cs["aot_disk_hits" if hit else "aot_disk_misses"] = \
            cs.get("aot_disk_hits" if hit else "aot_disk_misses", 0) + 1


def _env_fields() -> dict:
    """The backend identity fields of the AOT key — everything that can
    change generated code without changing the lowered module."""
    import jaxlib

    devs = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devs[0].platform,
        "device_kinds": sorted({d.device_kind for d in devs}),
        "num_devices": len(devs),
        "process_count": jax.process_count(),
    }


# default object repr / bound-method repr memory addresses: a key built
# from them differs every process start, so every warm start misses
_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")


def _stable_repr(obj: Any) -> str:
    """Process-stable fallback serializer for non-JSON key fields.

    ``repr`` of an arbitrary object embeds its memory address
    (``<Mesh object at 0x7f...>``) — a different AOT key every process,
    i.e. a warm start that silently never hits (hvdlint HVD003).  Strip
    the address; the remaining type/name text still distinguishes
    semantically different values, and anything that needs finer
    identity must be passed as a JSON-serializable extra."""
    return _ADDR_RE.sub("", repr(obj))


def executable_key(lowered_text: str, extras: Optional[dict] = None,
                   compiler_options: Optional[dict] = None) -> str:
    """Content hash identifying one compiled executable.

    ``lowered_text`` is the StableHLO of the lowered program — model
    config, mesh shape, exchange schedule and steps_per_call are all
    functions of it, so they invalidate the key by construction.
    ``extras`` carries those same knobs explicitly (mesh shape,
    hierarchy, bucket bytes, ...) so cache entries are auditable and so
    semantically-relevant knobs that *don't* reach the HLO still key."""
    payload = {
        "env": _env_fields(),
        "extras": extras or {},
        "compiler_options": sorted((compiler_options or {}).items()),
        "module_sha": hashlib.sha256(
            lowered_text.encode("utf-8", "replace")).hexdigest(),
    }
    blob = json.dumps(payload, sort_keys=True, default=_stable_repr)
    return hashlib.sha256(blob.encode()).hexdigest()


def _aot_dir(root: str) -> str:
    return os.path.join(root, "aot")


def _entry_path(root: str, key: str) -> str:
    return os.path.join(_aot_dir(root), key + _AOT_SUFFIX)


def _evict(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


def load_executable(key: str, root: str):
    """Deserialize a cached entry: ``(executable, meta)``, or ``(None,
    None)`` on miss/failure.  ``meta`` is what :func:`store_executable`
    was given.  A successful load touches the entry's mtime (LRU
    recency)."""
    path = _entry_path(root, key)
    if not os.path.exists(path):
        return None, None
    try:
        from jax.experimental import serialize_executable as se

        with open(path, "rb") as f:
            payload = pickle.load(f)
        # the executable goes back onto the devices it was compiled
        # for, in assignment order: deserialize_and_load otherwise
        # spreads it over every device of the backend, and a one-device
        # program then fails at call time expecting one shard a device
        by_id = {d.id: d for d in jax.devices()}
        compiled = se.deserialize_and_load(
            payload["serialized"], payload["in_tree"], payload["out_tree"],
            execution_devices=[by_id[i] for i in payload["device_ids"]])
        os.utime(path, None)
        return compiled, payload.get("meta") or {}
    except Exception as e:  # noqa: BLE001 — any failure = plain compile
        hvd_logging.warning(
            "compile_cache: could not load AOT entry %s (%s); recompiling",
            key[:12], e)
        _evict(path)
        return None, None


class _OnProbation:
    """A deserialized executable until its first call has returned.

    Loading proves the bytes parse, not that the program runs: a stored
    entry can still be rejected when it is first handed arguments.  A
    cold start would have passed there, so that failure evicts the
    entry and compiles the lowered program fresh; the fresh
    executable's own errors propagate."""

    def __init__(self, compiled, lowered, compiler_options, path):
        self._compiled = compiled
        self._fallback = (lowered, compiler_options, path)

    def __call__(self, *args):
        if self._fallback is None:
            return self._compiled(*args)
        lowered, compiler_options, path = self._fallback
        try:
            out = self._compiled(*args)
        except Exception as e:  # noqa: BLE001 — a stored entry must not sink a run
            hvd_logging.warning(
                "compile_cache: stored AOT entry %s failed on its first "
                "call (%s); evicted, compiling fresh",
                os.path.basename(path)[:12], e)
            _evict(path)
            self._compiled = lowered.compile(
                compiler_options=compiler_options)
            out = self._compiled(*args)
        self._fallback = None
        return out


def _execution_device_ids(compiled) -> list:
    """Ids of the devices ``compiled`` runs on, in assignment order,
    read from its shardings: every sharding of one executable spans the
    same devices in the same order."""
    for s in jax.tree_util.tree_leaves(
            (compiled.input_shardings, compiled.output_shardings)):
        if isinstance(s, jax.sharding.NamedSharding):
            return [d.id for d in s.mesh.devices.flat]
        if isinstance(s, jax.sharding.SingleDeviceSharding):
            return [d.id for d in s.device_set]
    raise ValueError("no sharding of the executable names its devices")


def store_executable(key: str, compiled, root: str,
                     capacity: Optional[int] = None,
                     meta: Optional[dict] = None) -> bool:
    """Serialize ``compiled`` under ``key`` (atomic tmp+rename write),
    then prune least-recently-used entries beyond ``capacity``."""
    try:
        from jax.experimental import serialize_executable as se

        serialized, in_tree, out_tree = se.serialize(compiled)
        payload = {"serialized": serialized, "in_tree": in_tree,
                   "out_tree": out_tree,
                   "device_ids": _execution_device_ids(compiled),
                   "meta": meta or {}}
        d = _aot_dir(root)
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, _entry_path(root, key))
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    except Exception as e:  # noqa: BLE001 — never sink the train step
        hvd_logging.warning(
            "compile_cache: could not serialize executable (%s); the "
            "in-memory copy still runs, next start recompiles", e)
        return False
    prune(root, capacity)
    return True


def prune(root: str, capacity: Optional[int] = None) -> int:
    """LRU-evict AOT entries beyond ``capacity`` (default: the runtime
    config's ``cache_capacity``).  Returns the number evicted."""
    if capacity is None:
        from horovod_tpu.runtime import state as rt_state

        capacity = (rt_state.global_state().config.cache_capacity
                    if rt_state.is_initialized() else 1024)
    d = _aot_dir(root)
    try:
        entries = [os.path.join(d, n) for n in os.listdir(d)
                   if n.endswith(_AOT_SUFFIX)]
    except OSError:
        return 0
    if len(entries) <= capacity:
        return 0
    entries.sort(key=lambda p: os.path.getmtime(p))
    evicted = 0
    for path in entries[:len(entries) - capacity]:
        try:
            os.remove(path)
            evicted += 1
        except OSError:
            pass
    if evicted:
        hvd_logging.info(
            "compile_cache: evicted %d LRU AOT entr%s (capacity %d)",
            evicted, "y" if evicted == 1 else "ies", capacity)
    return evicted


def entry_count(root: Optional[str] = None) -> int:
    """Number of AOT entries on disk (0 when the cache is disabled)."""
    root = root or resolve_dir()
    if root is None:
        return 0
    try:
        return sum(1 for n in os.listdir(_aot_dir(root))
                   if n.endswith(_AOT_SUFFIX))
    except OSError:
        return 0


_UNSET = object()


def aot_compile(jitted, args: Tuple[Any, ...],
                extras: Optional[dict] = None,
                compiler_options: Optional[dict] = None,
                directory: Any = _UNSET,
                capacity: Optional[int] = None,
                describe=None):
    """Lower + compile ``jitted(*args)`` through the AOT store.

    Returns ``(compiled, cache_hit)``.  Lowering (tracing) always runs —
    it is cheap relative to XLA compilation and its output is the cache
    key — then the executable is either deserialized from disk
    (``cache_hit=True``; callable only, and on probation until its
    first call returns — :class:`_OnProbation`) or compiled and
    serialized for the next start.
    ``directory`` defaults to the configured root; pass ``None`` to
    bypass the store — either way a disabled cache degrades to a plain
    ``lower().compile()``.
    ``describe(compiled) -> dict`` is called on a freshly compiled
    executable; what it returns joins the ``train_step.compile`` span's
    attributes and is kept in the stored entry, so that a hit reports
    the same facts without reading the executable again."""
    from horovod_tpu import telemetry

    root = resolve_dir() if directory is _UNSET else directory
    with telemetry.span("train_step.lower") as lowering:
        lowered = jitted.lower(*args)
    with telemetry.span("train_step.compile") as compiling:
        compiled = None
        if root is not None:
            key = executable_key(lowered.as_text(), extras=extras,
                                 compiler_options=compiler_options)
            compiled, meta = load_executable(key, root)
        hit = compiled is not None
        if hit:
            described = meta.get("described", {})
            compiled = _OnProbation(compiled, lowered, compiler_options,
                                    _entry_path(root, key))
        else:
            compiled = lowered.compile(compiler_options=compiler_options)
            described = describe(compiled) if describe is not None else {}
            if root is not None:
                store_executable(key, compiled, root, capacity=capacity,
                                 meta={"extras": extras or {},
                                       "env": _env_fields(),
                                       "described": described})
        # what the traced program said of itself (telemetry.annotate)
        compiling.attrs = {"hit": hit, **(lowering.attrs or {}),
                           **described}
    if root is not None:
        _bump(hit)
    return compiled, hit
