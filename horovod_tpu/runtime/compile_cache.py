"""Warm start: where JAX's persistent compilation cache lives.

The steady-state hot loop never pays for compilation, but *time to
first step* does, and an elastic restart pays it again while the rest
of the fleet idles.  The SPMD re-design moved the whole training step
into one compiled program, so a restart is made cheap by one cache —
JAX's own persistent compilation cache — and this module only *places*
it.  Every compile of the process (train step, eager collectives,
init) goes through it; what invalidates an entry is JAX's key (the
module, the devices, the compile options, the jaxlib and backend
versions).

Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and this module sets nothing; otherwise
:func:`enable_persistent_cache` points ``jax_compilation_cache_dir`` at
``HOROVOD_COMPILE_CACHE_DIR`` or the fixed in-checkout
:func:`default_dir`.  ``HOROVOD_COMPILE_CACHE=0`` places none.  Wired
by ``GlobalState.initialize()``.  See docs/warmstart.md.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import jax
from jax.experimental.compilation_cache import compilation_cache as _jax_cc

from horovod_tpu.utils import logging as hvd_logging

#: JAX's own cache-placement variable.  When set it is the cache root:
#: JAX keeps its persistent cache there by itself, so a caller (the
#: chip tool, a scheduler) can place the cache from outside.
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: What JAX records (``jax.monitoring``) each time a compile is served
#: from its persistent cache.
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_hits = threading.local()     # JAX records the event on the compiling thread
_listener_lock = threading.Lock()
_listener_on = False


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
    """Make JAX's persistent compilation cache live at the cache root;
    returns the root, or ``None`` when disabled.

    With ``JAX_COMPILATION_CACHE_DIR`` set there is nothing to do: JAX
    reads the variable itself.  Otherwise ``jax_compilation_cache_dir``
    becomes the root — or nothing, when the cache is disabled.
    Idempotent, and safe to re-run after an elastic reset (the config
    value survives ``clear_backends``)."""
    root = resolve_dir(config)
    if os.environ.get(ENV_JAX_CACHE_DIR):
        return root
    if root is not None:
        try:
            os.makedirs(root, exist_ok=True)
        except OSError as e:
            hvd_logging.warning(
                "compile_cache: persistent cache unavailable (%s)", e)
            root = None
    if jax.config.jax_compilation_cache_dir != root:
        jax.config.update("jax_compilation_cache_dir", root)
        # JAX opens its cache once, at the directory it then finds: a
        # root that moved (a re-init under another config) is reopened
        _jax_cc.reset_cache()
    return root


def active() -> bool:
    """Whether a compile of this process goes through JAX's persistent
    cache, whoever placed it."""
    return bool(jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)


def cache_hits() -> int:
    """Compiles of the calling thread that JAX served from its
    persistent cache, counted from the first call of this function:
    read it before and after a compile to learn whether that compile
    was a hit."""
    global _listener_on
    with _listener_lock:
        if not _listener_on:
            jax.monitoring.register_event_listener(_on_event)
            _listener_on = True
    return getattr(_hits, "n", 0)


def _on_event(event: str, **kwargs) -> None:
    if event == _HIT_EVENT:
        _hits.n = getattr(_hits, "n", 0) + 1
