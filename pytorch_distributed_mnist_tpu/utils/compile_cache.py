"""Persistent XLA compile-cache wiring — ONE config-update path for every
entry point (``cli.run``, ``serve``, ``benchmark/run.py``'s runners,
``chip_smoke.py``'s children, ``tests_tpu/``, the tools).

First compilation of the jitted whole-epoch programs is the framework's
startup tax, and a chip-tool call starts on a cold machine every time, so
the persistent compilation cache + AOT lowering is the standard remedy.
Every entry point calls :func:`configure`; none writes
``jax_compilation_cache_dir`` itself.

Resolution order for the cache directory:

1. an explicit empty ``--compile-cache ""`` — caching disabled;
2. ``JAX_COMPILATION_CACHE_DIR`` when set and non-empty — the standard jax
   variable places the cache from outside and WINS over any directory
   named in code or flags (jax already bound it at import; this module
   sets no other);
3. a non-empty ``--compile-cache DIR`` flag;
4. ``JAX_COMPILATION_CACHE_DIR`` set but empty — caching disabled (what
   the hermetic CPU suite exports);
5. the default ``<checkout>/.xla_cache`` — a fixed path, never a temporary
   name: the path is part of the cache key, so a directory that moves
   never hits.

Cache entries are keyed by jax/jaxlib version, backend, and the serialized
program, so CPU test entries never collide with TPU entries and a jax
upgrade invalidates cleanly (stale entries are simply never hit again).
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_lock = threading.Lock()
# jax's own (min_compile_secs, min_entry_bytes) from before the first
# configure(): what the implicit default dir and the disable path restore.
_jax_thresholds: Optional[Tuple] = None


def default_cache_dir() -> str:
    """``<checkout>/.xla_cache`` (gitignored, shared by every entry point)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".xla_cache")


def _resolve(flag: Optional[str]):
    """``(dir, explicit)``: the directory :func:`configure` would activate
    for ``flag`` (``None`` = disabled) and whether it was explicitly
    requested (env/flag) rather than the implicit checkout default.
    Explicit requests cache EVERY program (thresholds zeroed — the CPU-test
    programs compile sub-second and must still hit); the implicit default
    keeps jax's thresholds, which skip sub-second micro-programs
    (model-init one-offs) so a flag-less run doesn't litter the dir with
    hundreds of tiny entries per run."""
    if flag == "":
        return None, True
    env = os.environ.get(ENV_VAR)
    if env:
        return env, True
    if flag:
        return flag, True
    if env is not None:
        return None, True
    return default_cache_dir(), False


def resolve_cache_dir(flag: Optional[str] = None) -> Optional[str]:
    """The directory :func:`configure` would activate for ``flag`` —
    resolution only, no config writes. ``None`` means caching disabled."""
    return _resolve(flag)[0]


def _apply(cache_dir: Optional[str], cache_everything: bool) -> None:
    min_secs, min_bytes = (0.0, 0) if cache_dir and cache_everything \
        else _jax_thresholds
    if cache_dir:
        if jax.config.jax_compilation_cache_dir != cache_dir:
            # jax binds its cache object to the first dir that initializes
            # it, and an earlier run in this process may have compiled the
            # same programs under another dir (or none); reset so THIS
            # run's programs land in the requested dir. The in-memory jit
            # cache must go too — a program it already holds would never
            # reach XLA, so nothing would be written to the new dir.
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc,
            )

            _cc.reset_cache()
            jax.clear_caches()
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Created eagerly (idempotent) so a first run's background
        # precompile threads never race the cache backend's own mkdir.
        os.makedirs(cache_dir, exist_ok=True)
    else:
        jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", min_bytes)


def configure(flag: Optional[str] = None) -> Optional[str]:
    """Activate the persistent cache for this run; returns the active dir
    (``None`` = disabled). Safe to call repeatedly in one process — a
    previous run's dir never leaks into a run that asked for another (or
    for none), and an unchanged dir never clears the in-memory jit cache.
    """
    global _jax_thresholds
    with _lock:
        if _jax_thresholds is None:
            _jax_thresholds = (
                jax.config.jax_persistent_cache_min_compile_time_secs,
                jax.config.jax_persistent_cache_min_entry_size_bytes,
            )
        cache_dir, explicit = _resolve(flag)
        _apply(cache_dir, cache_everything=explicit)
        return cache_dir


def active_cache_dir() -> Optional[str]:
    return jax.config.jax_compilation_cache_dir or None
