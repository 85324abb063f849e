"""Where the program runs: the one platform check and the compile cache.

``on_tpu`` is the only place the repo asks which backend it is on.  Every
Pallas entry resolves its ``interpret`` flag through ``interpret_mode``,
outside its jit (``resolves_interpret``): kernels compile for the chip on a
TPU and run under the Pallas interpreter everywhere else, unless a caller
(a test) asks for one explicitly.

``enable_compile_cache`` is called first by the launch entry points (never
at import of library code, never by tests).
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Optional

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
# fixed in-checkout path: the path is part of the cache key, so a directory
# that moves between runs (temp names, pids, times) would never hit
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Compiled on a TPU, interpreted off it; an explicit bool wins."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def resolves_interpret(entry: Callable) -> Callable:
    """Wrap a jitted Pallas entry whose ``interpret`` is static so the
    policy is applied before the jit: the compiled kernel is keyed by the
    concrete choice, never by ``None`` (a trace keyed by ``None`` could be
    reused for a caller on the other side of the policy)."""
    @functools.wraps(entry)
    def call(*args, interpret: Optional[bool] = None, **kwargs):
        return entry(*args, interpret=interpret_mode(interpret), **kwargs)
    return call


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache every program: the serving steps compile in well under the
    # default one-second threshold, and a cold chip call recompiles them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
