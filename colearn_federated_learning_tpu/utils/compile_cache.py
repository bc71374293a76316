"""Where the persistent XLA compile cache lives.

Every entry point (``cli.main``, ``chip_smoke.py``,
``__graft_entry__.py``, the scripts, ``tests/conftest.py``) calls
:func:`enable_compile_cache` once, before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: the cache is placed from outside.
  jax read the variable at import, so nothing is assigned here.
- unset: ``<checkout>/.jax_cache/<host_key>`` — a fixed path, because
  the path is part of the cache key and a directory that moves never
  hits.  The per-host component is there because an XLA:CPU AOT result
  loaded on a CPU with other features can SIGILL; it is derived from
  ``/proc/cpuinfo`` and so is stable on a machine.  The path is handed
  to jax through the variable itself, which also reaches child processes
  that pass through no entry point of ours (test helpers); a jax that
  was imported before the call has already read its environment, so it
  is told directly as well.  Subcommands that never import jax (lint,
  broker, sentinel) therefore do not pay for the import here.
"""

from __future__ import annotations

import hashlib
import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def host_key() -> str:
    """Stable 10-hex digest of this host's CPU feature lines."""
    try:
        with open("/proc/cpuinfo") as f:
            # x86 lists "flags", aarch64 lists "Features".
            feats = sorted(
                {line for line in f if line.startswith(("flags", "Features"))}
            )
    except OSError:
        feats = []
    if not feats:
        import platform

        feats = [platform.machine(), platform.processor()]
    return hashlib.sha1("".join(feats).encode()).hexdigest()[:10]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    cache = os.path.join(_CHECKOUT, ".jax_cache", host_key())
    os.environ[ENV_VAR] = cache
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", cache)
    return cache
