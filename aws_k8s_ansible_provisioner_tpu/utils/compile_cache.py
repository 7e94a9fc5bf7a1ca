"""Where the persistent XLA compilation cache lives — one rule for every
entry point (server, bench.py, bench_sweep.py, chip_smoke.py).

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module sets
  NO directory in code, so whoever placed the cache from outside (the serving
  manifest's emptyDir, a chip runner that keeps caches between calls) is
  obeyed exactly.
- unset: the fixed, git-ignored ``.jax_compile_cache/`` at the checkout root.
  Fixed because the directory is part of the cache key — a path that moves
  (a temp dir) never hits.

The op metadata is PART OF THE KEY here (``jax_compilation_cache_include_
metadata_in_key``; JAX leaves it out by default). The step programs' operations
carry the names of the model's parts (models/parts.py) in that metadata and the
device trace is read by them; with the metadata out of the key an executable
cached by a tree without the names — or with other names — is loaded in place
of this tree's, names missing, and the trace reads ``-`` (PERF.md, PR 36: the
0.6B's prefill programs, cached by the parent, came back without a single
part). The price: the key now also holds the file names and lines of the
traced code and of its callers, so the first start after ANY edit on that
path compiles afresh, where an edit that left the jaxprs alone used to hit.

The chip-free cold/warm A/B (``bench.py --coldstart``) and the deploy
rehearsals isolate the cache on purpose and do not come through here.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_compile_cache")


def enable_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_dir
