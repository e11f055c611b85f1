"""JAX persistent compilation cache wiring (shared by train and serve).

Every preempt -> resubmit restart cold-compiles its AOT programs — the
train step for the trainer, a decode program plus one prefill program per
bucket (plus the speculative draft/verify pair) for the serving engine.
Cold compiles are pure MTTR: nothing useful runs while XLA rebuilds code
it already built last incarnation. A persistent cache turns that wall
into a disk read, so it is ON by default for every entry point.

Where the cache lives, in order:

1. ``JAX_COMPILATION_CACHE_DIR`` — JAX reads it itself; this module then
   sets no directory in code, so whoever runs the program (a scheduler
   prolog, a test harness, the machine image) places the cache.
2. an explicit ``cache_dir`` (the ``--compile-cache-dir`` flags).
3. :data:`DEFAULT_COMPILE_CACHE_DIR` — ONE fixed path inside the
   checkout. The path is part of the cache's key, so a directory named
   after a pid, a time or a temp dir would never hit.

Lives in utils/ so the training loop does not import inference/ for it;
inference/engine.py re-exports the names (serve.py and the tests import
them from there).
"""

import os
from typing import Optional

import jax

DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compilation_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache and return the directory
    in effect (``""`` = the cache stays off).

    ``cache_dir=None`` means the fixed default, ``""`` means off — but a
    cache already configured (the ``JAX_COMPILATION_CACHE_DIR`` env var,
    or an earlier call) wins over both and is left exactly as it is.
    Where this function sets the directory it also drops the
    min-compile-time / entry-size floors to 0, so the small prefill-bucket
    programs cache too. In every case the cache key takes the op metadata
    in (see below).
    """
    # An executable read back from the cache keeps the op names and source
    # lines it was compiled with. JAX's default leaves that metadata out of
    # the key, so a program whose ops were merely renamed (a new
    # obs/trace.py scope) would run under the OLD names and every profile
    # of it would misattribute its device time. The key holds the metadata
    # here, whoever placed the directory: a restart of unchanged code still
    # hits; changed code compiles once.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    if cache_dir == "":
        return ""
    cache_dir = cache_dir or DEFAULT_COMPILE_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
