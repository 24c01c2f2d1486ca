"""Where JAX's persistent compilation cache lives.

A cold process pays every XLA compile again (tens of seconds per program at
real widths on the chip), so the entry points place the cache before their
first compile.  The location can be given from outside: JAX itself honours
``JAX_COMPILATION_CACHE_DIR``, and where that is set no directory is set in
code.
Otherwise the cache goes to one fixed directory inside the checkout: a
directory that moves between runs is never found again.

JAX keeps only programs that took a second to compile.  A serving engine
compiles one decode program per (width, window length) — 28 in the
benchmark's prefill cell — and since the decode kernel itself compiles in
half a second (it was 2-3 s of every program, PERF.md section 6, PR 29) the
narrow ones take 0.3-0.9 s each: under that threshold, so never kept and
compiled again at every start (10 s of an 83 s set-up on the chip).  Wherever
the cache lives, programs from ``MIN_COMPILE_SECS`` up are kept, unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise from outside.

A process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, the rehearsals)
gets no cache from here.  There is nothing worth keeping — the toy programs
compile in about a second — and XLA:CPU logs a machine-feature error line
for every entry it reloads.
"""
from __future__ import annotations

import os
from typing import Optional

#: ``<checkout>/.jax_cache`` (listed in .gitignore); never built from a
#: temporary directory, a pid or the time
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


#: programs that compiled at least this long are written to the cache
#: (JAX's own default is 1.0); op-by-op programs of a few milliseconds stay out
MIN_COMPILE_SECS = 0.1


def configure_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache; returns the directory in
    effect (``None``: held to the CPU, no cache).  Cheap and idempotent —
    ``deepspeed_tpu.initialize()``, ``InferenceEngineV2``,
    ``benchmark/run.py`` and ``chip_smoke.py`` all call it before they
    compile anything."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not from_env and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          MIN_COMPILE_SECS)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
