"""Where JAX's persistent compilation cache lives.

A cold process pays every XLA compile again (tens of seconds per program at
real widths on the chip), so the entry points place the cache before their
first compile.  The location can be given from outside: JAX itself honours
``JAX_COMPILATION_CACHE_DIR``, and where that is set nothing is set in code.
Otherwise the cache goes to one fixed directory inside the checkout: a
directory that moves between runs is never found again.

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


def configure_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache; returns the directory in
    effect (``None``: held to the CPU, no cache).  Cheap and idempotent —
    ``deepspeed_tpu.initialize()``, ``InferenceEngineV2``, ``bench.py`` and
    ``chip_smoke.py`` all call it before they compile anything."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
