"""Where JAX's persistent compilation cache lives, and the program's own
account of what it compiled.

A cold process pays every XLA compile again (tens of seconds per program at
real widths on the chip), so the entry points place the cache before their
first compile.  The location can be given from outside: JAX itself honours
``JAX_COMPILATION_CACHE_DIR``, and where that is set no directory is set in
code.
Otherwise the cache goes to one fixed directory inside the checkout: a
directory that moves between runs is never found again.

JAX keeps only programs that took a second to compile.  A serving engine
compiles one decode program per (width, window length) and the narrow ones
take 0.3-0.9 s each (PERF.md section 6, PR 29): under that threshold, so
never kept and compiled again at every start.  Wherever the cache lives,
programs from ``MIN_COMPILE_SECS`` up are kept, unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise from outside.
What still compiles at every start is in the account below (``cache`` =
``compiled``).  On a warm start of the benchmark's prefill cell (34 serving
programs, 28 of them decode windows; chip run, PR 36, PERF.md section 5) the
cache answers in 2.1 s and 18 programs too small to keep compile again in
2.1 s, but 33 s of the 45 s set-up is Python tracing and lowering, which a
cache hit does not skip: the cost of a program that is kept is its trace.

A process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, the rehearsals)
gets no cache from here.  There is nothing worth keeping — the toy programs
compile in about a second — and XLA:CPU logs a machine-feature error line
for every entry it reloads.

**The account.**  JAX times every phase of a compile where the work happens
and says which program it was (``jax.monitoring``, public).
``configure_compile_cache()`` installs listeners, once a process, CPU or
not, that write one record a phase into the ring of the process-global
tracer (``telemetry.get_tracer()``, ``Tracer.record()``: no profiler event,
no file):

* ``compile/trace`` (``program``, ``inner_traces``) — Python tracing of the
  OUTERMOST function on the thread.  Every ``jax.numpy`` call inside a trace
  fires the event again, thousands of times a model program; such an inner
  one moves a per-thread depth counter and writes nothing.
* ``compile/lower`` (``program``, ``inner_traces``) — jaxpr to MLIR; for a
  Pallas call this holds the Mosaic lowering.  Lowering rules trace
  ``jax.numpy`` helpers too: they are inner, and counted.
* ``compile/backend`` (``program``, ``cache``; on a hit ``retrieval_s``,
  ``saved_s``) — XLA, or the cache's answer in its place: ``hit``;
  ``written`` (compiled, and an entry written inside the phase);
  ``compiled`` (the cache was asked and nothing followed: under
  ``MIN_COMPILE_SECS`` or the size floor, so compiled again at every
  start); ``off`` (never asked).

``program`` is the compiled module's name as a device profile's "XLA
Modules" line shows it (``jit_serve_decode_s64x8``), so a program's compile
cost and its device time join on one key.  A record's ``parent`` is the span
open on the compiling thread: after start-up ``compile_account()`` says
what each program cost and which missed the cache, and a ``compile/backend``
record later is a recompile, its ``parent`` the call that paid
(``engine/decode_launch``, ``engine/put_dispatch``, ``engine/dispatch``).
A step that hits ``jit``'s fast path fires no event: the hot path gains no
instruction.
"""
from __future__ import annotations

import os
import re
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

from ..telemetry.trace import SpanRecord, get_tracer

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
    compile anything.  Installs the compile listeners too (module text)."""
    install_compile_listeners()
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


# --------------------------------------------------------------------- #
# The account of compiles (module text): jax.monitoring -> the tracer's ring
# --------------------------------------------------------------------- #
#: JAX's event of each phase (fired at entry as a scalar, at exit as a
#: duration, both with ``fun_name``) -> the record it becomes
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
#: what the persistent cache says inside a backend phase, in the order JAX
#: says it: asked; then found, or compiled and an entry written
_CACHE_ANSWERS = {
    "/jax/compilation_cache/compile_requests_use_cache": "compiled",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "written",
}
_HIT_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")


class _Compiling(threading.local):
    """What this thread has open (the class attributes are each thread's
    start)."""
    depth = 0       # compile phases open
    inner = 0       # traces that opened and closed inside the outermost phase
    cache = None    # the open backend phase's attributes


_THREAD = _Compiling()
_INSTALLED = False
_INSTALL_LOCK = threading.Lock()


def _program(fun_name: str) -> str:
    """The compiled module's name, from the function's name (the trace
    event: ``serve_decode_s64x8``, taken for a ``jit``) or the wrapped one
    (the other two: ``jit(serve_decode_s64x8)``), as JAX names the module."""
    if not fun_name.endswith(")"):
        fun_name = f"jit({fun_name})"
    return _NOT_IN_A_MODULE_NAME.sub("_", fun_name).rstrip("_")


def _on_open(event: str, value, **kwargs) -> None:
    name = _PHASES.get(event)
    if name is not None:
        _THREAD.depth += 1
        if name == "compile/backend":
            _THREAD.cache = {"cache": "off"}


def _on_cache_event(event: str, **kwargs) -> None:
    answer = _CACHE_ANSWERS.get(event)
    if answer is not None and _THREAD.cache is not None:
        _THREAD.cache["cache"] = answer


def _on_close(event: str, duration: float, **kwargs) -> None:
    name = _PHASES.get(event)
    thread = _THREAD
    if name is None:
        key = _HIT_SECONDS.get(event)
        if key is not None and thread.cache is not None:
            thread.cache[key] = duration
        return
    thread.depth -= 1
    if thread.depth < 0:            # opened before the listeners were there
        thread.depth = 0
        return
    if name == "compile/backend":   # never noise: XLA ran or the cache spoke
        attrs, thread.cache = thread.cache or {"cache": "off"}, None
    elif thread.depth:              # inside another phase: counted, not written
        thread.inner += name == "compile/trace"
        return
    else:
        attrs, thread.inner = {"inner_traces": thread.inner}, 0
    get_tracer().record(name, time.perf_counter() - duration, duration,
                        program=_program(kwargs.get("fun_name", "")), **attrs)


def install_compile_listeners() -> None:
    """Once a process; a second call changes nothing."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if _INSTALLED:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_open)
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_duration_secs_listener(_on_close)
        _INSTALLED = True


def remove_compile_listeners() -> None:
    """For the tests: JAX fires into nothing of this module afterwards."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if not _INSTALLED:
            return
        from jax import monitoring

        monitoring.unregister_scalar_listener(_on_open)
        monitoring.unregister_event_listener(_on_cache_event)
        monitoring.unregister_event_duration_listener(_on_close)
        _INSTALLED = False


_SECONDS_OF = {"compile/trace": "trace_s", "compile/lower": "lower_s",
               "compile/backend": "backend_s"}


def compile_account(records: Optional[Iterable[SpanRecord]] = None
                    ) -> List[Dict[str, Any]]:
    """The ring's ``compile/*`` records (or ``records``) as one row a
    program, in the order the programs were first seen: ``program``,
    ``trace_s``, ``inner_traces``, ``lower_s``, ``backend_s``, ``cache``
    ({answer: backend phases that got it}), ``times`` (how often it was
    built: its backend phases), ``first_start_s`` (on the tracer's clock)
    and ``parents`` (the spans its phases ran under; ``None`` = no span).
    Keeps no state: ask again after the ring moved on and get what is
    there."""
    rows: Dict[str, Dict[str, Any]] = {}
    for rec in get_tracer().records() if records is None else records:
        seconds = _SECONDS_OF.get(rec.name)
        if seconds is None:
            continue
        attrs = rec.attrs or {}
        program = attrs.get("program", "")
        row = rows.get(program)
        if row is None:
            row = rows[program] = {
                "program": program, "trace_s": 0.0, "inner_traces": 0,
                "lower_s": 0.0, "backend_s": 0.0, "cache": {}, "times": 0,
                "first_start_s": rec.start_s, "parents": []}
        row[seconds] += rec.dur_s
        row["inner_traces"] += attrs.get("inner_traces", 0)
        if rec.name == "compile/backend":
            answer = attrs.get("cache", "off")
            row["cache"][answer] = row["cache"].get(answer, 0) + 1
            row["times"] += 1
        row["first_start_s"] = min(row["first_start_s"], rec.start_s)
        if rec.parent not in row["parents"]:
            row["parents"].append(rec.parent)
    return sorted(rows.values(), key=lambda row: row["first_start_s"])
