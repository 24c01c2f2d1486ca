"""A profiler slice inside the measured window, reduced when it ends."""
from __future__ import annotations

import os
import shutil
import time
from typing import Dict, Optional

from . import trace as trace_lib

#: seconds of the window's end a traced run profiles (never over half of it)
SLICE_S = 3.0


class TraceSlice:
    """Starts JAX's profiler ``slice`` seconds before the window ends and
    stops it at the end; ``reduced`` is then the trace as
    ``trace_lib.load_xplane`` cuts it down."""

    def __init__(self, enabled: bool, trace_dir: str, spans, t_start: float,
                 seconds: float):
        self.enabled = enabled
        self.dir = trace_dir
        self.spans = spans
        self.t_on = t_start + seconds - min(SLICE_S, seconds / 2.0)
        self.running = False
        self.reduced: Optional[Dict] = None
        self.t_started = self.t_stopped = None   # host clock

    def maybe_start(self, now: float) -> None:
        if not self.enabled or self.running or self.reduced is not None \
                or now < self.t_on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans are the bench/ ones
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.running = True
        self.spans.annotate = True
        self.t_started = time.perf_counter()

    def end_slice(self) -> None:
        """The window is over: no more ``bench/`` spans go into the trace,
        so the reduced window ends here.  ``stop`` (which takes seconds to
        write the trace out) can wait until nothing is being timed."""
        if self.running and self.t_stopped is None:
            self.spans.annotate = False
            self.t_stopped = time.perf_counter()

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        self.end_slice()
        jax.profiler.stop_trace()
        self.running = False
        path = trace_lib.find_xplane(self.dir)
        self.reduced = trace_lib.load_xplane(path) if path else None

    @property
    def slice(self):
        """The traced slice on the host clock, or None if none was taken."""
        if self.t_started is None or self.t_stopped is None:
            return None
        return (self.t_started, self.t_stopped)
