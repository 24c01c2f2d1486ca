"""Host spans of the benchmark's own: recorded in memory on the host clock
and, while a profiler trace runs, written into the trace under the same
name (``bench/...``) so that device gaps can be attributed to them."""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple


class Spans:
    def __init__(self) -> None:
        self.records: List[Tuple[str, float, float]] = []   # name, t0, dur
        self.annotate = False       # set while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter() - t0))
            if ann is not None:
                ann.__exit__(None, None, None)

    def total(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> float:
        return sum(d for n, t0, d in self.records
                   if n == name and lo <= t0 < hi)

    def by_name(self, lo: float, hi: float) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n, t0, d in self.records:
            if lo <= t0 < hi:
                out[n] = out.get(n, 0.0) + d
        return out
