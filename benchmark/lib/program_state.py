"""What the program says once about a state it carries: the last record of
a name on its process-global tracer, wherever in the run it was written
(``train/model_state``, read at ``engine.close()``, after the window).
Nothing here raises where the program has no tracer, no such record or no
such attribute: the caller returns None and the line leaves the metric out."""
from __future__ import annotations

from typing import Optional

from lib import program_trace


def last_record_attr(run, span: str, value: str) -> Optional[float]:
    spans = program_trace.ring(run)
    if not spans:
        return None
    for sp in reversed(spans):
        if sp[0] == span:
            got = sp[3].get(value)
            return None if got is None else float(got)
    return None
