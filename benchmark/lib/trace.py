"""From a profiler trace to numbers.

``load_xplane`` cuts JAX's ``.xplane.pb`` down to two plain lists — device
operations per device and host spans — on one clock (nanoseconds from the
start of the trace).  Everything else here is arithmetic on those lists, so
it is tested on a small recorded trace (``benchmark/tests/data``) and no PR
that claims a gain can change how a number is made.

A device operation is ``(name, start_ns, dur_ns, label)``: ``name`` the HLO
instruction, ``label`` what a person can act on (kernel name or name scope
where the event's metadata carries one, else the HLO name).
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: device lines that hold one event per executed operation.  "XLA Modules",
#: "Steps" and "XLA TraceMe" cover the same time again and would count it
#: twice.
_OP_LINES = ("XLA Ops",)
#: opcodes that move data between chips or wait for it.  On the TPU's one
#: operation timeline a collective that is running (or a ``-done`` that is
#: waiting) is time in which no compute runs on that core.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv|async-collective)", re.IGNORECASE)
_KERNEL_NAME = re.compile(r'kernel_name\W+([A-Za-z_][A-Za-z0-9_]*)')
_LAYOUT = re.compile(r"\{[^{}]*\}")


def parse_hlo(text: str) -> Tuple[str, str, str, int]:
    """(instruction name, opcode, result type without layouts, operand
    count) of an event name as the TPU's "XLA Ops" line gives it: the HLO
    instruction's text, ``%name = type opcode(operands), attributes``.  An
    event that is not such a text comes back as (text, "", "", 0)."""
    if not text.startswith("%") or " = " not in text:
        return text, "", "", 0
    name, rest = text[1:].split(" = ", 1)
    while _LAYOUT.search(rest):
        rest = _LAYOUT.sub("", rest)
    depth, i = 0, 0
    while i < len(rest):            # the result type may be a tuple
        ch = rest[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            break
        i += 1
    result, rest = rest[:i], rest[i + 1:]
    opcode = rest.split("(", 1)[0]
    depth, operands, any_operand = 0, 0, False
    for ch in rest[len(opcode):]:
        if ch in "([":
            depth += 1
        elif ch in ")]":
            depth -= 1
            if depth == 0:
                break
        elif ch == "," and depth == 1:
            operands += 1
        elif not ch.isspace():
            any_operand = True
    return name, opcode, result.replace(" ", ""), \
        operands + 1 if any_operand else 0


def label_of(text: str) -> Tuple[str, str, str]:
    """(name, opcode, label) of a device event.  The label is what a person
    can act on: a Pallas kernel's name where the text carries one, else for
    a Pallas call its name scope, operand count and result shapes
    (``attention/pallas(3)->(bf16[4,32,2048,128],f32[4,32,2048,8])``), for
    a collective its opcode, else the instruction's name (``fusion.102``)."""
    name, opcode, result, operands = parse_hlo(text)
    if opcode == "custom-call" and "tpu_custom_call" in text:
        m = _KERNEL_NAME.search(text)
        if m:
            return name, opcode, m.group(1)
        scope = re.sub(r"\.\d+$", "", name)
        return name, opcode, f"{scope}/pallas({operands})->{result}"
    if COLLECTIVE.match(opcode):
        return name, opcode, opcode
    return name, opcode, name


def self_times(ops: List[tuple]) -> List[float]:
    """Duration of each operation less the operations nested inside it (a
    ``while`` covers its body's operations on the same line).  ``ops`` is
    sorted by start."""
    self_ns = [op[2] for op in ops]
    stack: List[int] = []
    for i, op in enumerate(ops):
        start, end = op[1], op[1] + op[2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]][1] + ops[stack[-1]][2] + 1:
            self_ns[stack[-1]] -= op[2]
        stack.append(i)
    return [max(x, 0.0) for x in self_ns]


def load_xplane(path: str) -> Dict[str, object]:
    """``{"device": {plane: [op, ...]}, "host": [(name, start, dur), ...]}``
    with op = (name, start_ns, dur_ns, label, opcode, self_ns)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            named = [ln for ln in lines if ln.name in _OP_LINES]
            raw = []
            for line in named or lines:
                for ev in line.events:
                    name, opcode, label = label_of(ev.name)
                    raw.append((name, float(ev.start_ns),
                                float(ev.duration_ns), label, opcode))
            raw.sort(key=lambda op: (op[1], -op[2]))
            device[plane.name] = [op + (own,) for op, own in
                                  zip(raw, self_times(raw))]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    host.sort(key=lambda sp: sp[1])
    return {"device": device, "host": host}


def find_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def save_json(trace: Dict[str, object], path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def load_json(path: str) -> Dict[str, object]:
    with open(path) as f:
        raw = json.load(f)
    return {"device": {k: [tuple(op) for op in v]
                       for k, v in raw["device"].items()},
            "host": [tuple(sp) for sp in raw["host"]]}


# ---- interval arithmetic -------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same time."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of union ``a`` that union ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_intervals(ops: Sequence[tuple]) -> List[Interval]:
    return union((op[1], op[1] + op[2]) for op in ops)


def window_of(trace: Dict[str, object]) -> Optional[Interval]:
    """The traced slice: from the first to the last ``bench/`` host span if
    there are any (the slice the harness meant to trace), else from the
    first device operation to the last."""
    host = trace["host"]
    if host:
        return (min(s for _, s, _ in host),
                max(s + d for _, s, d in host))
    edges = [(ops[0][1], max(op[1] + op[2] for op in ops))
             for ops in trace["device"].values() if ops]
    if not edges:
        return None
    return (min(lo for lo, _ in edges), max(hi for _, hi in edges))


def busy(trace: Dict[str, object]) -> Optional[Dict[str, float]]:
    """``busy_s`` (union of device-operation intervals inside the window,
    averaged over devices) and ``window_s``."""
    win = window_of(trace)
    devices = [ops for ops in trace["device"].values() if ops]
    if win is None or not devices:
        return None
    lo, hi = win
    busy_ns = [total(clip(op_intervals(ops), lo, hi)) for ops in devices]
    return {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "window_s": (hi - lo) / 1e9}


def time_by_label(trace: Dict[str, object], top: int = 10
                  ) -> List[List[object]]:
    """Device seconds by label (each operation's own time, without what
    is nested in it), averaged over devices, largest first."""
    devices = [ops for ops in trace["device"].values() if ops]
    win = window_of(trace)
    if not devices or win is None:
        return []
    lo, hi = win
    acc: Dict[str, float] = {}
    for ops in devices:
        for name, start, dur, label, opcode, own in ops:
            if own > 0 and lo <= start and start + dur <= hi:
                acc[label] = acc.get(label, 0.0) + own
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[label, ns / len(devices) / 1e9] for label, ns in rows]


def kernel_seconds(trace: Dict[str, object], pattern: str
                   ) -> Optional[Dict[str, float]]:
    """Seconds and calls of the operations whose HLO name or label matches
    ``pattern``, averaged over devices; None when nothing matches."""
    rx = re.compile(pattern)
    devices = [ops for ops in trace["device"].values() if ops]
    win = window_of(trace)
    if not devices or win is None:
        return None
    lo, hi = win
    ns, calls = 0.0, 0
    for ops in devices:
        for name, start, dur, label, opcode, own in ops:
            if start < lo or start + dur > hi:
                continue        # whole calls only: bytes are counted per call
            if rx.search(name) or rx.search(label):
                ns += dur
                calls += 1
    if not calls:
        return None
    return {"seconds": ns / len(devices) / 1e9,
            "calls": calls / len(devices)}


def idle_gaps(trace: Dict[str, object], top: int = 10) -> List[List[object]]:
    """Idle device time by what the host was doing: each gap between device
    operations (first device; the others see the same host) is split among
    the innermost ``bench/`` host spans that overlap it; what no span covers
    is ``_none_``."""
    win = window_of(trace)
    devices = [ops for _, ops in sorted(trace["device"].items()) if ops]
    if win is None or not devices:
        return []
    lo, hi = win
    gaps = subtract([(lo, hi)], clip(op_intervals(devices[0]), lo, hi))
    # innermost span wins: walk spans shortest first and let each claim what
    # is still unclaimed
    acc: Dict[str, float] = {}
    left = gaps
    for name, start, dur in sorted(trace["host"], key=lambda sp: sp[2]):
        span = [(start, start + dur)]
        claimed = total(left) - total(subtract(left, span))
        if claimed > 0:
            acc[name] = acc.get(name, 0.0) + claimed
            left = subtract(left, span)
    rest = total(left)
    if rest > 0:
        acc["_none_"] = rest
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in rows]


def collective_exposed(trace: Dict[str, object]) -> Optional[Dict[str, float]]:
    """Per device: own time of collective operations (``-done`` waits
    included) inside the window.  A core runs one operation at a time, so
    while one of these runs no compute does: all of it is exposed.  Mean
    over devices, in seconds."""
    win = window_of(trace)
    devices = [ops for ops in trace["device"].values() if ops]
    if win is None or not devices:
        return None
    lo, hi = win
    exposed = 0.0
    for ops in devices:
        exposed += sum(own for name, start, dur, label, opcode, own in ops
                       if COLLECTIVE.match(opcode)
                       and lo <= start and start + dur <= hi)
    return {"exposed_s": exposed / len(devices) / 1e9,
            "window_s": (hi - lo) / 1e9}
