"""xprof / Chrome-trace parser: device-time attribution for a captured step.

``jax.profiler.trace`` (fired by ``comms_logger.xprof_step``, see
``runtime/engine.py``) writes a TensorBoard profile directory containing one
``*.trace.json.gz`` Chrome trace per host.  This module ingests that trace —
or any plain Chrome-trace JSON, including telemetry's own ``trace.json`` —
and attributes device time to fused ops, bucketed into compute /
communication / host-transfer categories (T3, arXiv:2401.16677: the
compute-vs-collective split is the prerequisite for overlap optimization).

Device time is the "XLA Ops" lane where a device has one (the other lanes —
steps, modules — cover the same time again): an operation's time is its own
(what is nested inside it, a ``while``'s body, taken out), and a device's time
is the union of its operations' intervals, never their sum.

The second half of the module names what the anonymous device operations
(``fusion.302``) belong to.  The TPU's profile carries no ``op_name`` on its
events (checked on a v5e, PR 24), so the scope comes from the compiled
program's text: :func:`parse_hlo_scopes` maps each instruction to the
``jax.named_scope`` path of its ``metadata={op_name=...}`` (a fusion without
one takes its fused computation's commonest; what is still nameless takes its
first operand's), and :func:`time_by_scope` groups own device time by it.
Programs worth naming register their text with :func:`register_step_text`.

Stdlib-only; consumed by ``bin/dstpu-telemetry``, the overlap tuner, the
benchmark's ``scope_time_share`` reader and the profiling tests.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

#: device-lane op-name patterns → category (first match wins)
COMM_PAT = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective|cross-replica|send(?:-done)?$|recv(?:-done)?$|ncclk?|"
    r"megascale", re.IGNORECASE)
TRANSFER_PAT = re.compile(
    r"infeed|outfeed|copy-start|copy-done|host-transfer|[hd]2[hd]|"
    r"transpose-convert", re.IGNORECASE)
#: process-name patterns marking device (vs host) trace lanes
DEVICE_PROC_PAT = re.compile(r"/device:|^TPU|XLA Op|Tensor ?Core|SparseCore",
                             re.IGNORECASE)

CATEGORIES = ("compute", "communication", "host_transfer")
#: the lane of a device process that holds one event per executed operation
OP_LANE = "XLA Ops"

Interval = Tuple[float, float]


def union_seconds(intervals: Iterable[Interval]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def own_times(ops: Sequence[Tuple[float, float]]) -> List[float]:
    """Duration of each ``(start, dur)`` less the operations nested inside
    it on the same lane.  ``ops`` is sorted by (start, -dur)."""
    own = [dur for _, dur in ops]
    stack: List[int] = []
    for i, (start, dur) in enumerate(ops):
        while stack and sum(ops[stack[-1]]) <= start:
            stack.pop()
        if stack and start + dur <= sum(ops[stack[-1]]) * (1 + 1e-12) + 1e-9:
            own[stack[-1]] -= dur
        stack.append(i)
    return [max(x, 0.0) for x in own]


def find_trace_files(root: str) -> List[str]:
    """Every Chrome trace under ``root`` (a file is returned as itself):
    xprof's ``*.trace.json.gz`` plus plain ``*.trace.json`` /
    ``trace.json``, newest first."""
    if os.path.isfile(root):
        return [root]
    pats = ("**/*.trace.json.gz", "**/*.trace.json", "**/trace.json")
    found: List[str] = []
    for pat in pats:
        found.extend(glob.glob(os.path.join(root, pat), recursive=True))
    uniq = sorted(set(found), key=lambda p: os.path.getmtime(p), reverse=True)
    return uniq


def load_trace_events(path: str) -> List[Dict[str, Any]]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    if isinstance(data, list):        # bare event-array variant
        return data
    return data.get("traceEvents", [])


def _lane_names(events: Sequence[Dict[str, Any]]):
    """(pid → process name, (pid, tid) → thread name) from metadata events."""
    procs: Dict[Any, str] = {}
    threads: Dict[Any, str] = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        args = ev.get("args") or {}
        if ev.get("name") == "process_name":
            procs[ev.get("pid")] = str(args.get("name", ""))
        elif ev.get("name") == "thread_name":
            threads[(ev.get("pid"), ev.get("tid"))] = str(args.get("name", ""))
    return procs, threads


def categorize_op(name: str) -> str:
    if COMM_PAT.search(name):
        return "communication"
    if TRANSFER_PAT.search(name):
        return "host_transfer"
    return "compute"


def attribute_device_time(path_or_dir: str,
                          top_n: int = 15) -> Dict[str, Any]:
    """Parse trace file(s) and attribute duration per op and per category.

    Returns::

        {files, device_lanes, categories: {compute|communication|
         host_transfer: seconds}, device_time_s, host_time_s,
         top_ops: [{op, category, calls, total_s, pct}]}

    Device lanes are processes whose metadata name looks like a device
    (``/device:TPU:0`` etc.); when a trace has none (CPU-only capture), the
    host lanes are attributed instead and ``device_lanes`` is empty — the
    table is then host wall time, clearly labelled by the caller.
    """
    all_files = find_trace_files(path_or_dir)
    # a reused xprof dir accumulates one timestamped capture dir per run;
    # summing across runs would silently double device time.  Keep only the
    # newest capture (all hosts of one capture share a directory) and count
    # what was skipped.
    files = [p for p in all_files
             if os.path.dirname(p) == os.path.dirname(all_files[0])] \
        if all_files else []
    skipped = len(all_files) - len(files)
    per_op: Dict[str, Dict[str, float]] = {}
    host_per_op: Dict[str, Dict[str, float]] = {}
    device_lanes: List[str] = []
    host_time = 0.0
    device_time = 0.0
    categories = {c: 0.0 for c in CATEGORIES}
    for path in files:
        try:
            events = load_trace_events(path)
        except (OSError, json.JSONDecodeError, EOFError):
            continue
        procs, threads = _lane_names(events)
        dev_pids = {pid for pid, name in procs.items()
                    if DEVICE_PROC_PAT.search(name)}
        device_lanes.extend(sorted(procs[p] for p in dev_pids))
        # a device that has an "XLA Ops" lane is read from that lane alone
        op_lane = {pid: {tid for (p, tid), name in threads.items()
                         if p == pid and name == OP_LANE}
                   for pid in dev_pids}
        by_lane: Dict[Any, List[Tuple[float, float, str]]] = {}
        for ev in events:
            if ev.get("ph") != "X":
                continue
            dur_s = float(ev.get("dur", 0.0)) / 1e6
            name = str(ev.get("name", "?"))
            pid = ev.get("pid")
            if pid in dev_pids:
                if op_lane[pid] and ev.get("tid") not in op_lane[pid]:
                    continue
                by_lane.setdefault((pid, ev.get("tid")), []).append(
                    (float(ev.get("ts", 0.0)) / 1e6, dur_s, name))
            else:
                host_time += dur_s
                rec = host_per_op.setdefault(name,
                                             {"total_s": 0.0, "calls": 0})
                rec["total_s"] += dur_s
                rec["calls"] += 1
        by_dev: Dict[Any, List[Tuple[float, float, str]]] = {}
        for (pid, _tid), lane in by_lane.items():
            lane.sort(key=lambda op: (op[0], -op[1]))
            for (start, dur, name), own in zip(
                    lane, own_times([op[:2] for op in lane])):
                rec = per_op.setdefault(name, {"total_s": 0.0, "calls": 0})
                rec["total_s"] += own
                rec["calls"] += 1
            by_dev.setdefault(pid, []).extend(lane)
        for lane in by_dev.values():
            # lanes of one device may cover the same time: unions, not sums
            device_time += union_seconds((s0, s0 + d) for s0, d, _ in lane)
            for cat in CATEGORIES:
                categories[cat] += union_seconds(
                    (s0, s0 + d) for s0, d, name in lane
                    if categorize_op(name) == cat)
    if not device_lanes:
        # host-only capture (CPU smoke runs): attribute host lanes so the
        # table stays useful, flagged by the empty device_lanes list
        per_op = host_per_op
        for name, rec in per_op.items():
            categories[categorize_op(name)] += rec["total_s"]
    attributed = device_time if device_lanes else host_time
    top = sorted(per_op.items(), key=lambda kv: -kv[1]["total_s"])[:top_n]
    return {
        "files": files,
        "stale_files_skipped": skipped,
        "device_lanes": sorted(set(device_lanes)),
        "categories": categories,
        "device_time_s": device_time,
        "host_time_s": host_time,
        "top_ops": [
            {"op": name, "category": categorize_op(name),
             "calls": rec["calls"], "total_s": rec["total_s"],
             "pct": round(100.0 * rec["total_s"] / max(attributed, 1e-12), 2)}
            for name, rec in top],
    }


def format_device_table(report: Dict[str, Any]) -> List[str]:
    """Human rendering of an :func:`attribute_device_time` report."""
    lines: List[str] = []
    lanes = report.get("device_lanes") or []
    where = ", ".join(lanes) if lanes else "host lanes (no device lane found)"
    lines.append(f"trace lanes: {where}")
    if report.get("stale_files_skipped"):
        lines.append(f"(skipped {report['stale_files_skipped']} older trace "
                     f"file(s) from previous captures in this dir)")
    total = sum(report["categories"].values()) or 1e-12
    cat_txt = "  ".join(
        f"{c}: {report['categories'][c]*1e3:.2f} ms "
        f"({100.0*report['categories'][c]/total:.1f}%)" for c in CATEGORIES)
    lines.append(cat_txt)
    if report["top_ops"]:
        lines.append(f"{'op':<48}{'cat':<16}{'calls':>7}{'total(ms)':>12}"
                     f"{'%':>7}")
        for r in report["top_ops"]:
            op = r["op"] if len(r["op"]) <= 46 else r["op"][:43] + "..."
            lines.append(f"{op:<48}{r['category']:<16}{r['calls']:>7}"
                         f"{r['total_s']*1e3:>12.3f}{r['pct']:>6.1f}%")
    else:
        lines.append("(no duration events in trace)")
    return lines


# --------------------------------------------------------------------- #
# Whose time is it: name-stack scopes from the compiled program's text
# --------------------------------------------------------------------- #
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^(?:transpose|jvp|vmap|remat)\((.*)\)$")


def scope_of_op_name(op_name: str) -> str:
    """``jit(f)/jit(main)/transpose(jvp(layers))/while/body/attention/dot_general``
    → ``layers/while/body/attention``: the ``jax.named_scope`` path without
    the jit wrappers, the transformation wrappers (the backward pass of a
    phase is that phase) and the primitive's own name.  A name with no ``/``
    was made by the compiler and has no scope."""
    parts, depth, cur = [], 0, ""
    for ch in op_name:      # a scope may hold a "/": split outside brackets
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    parts.append(cur)
    if len(parts) < 2:
        return ""
    out = []
    for part in parts[:-1]:
        if part.startswith("jit(") or part.startswith("pjit("):
            continue
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part:
            out.append(part)
    return "/".join(out)


def parse_hlo_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: scope}) of a compiled program's
    ``as_text()``.  An instruction's scope is its own ``op_name``'s; a fusion
    that has none takes the commonest scope inside the computation it calls;
    what still has none (copies, async halves, tuples) takes its first
    operand's that has one.  "" = nobody's."""
    module = ""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    inside: Dict[str, Dict[str, int]] = {}
    comp = None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        scope = scope_of_op_name(meta.group(1)) if meta else ""
        own[name] = scope
        if scope and comp is not None:
            votes = inside.setdefault(comp, {})
            votes[scope] = votes.get(scope, 0) + 1
        body = line[m.end():]
        called = _CALLS.search(body)
        if called:
            calls[name] = called.group(1)
        operands[name] = _OPERAND.findall(body.split(", metadata=")[0])
    for name, scope in own.items():
        if not scope and calls.get(name) in inside:
            votes = inside[calls[name]]
            own[name] = max(votes, key=votes.get)

    def resolve(name: str, depth: int = 0) -> str:
        scope = own.get(name, "")
        if scope or depth > 8:
            return scope
        for operand in operands.get(name, ()):
            if operand != name and operand in own:
                scope = resolve(operand, depth + 1)
                if scope:
                    return scope
        return ""

    return module, {name: resolve(name) for name in own}


def time_by_scope(ops: Sequence[Tuple[str, float, float]],
                  scopes: Dict[str, str],
                  lo: float = float("-inf"), hi: float = float("inf"),
                  only: Optional[Callable[[str], bool]] = None
                  ) -> Dict[str, float]:
    """Own time of one lane's operations ``(instruction, start, dur)``
    grouped by scope ("" = no scope known), for the operations that lie
    inside ``[lo, hi]`` and pass ``only(instruction)``."""
    ordered = sorted(ops, key=lambda op: (op[1], -op[2]))
    out: Dict[str, float] = {}
    for (name, start, dur), own in zip(
            ordered, own_times([op[1:3] for op in ordered])):
        if own <= 0 or start < lo or start + dur > hi:
            continue
        if only is not None and not only(name):
            continue
        scope = scopes.get(name, "")
        out[scope] = out.get(scope, 0.0) + own
    return out


#: {program name: a callable giving the compiled program's text}.  A program
#: whose device operations should be attributable registers here (the train
#: engine does, for its step); nothing is compiled until somebody asks.
_STEP_TEXTS: Dict[str, Callable[[], Optional[str]]] = {}
_SCOPE_CACHE: Dict[str, Tuple[str, Dict[str, str]]] = {}


def register_step_text(name: str,
                       text_of: Callable[[], Optional[str]]) -> None:
    _STEP_TEXTS[name] = text_of
    _SCOPE_CACHE.pop(name, None)


def registered_scopes() -> Dict[str, Dict[str, str]]:
    """{XLA module name: {instruction: scope}} of every registered program
    that can still give its text (each parsed once)."""
    out: Dict[str, Dict[str, str]] = {}
    for name, text_of in list(_STEP_TEXTS.items()):
        if name not in _SCOPE_CACHE:
            text = text_of()
            if not text:
                continue
            _SCOPE_CACHE[name] = parse_hlo_scopes(text)
        module, scopes = _SCOPE_CACHE[name]
        out[module] = scopes
    return out
