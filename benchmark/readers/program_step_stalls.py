"""Seconds of the window lost to stalled steps, from the ring alone
(``deepspeed_tpu.telemetry.get_tracer()``: a traced and an untraced run read
alike), and ``stall_account.json``, which names each one.

args: ``step`` (the span that delimits a step: ``serve/step`` or
``engine/train_batch``), ``part`` ("all" | "wait" | "client"), ``gaps``
(default true: judge the gaps between steps too; false where the client
waits there by design, an open loop between arrivals).

**Classes.**  The ``step`` spans that END in the window are classed by what
they did: their ``kind``, the size of the work (the ``steps`` of the
``serve/window`` inside, the ``bucket`` of the ``engine/put`` inside) and the
decode steps they drained (the ``steps`` of every ``engine/window_wait``
inside: since PR 53 a window's drain may lie in ANY later step, and a
prefill step that waits out an 8-step window is not a slow prefill step),
and whether they ``fetched`` (a ``serve/logits_fetch`` inside: a prompt's
last chunk waits for the device, the chunks before it only enqueue — 2 ms
against 150 ms in the long-context cell, my chip run, PR 57) ``behind`` how
many bucket tokens enqueued since the thread last waited for the device (the
fetch waits for those chunks too: 42 ms against 170–300 ms in the prefill
cell).
Train steps are one class.  The gaps between consecutive steps of one thread
are one class.

**Stalled** is a step (or gap) that took more than ``FACTOR`` x its class's
median and at least ``FLOOR_S`` more; its **excess** over the median is the
time lost.  A class of fewer than ``MIN_CLASS`` steps has no median to speak
of: its steps are judged against the LARGEST median among the classes of
their kind that have one (a lower bound of the loss, and no false alarm from
a rare shape); a kind with no such class is not judged (``unjudged`` in the
account).

**Owner.**  Each span inside a stalled step has an excess of its own, over
the median of its name over the steps of the class (0 where most lack it).  The step's excess is owned by
the innermost ``serve/*`` / ``engine/*`` span whose own excess is at least
half of it — ``engine/window_wait``, ``engine/step_wait``,
``serve/logits_fetch``: below the host (the runtime or the chip), ``part:
wait``; ``engine/host_gc``: the collector; ``engine/decode_launch``: the
enqueue blocked — else by the step itself (host code under no span).  A
stalled gap is the client's (``part: client``) whatever ran inside it.

``stall_account.json`` (``<checkout>/.bench_trace/<cell>/``, made if the run
left none; the cell is ``--workload`` of the command line): the classes with
their counts and medians, and one entry a stalled step or gap — offset in the
window, duration, class, median, excess, owner and its attributes, the
step's ``key`` / ``steps`` / ``bucket``, ``cpu_s`` / ``nvcsw`` / ``nivcsw``
(wall far over CPU with involuntary switches: descheduled; no CPU: blocked
below Python; CPU = wall: Python ran that long), the ``engine/host_gc``
seconds and the ``compile/*`` records inside it.  One line on stderr for
each.

**What the device did meanwhile** (a traced run only).  Where a stall
overlaps the traced slice, its entry also says what the first device did
over the overlap: ``device_overlap_s``, ``device_busy_s``,
``device_idle_s`` and ``device_longest_op`` (label, opcode, seconds) — busy
under one long operation: a slow program; idle: the runtime or the link held
the thread, not the chip.  The ring's clock is laid on the profile's by the
``step`` spans themselves, which are in both (``lib/program_trace.xplane``'s
host events); no entry gets these keys where the two do not line up or
nothing overlaps.  ``windows_held_by``: the window's
``serve/window`` spans counted by their ``held_by`` (``ahead`` for ""), the
whole table of which ``window_held_share.*`` report three rows.

No ``step`` span anywhere in the ring: no value.  A ring that dropped
records of the window: no value.  Never raises for what a program lacks.
"""
import bisect
import json
import os
import sys

from lib import program_trace, stats, trace as trace_lib

FACTOR = 3.0
FLOOR_S = 0.050
MIN_CLASS = 3
#: owners below the host: the runtime or the chip held the thread
WAIT_SPANS = ("engine/window_wait", "engine/step_wait", "serve/logits_fetch")
#: records of a REQUEST's life (explicit start and duration, ``Tracer.record``),
#: not of the thread's time: never an owner
NOT_THE_THREADS = ("serve/queue_wait", "serve/first_token")


def _dropped() -> int:
    try:
        from deepspeed_tpu.telemetry import get_tracer
    except ImportError:
        return 0
    return get_tracer().dropped


def _threshold(median: float) -> float:
    return max(FACTOR * median, median + FLOOR_S)


def _inside(thread, starts, lo, hi, skip=None):
    """The thread's spans that lie within [lo, hi], with their nesting depth
    there (by containment: the ring's tuples carry no depth)."""
    out, open_ends = [], []
    i = bisect.bisect_left(starts, lo)
    rows = []
    while i < len(thread) and thread[i][1] <= hi:
        sp = thread[i]
        if sp is not skip and sp[1] + sp[2] <= hi + 1e-9:
            rows.append(sp)
        i += 1
    for sp in sorted(rows, key=lambda r: (r[1], -r[2])):
        while open_ends and open_ends[-1] < sp[1] + sp[2] - 1e-9:
            open_ends.pop()
        out.append((len(open_ends), sp))
        open_ends.append(sp[1] + sp[2])
    return out


def _class_of(step, inside, behind):
    """(kind, label, tokens enqueued and not waited for after this step).
    ``behind``: the same before it."""
    kind = step[3].get("kind", "step")
    size = drained = None
    fetched = False
    for _, sp in inside:
        if sp[0] == "serve/window" and size is None:
            size = ("steps", sp[3].get("steps"))
        elif sp[0] == "engine/put" and size is None:
            size = ("bucket", sp[3].get("bucket"))
        elif sp[0] == "engine/window_wait":
            drained = (drained or 0) + int(sp[3].get("steps", 0))
        elif sp[0] == "serve/logits_fetch":
            fetched = True
    label = str(kind)
    if size is not None:
        label += f" {size[0]}={size[1]}"
    if drained is not None:
        label += f" drained={drained}"
    if fetched:
        label += " fetched" + (f" behind={behind}" if behind else "")
    if fetched or drained is not None:
        behind = 0                  # the device's queue was waited out
    elif size is not None and size[0] == "bucket":
        behind += int(size[1] or 0)
    return kind, label, behind


def _owner(inside, medians, excess):
    """(name, attrs) of the innermost program span that holds at least half
    of ``excess`` over its name's median, or None."""
    best = None
    for depth, sp in inside:
        if not program_trace.PROGRAM_SPAN.match(sp[0]) \
                or sp[0] in NOT_THE_THREADS:
            continue
        own = sp[2] - medians.get(sp[0], 0.0)
        if own >= excess / 2.0 and (best is None or (depth, own) > best[0]):
            best = ((depth, own), sp)
    return None if best is None else (best[1][0], best[1][3])


def _name_medians(members):
    """By span name, the median over the class's steps of the name's longest
    instance in a step (0 in a step without it: a span that most steps lack,
    a collector's pause, has a median of 0)."""
    by_name = {}
    for _, inside in members:
        longest = {}
        for _, sp in inside:
            longest[sp[0]] = max(longest.get(sp[0], 0.0), sp[2])
        for name, dur in longest.items():
            by_name.setdefault(name, []).append(dur)
    return {name: stats.percentile(durs + [0.0] * (len(members) - len(durs)),
                                   50.0) for name, durs in by_name.items()}


def _entry(what, lo, t0, dur, label, n, median, inside, medians, step=None):
    excess = dur - median
    found = _owner(inside, medians, excess)
    entry = {
        "what": what, "offset_s": t0 - lo, "dur_s": dur, "class": label,
        "class_n": n, "median_s": median, "excess_s": excess,
        "owner": found[0] if found else
        (step[0] if step is not None else "client"),
        "owner_attrs": {k: v for k, v in (found[1] if found else {}).items()
                        if isinstance(v, (int, float, str, bool))},
        "host_gc_s": sum(sp[2] for _, sp in inside
                         if sp[0] == "engine/host_gc"),
        "compiles": [{"name": sp[0], "dur_s": sp[2],
                      "program": sp[3].get("program")}
                     for _, sp in inside if sp[0].startswith("compile/")]}
    for _, sp in inside:
        for name, key in (("engine/decode_dispatch", "key"),
                          ("serve/window", "steps"), ("engine/put", "bucket")):
            if sp[0] == name and key in sp[3]:
                entry.setdefault(key, sp[3][key])
    if step is not None:
        for key in ("cpu_s", "nvcsw", "nivcsw"):
            if key in step[3]:
                entry[key] = step[3][key]
    return entry


def analyse(spans, lo, hi, step_name, gaps=True):
    """{"classes", "unjudged", "stalls"} of the ``step_name`` steps that end
    in [lo, hi), and of the gaps between them if ``gaps``; None where no
    step does."""
    steps = program_trace.in_window(spans, lo, hi, {step_name})
    if not steps:
        return None
    threads = {}
    for sp in spans:
        threads.setdefault(sp[4], []).append(sp)
    starts = {tid: [sp[1] for sp in rows] for tid, rows in threads.items()}

    classes, kinds, behind = {}, {}, {}
    for step in steps:              # oldest first, as the ring is
        tid = step[4]
        inside = _inside(threads[tid], starts[tid], step[1],
                         step[1] + step[2], skip=step)
        kind, label, behind[tid] = _class_of(step, inside,
                                             behind.get(tid, 0))
        classes.setdefault(label, []).append((step, inside))
        kinds[label] = kind
    median = {label: stats.percentile([m[0][2] for m in members], 50.0)
              for label, members in classes.items()}
    # a class too small for a median borrows its kind's largest
    judged_by = {}
    for label, members in classes.items():
        if len(members) >= MIN_CLASS:
            judged_by[label] = label
            continue
        peers = [other for other in classes if kinds[other] == kinds[label]
                 and len(classes[other]) >= MIN_CLASS]
        judged_by[label] = max(peers, key=median.get) if peers else None

    stalls, unjudged, name_medians = [], 0, {}
    for label, members in classes.items():
        judge = judged_by[label]
        if judge is None:
            unjudged += len(members)
            continue
        for step, inside in members:
            if step[2] <= _threshold(median[judge]):
                continue
            if judge not in name_medians:
                name_medians[judge] = _name_medians(classes[judge])
            stalls.append(_entry(
                "step", lo, step[1], step[2], label, len(members),
                median[judge], inside, name_medians[judge], step=step))

    # the gaps between consecutive steps of a thread: one class
    between = []
    by_tid = {}
    for step in steps if gaps else ():
        by_tid.setdefault(step[4], []).append(step)
    for tid, rows in by_tid.items():
        rows.sort(key=lambda sp: sp[1])
        between += [(a[1] + a[2], b[1] - a[1] - a[2], tid)
                    for a, b in zip(rows, rows[1:])]
    gap_median = stats.percentile([g[1] for g in between], 50.0) \
        if len(between) >= MIN_CLASS else None
    if gap_median is not None and any(g[1] > _threshold(gap_median)
                                      for g in between):
        every = [(gap, _inside(threads[gap[2]], starts[gap[2]], gap[0],
                               gap[0] + gap[1])) for gap in between]
        medians = _name_medians(every)
        stalls += [_entry("gap", lo, t0, dur, "gap", len(between),
                          gap_median, inside, medians)
                   for (t0, dur, _), inside in every
                   if dur > _threshold(gap_median)]
    stalls.sort(key=lambda e: e["offset_s"])
    held = {}       # the window's ``serve/window`` spans by why they waited
    for sp in program_trace.in_window(spans, lo, hi, {"serve/window"}):
        if "held_by" in sp[3]:
            why = sp[3]["held_by"] or "ahead"
            held[why] = held.get(why, 0) + 1
    return {
        "step": step_name, "window_s": hi - lo, "steps": len(steps),
        "unjudged": unjudged, "windows_held_by": held,
        "classes": {label: {"n": len(members), "median_s": median[label],
                            "judged_by": judged_by[label]}
                    for label, members in sorted(classes.items())},
        "gaps": {"judged": bool(gaps), "n": len(between),
                 "median_s": gap_median},
        "stalls": stalls}


def _profile_offset(spans, host, step_name, slice_lo):
    """Seconds to add to a ring time (``perf_counter``) to get the profile's
    clock, from the ``step_name`` spans that are in both: the profile's k-th
    is the ring's k-th from the slice's start on, but for a few that were
    open when it started.  None where no such pairing agrees to 1 ms."""
    events = [ev for ev in host if ev[0] == step_name]
    steps = [sp for sp in spans if sp[0] == step_name
             and sp[1] + sp[2] > slice_lo]
    for skip in range(4):
        pairs = list(zip(events, steps[skip:]))
        if not pairs:
            return None
        offsets = [ev[1] / 1e9 - sp[1] for ev, sp in pairs]
        if max(offsets) - min(offsets) < 1e-3:
            return stats.percentile(offsets, 50.0)
    return None


def device_meanwhile(run, found, spans) -> None:
    """Give each stall of a traced run that overlaps the traced slice what
    the first device did over the overlap; never raises."""
    try:
        trace = run.get("trace")
        if not trace or not found["stalls"]:
            return
        extra = program_trace.xplane(run)
        win = trace_lib.window_of(trace)
        devices = [ops for _, ops in sorted(trace["device"].items()) if ops]
        if extra is None or win is None or not devices:
            return
        offset = _profile_offset(spans, extra["host"], found["step"],
                                 run.get("slice", run["window"])[0])
        if offset is None:
            return
        ops, lo = devices[0], run["window"][0]
        busy = trace_lib.op_intervals(ops)
        for e in found["stalls"]:
            a = max((lo + e["offset_s"] + offset) * 1e9, win[0])
            b = min((lo + e["offset_s"] + e["dur_s"] + offset) * 1e9, win[1])
            if b <= a:
                continue
            busy_ns = trace_lib.total(trace_lib.clip(busy, a, b))
            e["device_overlap_s"] = (b - a) / 1e9
            e["device_busy_s"] = busy_ns / 1e9
            e["device_idle_s"] = (b - a - busy_ns) / 1e9
            # its own time, so that a loop does not stand for its body
            inside = [op for op in ops if op[1] < b and op[1] + op[2] > a]
            if inside:
                op = max(inside, key=lambda op: min(
                    op[5], min(op[1] + op[2], b) - max(op[1], a)))
                e["device_longest_op"] = {
                    "label": op[3], "opcode": op[4], "dur_s": op[2] / 1e9}
    except Exception as exc:  # noqa: BLE001 — four keys less, not a run
        print(f"program_step_stalls: no device account: {exc!r}",
              flush=True, file=sys.stderr)


def _cell_dir():
    """``<checkout>/.bench_trace/<--workload>``; None without the flag."""
    argv = sys.argv
    for i, word in enumerate(argv):
        if word == "--workload" and i + 1 < len(argv):
            name = argv[i + 1]
        elif word.startswith("--workload="):
            name = word.split("=", 1)[1]
        else:
            continue
        return os.path.join(program_trace.ROOT, ".bench_trace", name)
    return None


def write_account(found) -> None:
    """``stall_account.json`` and a line a stall on stderr; never raises."""
    try:
        for e in found["stalls"]:
            facts = " ".join(f"{k}={e[k]:.3f}" if isinstance(e[k], float)
                             else f"{k}={e[k]}" for k in
                             ("cpu_s", "nvcsw", "nivcsw") if k in e)
            print(f"program_step_stalls: {e['what']} at +{e['offset_s']:.3f}"
                  f" s took {e['dur_s']:.3f} s (class {e['class']!r}, median"
                  f" {e['median_s']:.3f} s): {e['excess_s']:.3f} s lost in "
                  f"{e['owner']} {facts} host_gc_s={e['host_gc_s']:.3f} "
                  f"compiles={len(e['compiles'])}" + (
                      f" device_busy_s={e['device_busy_s']:.3f} of "
                      f"{e['device_overlap_s']:.3f} traced"
                      if "device_busy_s" in e else ""), flush=True,
                  file=sys.stderr)
        where = _cell_dir()
        if where is None:
            return
        os.makedirs(where, exist_ok=True)
        with open(os.path.join(where, "stall_account.json"), "w") as f:
            json.dump(found, f, indent=1)
    except Exception as exc:  # noqa: BLE001 — a table less, not a run
        print(f"program_step_stalls: no stall_account.json: {exc!r}",
              flush=True, file=sys.stderr)


def read(run, args):
    spans = program_trace.ring(run)
    if spans is None:
        return None
    step_name = args["step"]
    if not any(sp[0] == step_name for sp in spans):
        return None                 # the program has no such span
    lo, hi = run["window"]
    if _dropped() and spans[0][1] > lo:
        return None                 # the ring lost part of the window
    # once a run, kept with the run: ``run.py`` loads a reader's module
    # anew for every metric, so the module can keep nothing
    done = run.setdefault("step_stalls", {})
    key = step_name if args.get("gaps", True) else step_name + ", no gaps"
    if key not in done:
        done[key] = analyse(spans, lo, hi, step_name,
                            gaps=args.get("gaps", True))
        if done[key] is not None:
            device_meanwhile(run, done[key], spans)
            write_account(done[key])
    found = done[key]
    if found is None:
        return 0.0                  # steps, but none in the window
    part = args.get("part", "all")
    total = 0.0
    for e in found["stalls"]:
        if e["what"] == "gap":
            if part in ("client", "all"):
                total += e["excess_s"]
        elif part == "all" or (part == "wait" and e["owner"] in WAIT_SPANS):
            total += e["excess_s"]
    return total
