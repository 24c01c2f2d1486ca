"""Own device time, inside the traced slice, of the operations whose
``jax.named_scope`` path matches ``scope`` (and not ``not_scope``) and whose
opcode matches ``opcode`` — as a share of the device's busy time (``over``:
"busy") or of the slice ("slice"), mean over devices.  The scope of an
operation comes from the compiled step's text, which the program registers
(``deepspeed_tpu/profiling/xprof_parse.py``: the TPU's profile carries no
``op_name``); a cell whose program registered none reads nothing.
args: scope, not_scope, opcode, over."""
import re

from lib import program_trace, trace


def read(run, args):
    per_dev = program_trace.ops_with_scope(run)
    busy = trace.busy(run["trace"]) if run.get("trace") else None
    if not per_dev or busy is None:
        return None
    scope = re.compile(args["scope"])
    not_scope = re.compile(args["not_scope"]) if args.get("not_scope") \
        else None
    opcode = re.compile(args["opcode"]) if args.get("opcode") else None
    total = 0.0
    for rows in per_dev.values():
        for op_scope, op_code, own, _ in rows:
            if op_scope is None or not scope.search(op_scope):
                continue
            if not_scope is not None and not_scope.search(op_scope):
                continue
            if opcode is not None and not opcode.search(op_code):
                continue
            total += own
    over = busy["busy_s"] if args.get("over", "busy") == "busy" \
        else busy["window_s"]
    if over <= 0:
        return None
    return total / len(per_dev) / 1e9 / over
