"""An attribute of the LAST record named ``span`` on the program's own
tracer, wherever in the run it was written (a state the program reads once,
after the window: ``train/model_state``), times ``scale``.  No tracer, no
such record or no such attribute: no value.  args: span, value, scale."""
from lib import program_state


def read(run, args):
    got = program_state.last_record_attr(run, args["span"], args["value"])
    return None if got is None else got * args.get("scale", 1.0)
