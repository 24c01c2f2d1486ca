"""Share of the HBM roof the sparse-attention path of the decode steps
reaches in the traced slice: the bytes their queries MUST move
(``lib/flops_sparse``: every cached index key of the query's sequence and the
K/V rows of the tokens it keeps, from the program's own counters on
``engine/window_account``, whatever implements the path) over the chip's HBM
bandwidth, over the device time under the path's name scopes
(``attention/index_qk | index_score | index_select | sparse_read |
sparse_core``) in the slice's programs.  The bytes bound the path: at 2-10 FLOP a
byte the arithmetic is far from the bf16 peak.  A program without the scopes
or the counters (the parent of the PR that added them): no value."""
import re

from lib import flops_sparse, program_trace
from readers import serve_scope_share

SCOPES = re.compile(r"(^|/)attention/(index_(qk|score|select)|sparse_(read|core))")


def read(run, args):
    if run.get("trace") is None or run.get("peaks") is None \
            or "indexer_topk" not in run["sizes"]:
        return None
    per_dev = serve_scope_share.rows_by_device(run)
    spans = program_trace.ring(run)
    if not per_dev or spans is None:
        return None
    seconds = sum(own for rows in per_dev.values() for scope, own in rows
                  if scope is not None and SCOPES.search(scope)) \
        / len(per_dev) / 1e9
    lo, hi = run["slice"]
    # the windows drained inside the slice (a window that began before it
    # counts whole, one that ends after it not at all: a window in ~40)
    accounts = [sp[3] for sp in program_trace.in_window(
        spans, lo, hi, {"engine/window_account"})
        if "sparse_tokens_scored" in sp[3]]
    scored = sum(float(a["sparse_tokens_scored"]) for a in accounts)
    selected = sum(float(a["sparse_tokens_selected"]) for a in accounts)
    if seconds <= 0 or scored <= 0:
        return None
    least = flops_sparse.sparse_bytes(run["sizes"], scored, selected) \
        / run["peaks"].hbm_bytes_per_s
    return 100.0 * least / seconds
