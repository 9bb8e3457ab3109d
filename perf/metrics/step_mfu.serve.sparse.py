"""The served programs' share of the chip's bf16 peak over the window, for
a decoder whose attention layers attend a learned selection and whose
feed-forwards are routed over experts of which this chip holds a share:
forward operations of every prompt and output token served in it
(`perf/work/sparse.py`: the projections and the index's, the index scores
over the whole context, the attention over min(context, topk), the router,
the token-expert pairs computed HERE as the program's ring counts them,
the head) / window seconds / peak.  The share of the whole step."""
from perf.work import ledger, sparse


def read(record):
    work = record.get("work")
    if not work or not work["output_tokens"]:
        return None
    got = ledger.window(record, "step_mfu.serve.sparse")
    if got is None:
        return None
    counts = sparse.ring_counts(got[0], record["t_open"], record["t_close"])
    if counts is None:
        return None
    cfg = record["config"]
    selected = sparse.selected_work(
        record["requests"], record["t_open"], record["t_close"],
        record["chunk"], cfg["sa_config"]["topk"])
    need = sparse.serve_flops(cfg, work, selected, counts["pairs"])
    return 100.0 * need / record["window_s"] / record["peak"]["flops_bf16"]
