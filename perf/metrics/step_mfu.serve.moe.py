"""The served programs' share of the chip's bf16 peak over the window, for
a decoder of full and sliding-window attention layers with routed
feed-forwards of which this chip holds a share: forward operations of
every prompt and output token served in it (`perf/work/routed.py`: the
attention matrices by layer kind, the dense layer's feed-forward, the
routers, the token-expert pairs computed HERE as the program's ring counts
them, the head, the attention over the context with a window layer
counting min(context, window)) / window seconds / peak.  The share of the
whole step; small for decode by nature."""
from perf.work import ledger, routed


def read(record):
    work = record.get("work")
    if not work or not work["output_tokens"]:
        return None
    got = ledger.window(record, "step_mfu.serve.moe")
    if got is None:
        return None
    counts = routed.ring_counts(got[0], record["t_open"], record["t_close"])
    if counts is None:
        return None
    cfg = record["config"]
    windowed = routed.windowed_work(
        record["requests"], record["t_open"], record["t_close"],
        record["chunk"], cfg["sliding_window"])
    need = routed.serve_flops(cfg, work, windowed, counts["pairs"])
    return 100.0 * need / record["window_s"] / record["peak"]["flops_bf16"]
