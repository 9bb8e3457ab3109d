"""The sparse attention kernel's share of its roofline inside the served
programs: least time for the positions the traced slice's queries attended
over the device time of the operations named `paged_attention_sparse`.

The least time (`perf/work/sparse.py`): the K and V rows of min(context,
topk) positions a decode lane and layer, and for a chunk the smaller of its
queries' selections and its context once; or the selected products over
the peak, whichever is longer.  An implementation that reads the whole
context where `topk` rows would do reads low here: that is the finding,
not a fault of this reader."""
import json

from perf.work import hybrid, sparse


def read(record):
    t = record.get("trace")
    if not t or "trace_t0" not in record:
        return None
    kernel = hybrid.kernel_time(t, "paged_attention_sparse")
    if not kernel:
        return None
    cfg = record["config"]
    selected = sparse.selected_work(
        record["requests"], record["trace_t0"], record["trace_t1"],
        record["chunk"], cfg["sa_config"]["topk"])
    if not selected["decode_context"] + selected["chunk_rows"]:
        return None
    floor = sparse.sparse_floor_seconds(cfg, selected, record["peak"])
    print(json.dumps({"sparse_attn_roofline": {
        "kernel_s": kernel["seconds"], "kernel_calls": kernel["count"],
        "floor_s": floor, "decode_rows": selected["decode_context"],
        "chunk_rows": selected["chunk_rows"],
        "selected_products": selected["decode_context"]
        + selected["prefill_context"]}}), flush=True)
    return 100.0 * floor / kernel["seconds"]
