"""The index-scores kernel's share of its roofline inside the served
programs: least time for the positions the traced slice's queries scored
over the device time of the operations named `index_scores`.

The least time (`perf/work/sparse.py`): the index key, 64 lanes of bf16, of
every position scored, read once a decode lane and step and once a chunk,
in every layer; or the scores' products over the peak, whichever is longer.
Which steps and chunks the slice held is counted from the requests' own
stamps (`perf/work/served.py`).  A kernel that reads a wider row than the
key reads low here."""
import json

from perf.work import hybrid, served, sparse


def read(record):
    t = record.get("trace")
    if not t or "trace_t0" not in record:
        return None
    kernel = hybrid.kernel_time(t, "index_scores")
    if not kernel:
        return None
    work = served.count_work(record["requests"], record["trace_t0"],
                             record["trace_t1"], record["chunk"])
    if not work["decode_context"] + work["chunk_context"]:
        return None
    floor = sparse.index_floor_seconds(record["config"], work, record["peak"])
    print(json.dumps({"index_scores_roofline": {
        "kernel_s": kernel["seconds"], "kernel_calls": kernel["count"],
        "floor_s": floor, "chunks_counted": work["chunks"],
        "decode_tokens_counted": work["decode_tokens"],
        "keys_read": work["decode_context"] + work["chunk_context"],
        "positions_scored": work["decode_context"]
        + work["prefill_context"]}}), flush=True)
    return 100.0 * floor / kernel["seconds"]
