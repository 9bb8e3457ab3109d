"""The paged-attention kernel's share of its roofline inside the served
programs (decode steps and prefill chunks together): least time to read,
once a program, the keys and values at the valid positions that the decode
steps' lanes and the prefill chunks of the traced window attended
(`perf/work/paged.py`; which steps and chunks the window held is counted
from the requests' own stamps by `perf/work/served.py`) over the kernel's
device time in the trace.

Today's build gives its `pallas_call` no name; inside the served programs
the paged kernel is the only `tpu_custom_call`, and that is how it is found
until the tracing issue names it."""
import json

from perf import trace_reduce
from perf.work import paged, served


def read(record):
    t = record.get("trace")
    if not t or "trace_t0" not in record:
        return None
    kernel = trace_reduce.ops_matching(t, "tpu_custom_call")
    if not kernel:
        return None
    spent = kernel["seconds"]
    work = served.count_work(record["requests"], record["trace_t0"],
                             record["trace_t1"], record["chunk"])
    reads = work["decode_context"] + work["chunk_context"]
    if not reads:
        return None
    floor = paged.floor_seconds(
        reads, work["decode_context"] + work["prefill_context"],
        record["config"], record["peak"])
    # the counted chunks stand beside the prefill programs the trace saw
    print(json.dumps({"paged_attn_roofline": {
        "kernel_s": spent, "floor_s": floor, "chunks_counted": work["chunks"],
        "decode_tokens_counted": work["decode_tokens"],
        "decode_positions": work["decode_context"],
        "chunk_positions": work["chunk_context"]}}), flush=True)
    return 100.0 * floor / spent
