"""The paged-attention kernel's share of its roofline where query heads
share KV heads: as `paged_attn_roofline`, with the kernels found by their
names (so the programs may hold other kernels: `paged_attention`, a lane a
query, in the decode step; `paged_attention_window`, a chunk's queries
against each page once, in the prefill chunk, where the program has it)
and the keys and values counted by KV heads, in the attention layers alone
(`perf/work/hybrid.py`): least time to read, once a program, K and V at
the valid positions that the decode steps' lanes and the prefill chunks of
the traced window attended (which steps and chunks it held is counted from
the requests' own stamps by `perf/work/served.py`), or the products by
query heads over the peak, over the kernel's device time in the trace."""
import json

from perf.work import hybrid, served


def read(record):
    t = record.get("trace")
    if not t or "trace_t0" not in record:
        return None
    found = {name: hybrid.kernel_time(t, name)
             for name in ("paged_attention", "paged_attention_window")}
    if not any(found.values()):
        return None
    kernel = {k: sum(f[k] for f in found.values() if f)
              for k in ("seconds", "count")}
    work = served.count_work(record["requests"], record["trace_t0"],
                             record["trace_t1"], record["chunk"])
    reads = work["decode_context"] + work["chunk_context"]
    if not reads:
        return None
    floor = hybrid.paged_floor_seconds(
        reads, work["decode_context"] + work["prefill_context"],
        record["config"], record["peak"])
    print(json.dumps({"paged_attn_roofline.grouped": {
        "kernel_s": kernel["seconds"], "kernel_calls": kernel["count"],
        "by_kernel": found, "floor_s": floor,
        "chunks_counted": work["chunks"],
        "decode_tokens_counted": work["decode_tokens"],
        "decode_positions": work["decode_context"],
        "chunk_positions": work["chunk_context"]}}), flush=True)
    return 100.0 * floor / kernel["seconds"]
