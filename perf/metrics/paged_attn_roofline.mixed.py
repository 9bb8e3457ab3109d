"""The paged-attention kernel's share of its roofline where full-attention
and sliding-window layers are mixed: as `paged_attn_roofline.grouped`, the
kernels found by their names (`paged_attention`, and
`paged_attention_window` where a program has it), with the keys and values
counted by layer kind (`perf/work/routed.py`): a full layer's K and V at
every valid position a decode lane or a prefill chunk of the traced slice
attended, by its KV heads; a window layer's at min(context, window)
positions of each decode lane.  A window layer's chunk attends its own keys
where they are computed and reads no page through these kernels, so it
counts nothing here.  Which steps and chunks the slice held is counted
from the requests' own stamps (`perf/work/served.py`)."""
import json

from perf.work import hybrid, routed, served


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
    cfg = record["config"]
    lo, hi = record["trace_t0"], record["trace_t1"]
    work = served.count_work(record["requests"], lo, hi, record["chunk"])
    win = routed.windowed_work(record["requests"], lo, hi, record["chunk"],
                               cfg["sliding_window"])
    reads = work["decode_context"] + work["chunk_context"]
    if not reads:
        return None
    floor = routed.paged_floor_seconds(
        reads, win["decode_context"],
        work["decode_context"] + work["prefill_context"],
        win["decode_context"], cfg, record["peak"])
    print(json.dumps({"paged_attn_roofline.mixed": {
        "kernel_s": kernel["seconds"], "kernel_calls": kernel["count"],
        "by_kernel": found, "floor_s": floor,
        "chunks_counted": work["chunks"],
        "decode_tokens_counted": work["decode_tokens"],
        "full_positions": reads,
        "window_positions": win["decode_context"]}}), flush=True)
    return 100.0 * floor / kernel["seconds"]
