"""The routed experts' kernel's share of its roofline inside the served
programs: least time for the token-expert pairs of the traced slice over
the device time of the operations named `moe_experts`.

The least time (`perf/work/routed.py`): every program (a decode step, a
prefill chunk; counted off the trace) reads once the three matrices of
each held expert that a pair chose, in every routed layer, and each pair's
activation in and out; or the pairs' products over the peak, whichever is
longer.  The pairs are the program's own count (`expert_pairs` of the
ring's iterations committed in the slice).  A program without the kernel,
or without the count, reads nothing here."""
import json

from perf.work import hybrid, ledger, routed


def read(record):
    t = record.get("trace")
    if not t or "trace_t0" not in record:
        return None
    kernel = hybrid.kernel_time(t, "moe_experts")
    if not kernel:
        return None
    got = ledger.window(record, "moe_experts_roofline", until=None)
    if got is None:
        return None
    counts = routed.ring_counts(got[0], record["trace_t0"],
                                record["trace_t1"])
    if counts is None or not counts["pairs"]:
        return None
    cfg = record["config"]
    programs = hybrid.programs_run(t, "jit_serving_prefill_chunk(") \
        + hybrid.programs_run(t, "jit_serving_step(")
    floor = routed.experts_floor_seconds(cfg, counts["pairs"], programs,
                                         record["peak"])
    print(json.dumps({"moe_experts_roofline": {
        "kernel_s": kernel["seconds"], "kernel_calls": kernel["count"],
        "floor_s": floor, "pairs": counts["pairs"], "programs": programs,
        "bytes": routed.experts_bytes(cfg, counts["pairs"], programs)}}),
        flush=True)
    return 100.0 * floor / kernel["seconds"]
