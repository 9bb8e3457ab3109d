"""The selective-scan kernel's share of its roofline inside the served
programs: least time for the scans of the traced window over the device
time of the operations named `selective_scan`.

The least time (`perf/work/hybrid.py`): every program reads and writes
once the float32 state of each lane it advances and streams u, dt, B, C,
z, y of its tokens, in every Mamba layer; or the recurrence's operations
over the peak, whichever is longer.  Which tokens the window held is
counted from the requests' own stamps (`perf/work/served.py`): a prefill
chunk advances one lane by its tokens, a decode step every live lane by
one.  Decode tokens count only if the step runs the kernel: that is read
off the trace, by how many kernel calls stand beside how many programs (a
program calls it once a Mamba layer).  A program without the kernel reads
nothing here."""
import json

from perf.work import hybrid, served


def read(record):
    t = record.get("trace")
    if not t or "trace_t0" not in record:
        return None
    kernel = hybrid.kernel_time(t, "selective_scan")
    if not kernel:
        return None
    cfg = record["config"]
    work = served.count_work(record["requests"], record["trace_t0"],
                             record["trace_t1"], record["chunk"])
    n_ssm = hybrid.layer_counts(cfg)["ssm"]
    chunks = hybrid.programs_run(t, "jit_serving_prefill_chunk(")
    steps = hybrid.programs_run(t, "jit_serving_step(")
    # calls = n_ssm x (programs that hold the kernel)
    in_step = kernel["count"] > n_ssm * (chunks + 0.5 * steps)
    tokens = work["prompt_tokens"] + (work["decode_tokens"] if in_step else 0)
    lanes = work["chunks"] + (work["decode_tokens"] if in_step else 0)
    if not tokens:
        return None
    floor = hybrid.scan_floor_seconds(cfg, tokens, lanes, record["peak"])
    print(json.dumps({"selective_scan_roofline": {
        "kernel_s": kernel["seconds"], "kernel_calls": kernel["count"],
        "floor_s": floor, "kernel_in_step": bool(in_step),
        "chunk_programs": chunks, "step_programs": steps,
        "chunks_counted": work["chunks"],
        "prompt_tokens_counted": work["prompt_tokens"],
        "decode_tokens_counted": work["decode_tokens"],
        "bytes": hybrid.scan_bytes(cfg, tokens, lanes)}}), flush=True)
    return 100.0 * floor / kernel["seconds"]
