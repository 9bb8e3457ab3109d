"""Mean device time of one execution of the prefill program (one chunk of one prompt),
`serving_prefill_chunk`, in the traced window, ms."""
from perf import trace_reduce


def read(record):
    t = record.get("trace")
    return trace_reduce.program_mean_ms(t, "jit_serving_prefill_chunk(") if t else None
