"""Mean device time of one execution of the decode program,
`serving_step`, in the traced window, ms."""
from perf import trace_reduce


def read(record):
    t = record.get("trace")
    return trace_reduce.program_mean_ms(t, "jit_serving_step(") if t else None
