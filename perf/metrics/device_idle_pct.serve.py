"""Share of the traced window in which no operation ran on the device."""
from perf import trace_reduce


def read(record):
    t = record.get("trace")
    return trace_reduce.idle_pct(t) if t else None
