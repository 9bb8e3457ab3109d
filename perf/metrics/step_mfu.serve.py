"""The served programs' share of the chip's bf16 peak over the window:
forward operations of every prompt and output token served in it
(`perf/work/flops.py`) / window seconds / peak.  Small for decode by
nature; it is what still bounds a claim once a kernel is replaced."""
from perf.work import flops


def read(record):
    work = record.get("work")
    if not work or not work["output_tokens"]:
        return None
    need = flops.serve_flops(record["config"], work)
    return 100.0 * need / record["window_s"] / record["peak"]["flops_bf16"]
