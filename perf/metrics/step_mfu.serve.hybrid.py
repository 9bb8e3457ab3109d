"""The served programs' share of the chip's bf16 peak over the window, for
a decoder of Mamba and attention layers: forward operations of every
prompt and output token served in it (`perf/work/hybrid.py`: the matrices
by layer kind, the scans, the head, the attention over the context) /
window seconds / peak.  The share of the whole step; small for decode by
nature."""
from perf.work import hybrid


def read(record):
    work = record.get("work")
    if not work or not work["output_tokens"]:
        return None
    need = hybrid.serve_flops(record["config"], work)
    return 100.0 * need / record["window_s"] / record["peak"]["flops_bf16"]
