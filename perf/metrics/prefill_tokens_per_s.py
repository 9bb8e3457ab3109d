"""Prompt tokens prefilled in the window, by the engine's own chunk stamps,
over the window's seconds.  A stamp is taken when a chunk's program call
returns to the scheduler: the host has handed the chunk to the device, and
only a prompt's last chunk is ever waited for, so a stamp leads the chunk's
end on the device by up to the device's queue (one program).  Tokens that a
prefix-cache hit spared are in no chunk.

`serve_tokens_per_s` still places the prompts' chunks by interpolation
(`perf/work/served.py`); the line before the result holds both counts and
their difference, for the benchmark issue that moves it to the stamps."""
import json

from perf.work import ledger


def read(record):
    # the iteration under way at t_close holds stamps from before it
    got = ledger.window(record, "prefill_tokens_per_s", until=None)
    if got is None:
        return None
    stamps = [(n, t) for r in got[0] for _rid, _start, n, t in r.chunks
              if record["t_open"] <= t < record["t_close"]]
    stamped = sum(n for n, _t in stamps)
    placed = record["work"]["prompt_tokens"]
    print(json.dumps({"prefill_tokens_per_s": {
        "prompt_tokens_stamped": stamped, "chunks_stamped": len(stamps),
        "prompt_tokens_interpolated": placed,
        "chunks_interpolated": record["work"]["chunks"],
        "stamped_minus_interpolated": stamped - placed}}), flush=True)
    return stamped / record["window_s"] if stamps else None
