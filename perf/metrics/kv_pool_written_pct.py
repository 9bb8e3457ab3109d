"""Share of the KV pool's positions that hold a key and a value, mean over
the window's scheduler iterations: at each decode step's commit the engine
counts the positions its occupied lanes have written (`positions_written`:
a decoding lane's position, a prefilling lane's next chunk start) over
`blocks_total x block_size`.  The share the lanes had RESERVED (whole
blocks, prompt and output, taken at admission) stands on the line before
the result: what lies between the two is pool that waits for tokens."""
import json

from perf.work import ledger


def read(record):
    got = ledger.window(record, "kv_pool_written_pct")
    if got is None:
        return None
    records = [r for r in got[0] if r.blocks_total and r.block_size]
    if not records:
        return None
    written = [r.positions_written / (r.blocks_total * r.block_size)
               for r in records]
    reserved = [r.blocks_reserved / r.blocks_total for r in records]
    print(json.dumps({"kv_pool_written_pct": {
        "iterations": len(records),
        "written_pct_min_max": [100 * min(written), 100 * max(written)],
        "reserved_pct_mean": 100 * sum(reserved) / len(records)}}),
        flush=True)
    return 100.0 * sum(written) / len(written)
