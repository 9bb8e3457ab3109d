"""The window of a served run as the program's own scheduler saw it: the
iteration records of `incubator_mxnet_tpu.telemetry.profiler`'s ring.

An iteration runs from one decode step's commit to the next (`t0`, `t1` on
`time.monotonic()`, the clock of `record["t_open"]` and `["t_close"]`) and
holds the scheduler thread's seconds by cause, the lanes' hold on the KV
pool at the commit, and a stamp for every prefill chunk committed in it.
The ring outlives the engine, so the readers under `perf/metrics/` call
this after the driver has released it; it holds 32,768 records, so a driver
whose run goes on long after the window copies what they will read while
the ring still holds it (`read_ring`).  A program without such a ring (or
with its ledger switched off) gives None, and the reason on a line of its
own: a reader then leaves its metric out.
"""
from __future__ import annotations

import json

# the scheduler thread's time that is neither a wait for the device
# (`device_step`, `prefill_chunk`, `draft_step`, `verify_step`: the fault
# hook and the blocking fetch) nor an idle poll (`wait`)
HOST_CAUSES = ("lock_wait", "bookkeeping", "commit", "gather_params",
               "dispatch", "gc", "host_other")


def read_ring(since: float):
    """`(records, held, causes)`: the ring's records whose commit lies at
    or after `since`, whether the ring still held `since`, and the names of
    a record's `causes`, in order.  A string, the reason, where the program
    keeps no ring."""
    try:
        from incubator_mxnet_tpu.telemetry import profiler

        iterations, causes = profiler.iterations, profiler.CAUSES
    except (ImportError, AttributeError) as e:
        return f"the program keeps no ring of iterations: {e}"
    records, held = iterations(since, None)
    return records, held, tuple(causes)


def window(record: dict, metric: str, until: str = "t_close"):
    """`(records, causes)`: the ring's records whose commit lies in
    `[t_open, record[until])` (`until=None`: to the ring's end), and the
    names of their `causes`, in order.  They come from `record["ring"]`,
    the copy a driver took with `read_ring` before the ring could lose the
    window (a traced run serves on through the trace and the profiler's
    stall), else from the ring as it stands.  None, with the reason
    printed, where the program has no ring, the ring is empty, or it no
    longer held `t_open`: never a part of the window."""
    def nothing(why):
        print(json.dumps({metric: {"left_out": why}}), flush=True)

    got = record.get("ring") or read_ring(record["t_open"])
    if isinstance(got, str):
        return nothing(got)
    records, held, causes = got
    if until:
        records = [r for r in records if r.t1 < record[until]]
    if not records:
        return nothing("the ring holds no iteration of the window (is the "
                       "ledger switched off? MXTPU_SERVING_PROFILER=0)")
    if not held:
        return nothing("the ring has dropped records and no longer holds "
                       "the window's opening")
    return records, causes


def host_seconds(records: list, causes: tuple) -> tuple:
    """Per record the seconds under `HOST_CAUSES`, and per such cause its
    seconds in every record: `(totals, {cause: [seconds]})`."""
    by_cause = {c: [r.causes[causes.index(c)] for r in records]
                for c in HOST_CAUSES if c in causes}
    return [sum(col) for col in zip(*by_cause.values())], by_cause
