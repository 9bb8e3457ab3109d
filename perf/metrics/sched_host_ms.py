"""Mean over the window's scheduler iterations of the scheduler thread's
time that is not a wait for the device: `lock_wait + bookkeeping + commit +
gather_params + dispatch + gc + host_other` of the program's own ledger
(`perf/work/ledger.py`), ms an iteration.  An iteration is one decode step,
commit to commit, with the prefill chunk that ran between.  Source: the
spans `serving.<cause>` the scheduler times itself with.  Each cause's
mean stands on the line before the result."""
import json

from perf.work import ledger


def read(record):
    got = ledger.window(record, "sched_host_ms")
    if got is None:
        return None
    totals, by_cause = ledger.host_seconds(*got)
    n = len(totals)
    print(json.dumps({"sched_host_ms": {
        "iterations": n,
        "wall_ms_mean": 1e3 * sum(r.t1 - r.t0 for r in got[0]) / n,
        "ms_mean_by_cause": {c: 1e3 * sum(v) / n
                             for c, v in by_cause.items()}}}), flush=True)
    return 1e3 * sum(totals) / n
