"""99th percentile, over the window's scheduler iterations, of what
`sched_host_ms` takes the mean of: the scheduler thread's time an iteration
that is not a wait for the device, ms.  A 51 s window holds 1,300
iterations or more, so more than ten lie beyond it; the count is printed.
For the iterations beyond it, the line before the result says how far each
cause stands above its own mean over the window: which part of the host
makes a slow iteration slow."""
import json

import numpy as np

from perf.work import ledger


def read(record):
    got = ledger.window(record, "sched_host_p99_ms")
    if got is None:
        return None
    totals, by_cause = ledger.host_seconds(*got)
    totals = np.asarray(totals)
    p99 = float(np.percentile(totals, 99))
    beyond = totals > p99
    excess = {c: 1e3 * float(np.asarray(v)[beyond].mean() - np.mean(v))
              for c, v in by_cause.items()} if beyond.any() else {}
    print(json.dumps({"sched_host_p99_ms": {
        "samples": len(totals), "beyond": int(beyond.sum()),
        "p50_ms": 1e3 * float(np.median(totals)),
        "max_ms": 1e3 * float(totals.max()),
        "excess_ms_by_cause_beyond_p99": excess,
        "most_of_the_excess": max(excess, key=excess.get) if excess
        else None}}), flush=True)
    return 1e3 * p99
