"""How much of the context the selection keeps: 100 x the positions the
window's queries attended (`sparse_positions_attended`: min(context, topk)
a query and layer) over the positions its index scored
(`index_positions_scored`: the context a query and layer), both counted by
the program on the device and read from the ring's iterations.  About 17
at a context of 12k, 100 under `topk`: the lower, the more the traffic
lets the selection drop."""
import json

from perf.work import ledger, sparse


def read(record):
    got = ledger.window(record, "sparse_selected_share")
    if got is None:
        return None
    counts = sparse.ring_counts(got[0], record["t_open"], record["t_close"])
    if counts is None or not counts["scored"]:
        return None
    print(json.dumps({"sparse_selected_share": {
        "positions_scored": counts["scored"],
        "positions_attended": counts["attended"]}}), flush=True)
    return 100.0 * counts["attended"] / counts["scored"]
