"""`expert_load_imbalance`'s arithmetic under the keys of a configuration
whose every layer is routed (`perf/work/sparse.py`): mean over the window's
scheduler iterations of the busiest expert's pairs (`expert_busiest`: the
most pairs one held expert of one layer took in one program of the
iteration) over the mean pairs a held expert and layer took in it
(`expert_pairs` / (experts held x layers)).  1.0 would be an even spread
with every pair in one program.  The line above the result says what share
of an even router's pairs the held experts got (`num_experts_per_tok` x
held / published a token and layer)."""
import json

from perf.work import ledger, sparse


def read(record):
    got = ledger.window(record, "expert_load_imbalance.sparse")
    if got is None:
        return None
    counts = sparse.ring_counts(got[0], record["t_open"], record["t_close"])
    if counts is None:
        return None
    cfg = record["config"]
    s = sparse.sizes(cfg)
    slots = s["E"] * s["L"]
    ratios = [b * slots / p for b, p in zip(counts["busiest"],
                                            counts["pairs_each"]) if p]
    if not ratios:
        return None
    even = cfg["num_experts_per_tok"] * s["E"] / s["E_all"]
    print(json.dumps({"expert_load_imbalance.sparse": {
        "iterations": len(ratios), "pairs": counts["pairs"],
        "tokens_routed": counts["tokens"],
        "pairs_a_token_and_layer": counts["pairs"] / counts["tokens"],
        "an_even_router_gives": even,
        "busiest_max": max(counts["busiest"])}}), flush=True)
    return sum(ratios) / len(ratios)
