"""How unevenly the router loads the experts held here: mean over the
window's scheduler iterations of the busiest expert's pairs
(`expert_busiest`: the most pairs one held expert of one routed layer took
in one program of the iteration) over the mean pairs a held expert and
layer took in it (`expert_pairs` / (experts held x routed layers)).  1.0
would be an even spread with every pair in one program; random weights
route evenly, so what shows is the spread of small counts."""
import json

from perf.work import ledger, routed


def read(record):
    got = ledger.window(record, "expert_load_imbalance")
    if got is None:
        return None
    counts = routed.ring_counts(got[0], record["t_open"], record["t_close"])
    if counts is None:
        return None
    cfg = record["config"]
    slots = cfg["n_routed_experts"] * routed.layer_counts(cfg)["routed"]
    ratios = [b * slots / p for b, p in zip(counts["busiest"],
                                            counts["pairs_each"]) if p]
    if not ratios:
        return None
    print(json.dumps({"expert_load_imbalance": {
        "iterations": len(ratios), "pairs": counts["pairs"],
        "tokens_routed": counts["tokens"],
        "pairs_a_token_and_layer": counts["pairs"] / counts["tokens"],
        "busiest_max": max(counts["busiest"])}}), flush=True)
    return sum(ratios) / len(ratios)
