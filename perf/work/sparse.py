"""Operations and bytes of a decoder whose attention layers select what
they attend by a learned index and whose feed-forwards are routed over
experts of which this chip holds a share (Keye-VL-2.0's language model's
configuration keys), from the configuration's sizes and the requests'
stamps alone: not from how a kernel tiles, how a program chunks, whether a
selection is a list or a mask, or how wide a row of a pool lies in memory.

Matmul parameters count 2 operations each; embedding look-ups, norms,
rotary turns, the ReLU and the weighted sum of the index's heads, the
selection itself (no products, no bytes but the scores'), the router's
softmax and top-k and the gates are not matrix products.  Recomputed
operations do not count.  Of the routed experts only the pairs computed
HERE count (the ring's `expert_pairs`).
"""
from __future__ import annotations

from perf.work import routed, served


def sizes(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return dict(D=cfg["hidden_size"], V=cfg["vocab_size"],
                Hq=cfg["num_attention_heads"],
                Hkv=cfg["num_key_value_heads"], Dh=cfg["head_dim"],
                Fe=cfg["moe_intermediate_size"], E=cfg["num_experts"],
                E_all=cfg.get("num_experts_published", cfg["num_experts"]),
                Hi=sa["indexer_num_heads"], Di=sa["indexer_head_dim"],
                topk=sa["topk"], L=cfg["num_hidden_layers"])


def layer_params(cfg: dict) -> dict:
    """Matmul parameters every token is multiplied by in one layer, by
    part.  (The experts are counted by pair.)"""
    s = sizes(cfg)
    D = s["D"]
    return {"attention": (2 * s["Hq"] + 2 * s["Hkv"]) * s["Dh"] * D,
            "indexer": (s["Hi"] * s["Di"] + s["Di"] + s["Hi"]) * D,
            "router": s["E_all"] * D}


def pair_params(cfg: dict) -> int:
    """One token through one expert: gate, up, down."""
    s = sizes(cfg)
    return 3 * s["D"] * s["Fe"]


def selected_work(requests: list, lo: float, hi: float, chunk: int,
                  topk: int) -> dict:
    """What [lo, hi) held as the selection leaves it (the companion of
    `served.count_work`, same stamps, same placing of the chunks):
    `decode_context` the positions the decode tokens attended, min(P + i,
    topk) for output i; `prefill_context` those the chunks' prompt tokens
    attended, min(q + 1, topk) for token q; `chunk_rows` the K/V rows the
    chunks needed, for each chunk the smaller of its queries' selections
    together and its context at its end (read once)."""
    out = routed.windowed_work(requests, lo, hi, chunk, topk)
    out["chunk_rows"] = 0
    for t, a, b in served.chunk_ends(requests, chunk):
        if lo <= t < hi:
            m = min(b, topk)
            picked = ((m * (m + 1) - a * (a + 1)) // 2 if m > a else 0) \
                + max(0, b - max(a, topk)) * topk
            out["chunk_rows"] += min(picked, b)
    return out


def serve_flops(cfg: dict, work: dict, selected: dict,
                expert_pairs: int) -> float:
    """Forward operations of what a window served: every prompt token and
    every decode step's token through `layer_params` in every layer, the
    pairs computed here through `pair_params`, every output token through
    the head (2 V D), and in every layer each token's index scores over
    its whole context (2 Hi Di a position) and its attention over
    min(context, topk) positions (4 Hq Dh a position)."""
    s = sizes(cfg)
    forwards = work["prompt_tokens"] + work["decode_tokens"]
    context = work["prefill_context"] + work["decode_context"]
    picked = selected["prefill_context"] + selected["decode_context"]
    return (2.0 * s["L"] * sum(layer_params(cfg).values()) * forwards
            + 2.0 * pair_params(cfg) * expert_pairs
            + 2.0 * s["V"] * s["D"] * work["output_tokens"]
            + s["L"] * (2.0 * s["Hi"] * s["Di"] * context
                        + 4.0 * s["Hq"] * s["Dh"] * picked))


# --- the kernels' floors ----------------------------------------------------- #
def index_floor_seconds(cfg: dict, work: dict, peak: dict,
                        itemsize: int = 2) -> float:
    """The index scores' least time: the index key (Di wide) of every
    position scored, read once a decode lane and once a chunk, every
    layer; or the scores' products (2 Hi Di a query and position) over the
    peak, whichever is longer."""
    s = sizes(cfg)
    keys = work["decode_context"] + work["chunk_context"]
    pairs = work["decode_context"] + work["prefill_context"]
    return s["L"] * max(
        keys * s["Di"] * itemsize / peak["hbm_bytes_per_s"],
        2.0 * s["Hi"] * s["Di"] * pairs / peak["flops_bf16"])


def sparse_floor_seconds(cfg: dict, selected: dict, peak: dict,
                         itemsize: int = 2) -> float:
    """The attention's least time: the K and V rows of min(context, topk)
    positions a decode lane and of `chunk_rows` a chunk, every layer; or
    the selected products (4 Hq Dh a query and position) over the peak."""
    s = sizes(cfg)
    rows = selected["decode_context"] + selected["chunk_rows"]
    picked = selected["decode_context"] + selected["prefill_context"]
    return s["L"] * max(
        rows * 2 * s["Hkv"] * s["Dh"] * itemsize / peak["hbm_bytes_per_s"],
        4.0 * s["Hq"] * s["Dh"] * picked / peak["flops_bf16"])


# --- the ring's counts over a span ----------------------------------------- #
def ring_counts(records: list, lo: float, hi: float) -> dict:
    """Sums of the program's own counts over the iterations committed in
    [lo, hi): positions the index scored, positions then attended, pairs
    the experts held here computed, tokens routed.  None where the records
    carry no index counts (a program without an index, or an older one)."""
    span = [r for r in records if lo <= r.t1 < hi
            and getattr(r, "index_positions_scored", 0)]
    if not span:
        return None
    return {"scored": sum(r.index_positions_scored for r in span),
            "attended": sum(r.sparse_positions_attended for r in span),
            "pairs": sum(r.expert_pairs for r in span),
            "tokens": sum(r.expert_tokens for r in span),
            "busiest": [r.expert_busiest for r in span],
            "pairs_each": [r.expert_pairs for r in span]}
