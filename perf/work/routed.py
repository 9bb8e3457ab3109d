"""Operations and bytes of a decoder that mixes full-attention and
sliding-window layers and routes its feed-forwards over experts of which
this chip holds a share (MiMo-V2-Flash's configuration keys), from the
configuration's sizes and the tokens alone: not from how a kernel tiles,
how a program chunks, or whether a window's pages lie in a ring.

Matmul parameters count 2 operations each; embedding look-ups, norms,
rotary turns, the router's sigmoid and top-k, and the gates are not matrix
products.  Recomputed operations do not count.  Of the routed experts only
the pairs computed HERE count (the ring's `expert_pairs`): what the absent
experts would have computed is another chip's work.
"""
from __future__ import annotations

from perf.work import served


def sizes(cfg: dict) -> dict:
    return dict(D=cfg["hidden_size"], V=cfg["vocab_size"],
                Hq=cfg["num_attention_heads"], Dk=cfg["head_dim"],
                Dv=cfg["v_head_dim"], F=cfg["intermediate_size"],
                Fe=cfg["moe_intermediate_size"], E=cfg["n_routed_experts"],
                E_all=cfg.get("n_routed_experts_published",
                              cfg["n_routed_experts"]),
                W=cfg["sliding_window"])


def layers(cfg: dict) -> list:
    """(KV heads, windowed, routed) a layer, by depth."""
    return [(cfg["swa_num_key_value_heads"] if win
             else cfg["num_key_value_heads"], bool(win), bool(moe))
            for win, moe in zip(cfg["hybrid_layer_pattern"],
                                cfg["moe_layer_freq"])]


def layer_counts(cfg: dict) -> dict:
    ls = layers(cfg)
    return {"full": sum(not w for _, w, _ in ls),
            "window": sum(w for _, w, _ in ls),
            "routed": sum(r for _, _, r in ls),
            "dense": sum(not r for _, _, r in ls)}


def token_params(cfg: dict) -> int:
    """Matmul parameters every token is multiplied by: each layer's four
    attention matrices by its KV heads, a dense layer's three, a routed
    layer's router.  (The experts are counted by pair.)"""
    s = sizes(cfg)
    D, Hq, Dk, Dv = s["D"], s["Hq"], s["Dk"], s["Dv"]
    total = 0
    for Hkv, _, routed in layers(cfg):
        total += (Hq * Dk + Hkv * Dk + Hkv * Dv) * D + D * Hq * Dv
        total += s["E_all"] * D if routed else 3 * s["F"] * D
    return total


def pair_params(cfg: dict) -> int:
    """One token through one expert: gate, up, down."""
    s = sizes(cfg)
    return 3 * s["D"] * s["Fe"]


def windowed_work(requests: list, lo: float, hi: float, chunk: int,
                  W: int) -> dict:
    """What [lo, hi) held, as a window of `W` positions sees it (the
    companion of `served.count_work`, same stamps, same placing of the
    chunks): `decode_context` the positions the decode tokens attended,
    min(P + i, W) for output i; `prefill_context` those the chunks' prompt
    tokens attended, min(q + 1, W) for token q."""
    out = dict(decode_context=0, prefill_context=0)
    for P, _t_admit, stamps in requests:
        for i, t in enumerate(stamps):
            if i and lo <= t < hi:
                out["decode_context"] += min(P + i, W)
    for t, a, b in served.chunk_ends(requests, chunk):
        if lo <= t < hi:
            # tokens a .. b-1: token q attends q + 1 below the window's
            # width and W from there on
            m = min(b, W)
            below = (m * (m + 1) - a * (a + 1)) // 2 if m > a else 0
            out["prefill_context"] += below + max(0, b - max(a, W)) * W
    return out


def serve_flops(cfg: dict, work: dict, windowed: dict,
                expert_pairs: int) -> float:
    """Forward operations of what a window served: every prompt token and
    every decode step's token through `token_params`, the pairs computed
    here through `pair_params`, every output token through the head (2 V
    D), and each token's attention over its context, 2 Hq (Dk + Dv) a
    position: the whole context in the full layers, min(context, W) in the
    window layers."""
    s, n = sizes(cfg), layer_counts(cfg)
    forwards = work["prompt_tokens"] + work["decode_tokens"]
    per_position = 2.0 * s["Hq"] * (s["Dk"] + s["Dv"])
    return (2.0 * token_params(cfg) * forwards
            + 2.0 * pair_params(cfg) * expert_pairs
            + 2.0 * s["V"] * s["D"] * work["output_tokens"]
            + per_position * n["full"] * (work["prefill_context"]
                                          + work["decode_context"])
            + per_position * n["window"] * (windowed["prefill_context"]
                                            + windowed["decode_context"]))


# --- the experts' kernel's floor ------------------------------------------ #
def experts_bytes(cfg: dict, pairs: int, programs: int,
                  itemsize: int = 2) -> int:
    """What the experts of `programs` programs must move for `pairs`
    pairs, all routed layers: each program reads once the three matrices
    of each held expert that a pair chose (no more experts than pairs),
    and each pair's activation in and result out."""
    s, n = sizes(cfg), layer_counts(cfg)
    experts_read = min(programs * n["routed"] * s["E"], pairs)
    return itemsize * (experts_read * pair_params(cfg)
                       + pairs * 2 * s["D"])


def experts_floor_seconds(cfg: dict, pairs: int, programs: int,
                          peak: dict) -> float:
    return max(experts_bytes(cfg, pairs, programs) / peak["hbm_bytes_per_s"],
               2.0 * pair_params(cfg) * pairs / peak["flops_bf16"])


# --- paged attention's floor, by layer kind ------------------------------- #
def paged_bytes(full_positions: int, window_positions: int, cfg: dict,
                itemsize: int = 2) -> int:
    """K and V at the positions read, by each kind's KV heads: keys Dk and
    values Dv wide."""
    s = sizes(cfg)
    per_head = (s["Dk"] + s["Dv"]) * itemsize
    return sum(Hkv * per_head * (window_positions if win else full_positions)
               for Hkv, win, _ in layers(cfg))


def paged_flops(full_queries: int, window_queries: int, cfg: dict) -> int:
    """QK^T and PV by query heads: 2 Hq (Dk + Dv) a position, query and
    layer."""
    s, n = sizes(cfg), layer_counts(cfg)
    return 2 * s["Hq"] * (s["Dk"] + s["Dv"]) * (
        n["full"] * full_queries + n["window"] * window_queries)


def paged_floor_seconds(full_positions, window_positions, full_queries,
                        window_queries, cfg: dict, peak: dict) -> float:
    return max(paged_bytes(full_positions, window_positions, cfg)
               / peak["hbm_bytes_per_s"],
               paged_flops(full_queries, window_queries, cfg)
               / peak["flops_bf16"])


# --- the ring's counts over a span ----------------------------------------- #
def ring_counts(records: list, lo: float, hi: float) -> dict:
    """Sums of the program's own counts over the iterations committed in
    [lo, hi): pairs computed here, tokens routed, and per iteration the
    busiest expert's pairs.  None where the records carry no such fields
    (a program without routed layers, or an older one)."""
    span = [r for r in records if lo <= r.t1 < hi
            and getattr(r, "expert_tokens", 0)]
    if not span:
        return None
    return {"pairs": sum(r.expert_pairs for r in span),
            "tokens": sum(r.expert_tokens for r in span),
            "busiest": [r.expert_busiest for r in span],
            "pairs_each": [r.expert_pairs for r in span]}
