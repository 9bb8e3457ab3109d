"""Operations the algorithm needs per token, from a configuration's sizes.
Matmul parameters only: embedding look-ups are gathers, biases and
LayerNorms are not matrix products.  Recomputed operations do not count.
"""
from __future__ import annotations


def serve_flops(cfg: dict, work: dict) -> float:
    """Forward operations of what a window served (`work` as the serving
    driver counts it): every prompt token and every decode step's token
    goes through the layers' matrices (2 a parameter), every output token
    through the head (2 V D), and each attends its context (4 L D a
    position: QK^T and PV)."""
    D, F, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    layers = L * (4 * D * D + 2 * D * F)
    forwards = work["prompt_tokens"] + work["decode_tokens"]
    context = work["prefill_context"] + work["decode_context"]
    return (2.0 * layers * forwards
            + 2.0 * cfg["vocab_size"] * D * work["output_tokens"]
            + 4.0 * L * D * context)
