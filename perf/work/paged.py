"""What attention over a paged KV cache needs, whatever implements it: the
keys and values at the valid positions a program's queries attend, read
once a program.  A decode step's lane at `pos` reads pos + 1 positions; a
prefill chunk reads the context at its end once for all its queries.  It
depends on the positions alone: not on the block size, the table layout or
the kernel.
"""
from __future__ import annotations


def bytes_attended(positions: int, cfg: dict, itemsize: int = 2) -> int:
    """`positions`: summed over decode lanes and prefill chunks, the
    positions each reads.  K and V, all heads (H x D/H = n_embd a
    position), every layer."""
    return 2 * positions * cfg["n_embd"] * itemsize * cfg["n_layer"]


def flops_attended(query_positions: int, cfg: dict) -> int:
    """QK^T and PV: 2 x 2 x n_embd a position, query and layer.
    `query_positions`: summed over every query token, the positions it
    attends."""
    return 4 * query_positions * cfg["n_embd"] * cfg["n_layer"]


def floor_seconds(positions: int, query_positions: int, cfg: dict,
                  peak: dict) -> float:
    return max(bytes_attended(positions, cfg) / peak["hbm_bytes_per_s"],
               flops_attended(query_positions, cfg) / peak["flops_bf16"])
