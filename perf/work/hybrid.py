"""Operations and bytes of a decoder that mixes Mamba-1 layers with
grouped-query attention layers (the Jamba family's configuration keys),
per token, from the configuration's sizes and the tokens alone: not from
how a kernel tiles, how a program chunks or what a state's layout is.

Matmul parameters only count 2 operations each: embedding look-ups are
gathers, norms, biases, the conv's 4 taps and the gates are not matrix
products.  Recomputed operations do not count.
"""
from __future__ import annotations


def layer_counts(cfg: dict) -> dict:
    L, per, off = (cfg["num_hidden_layers"], cfg["attn_layer_period"],
                   cfg["attn_layer_offset"])
    attn = sum(i % per == off for i in range(L))
    return {"attn": attn, "ssm": L - attn, "all": L}


def sizes(cfg: dict) -> dict:
    D = cfg["hidden_size"]
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"],
                Hq=cfg["num_attention_heads"],
                Hkv=cfg["num_key_value_heads"],
                hd=D // cfg["num_attention_heads"],
                Di=cfg["mamba_expand"] * D, Ds=cfg["mamba_d_state"],
                R=cfg["mamba_dt_rank"])


def matmul_params(cfg: dict) -> dict:
    """Parameters a token is multiplied by, a layer of each kind."""
    s = sizes(cfg)
    D, Di, Ds, R = s["D"], s["Di"], s["Ds"], s["R"]
    return {
        "mlp": 3 * s["F"] * D,                          # gate, up, down
        "attn": (s["Hq"] + 2 * s["Hkv"]) * s["hd"] * D + D * s["Hq"] * s["hd"],
        "ssm": 2 * Di * D + (R + 2 * Ds) * Di + Di * R + D * Di,
    }


# the recurrence, per token, channel and state element: dt*A, exp, *s,
# (dt*u)*B, +, *C, + into y; per token and channel besides: dt*u, D*u, +,
# and silu(z)*y (exp, +, /, *, *)
SCAN_OPS_PER_STATE_ELEMENT = 7
SCAN_OPS_PER_CHANNEL = 8


def scan_ops_per_token(cfg: dict) -> int:
    """One Mamba layer's selective scan, one token."""
    s = sizes(cfg)
    return s["Di"] * (SCAN_OPS_PER_STATE_ELEMENT * s["Ds"]
                      + SCAN_OPS_PER_CHANNEL)


def serve_flops(cfg: dict, work: dict) -> float:
    """Forward operations of what a window served (`work` as
    `perf/work/served.py` counts it): every prompt token and every decode
    step's token goes through the layers' matrices by kind (2 a
    parameter) and through each Mamba layer's scan, every output token
    through the head (2 V D, the embedding's transpose), and each attends
    its context in the attention layers (4 Hq hd a position: QK^T, PV)."""
    n, p, s = layer_counts(cfg), matmul_params(cfg), sizes(cfg)
    forwards = work["prompt_tokens"] + work["decode_tokens"]
    context = work["prefill_context"] + work["decode_context"]
    per_token = (2.0 * (n["all"] * p["mlp"] + n["attn"] * p["attn"]
                        + n["ssm"] * p["ssm"])
                 + n["ssm"] * scan_ops_per_token(cfg))
    return (per_token * forwards
            + 2.0 * s["V"] * s["D"] * work["output_tokens"]
            + 4.0 * n["attn"] * s["Hq"] * s["hd"] * context)


# --- the selective scan's floor ------------------------------------------ #
def scan_bytes(cfg: dict, tokens: int, programs_lanes: int) -> int:
    """What the scans of `tokens` tokens must move, all Mamba layers:
    each program reads and writes once the float32 state of every lane it
    touches (`programs_lanes`: summed over the programs, the lanes each
    advances) and streams, a token, u, z, y (bfloat16) and dt (float32) a
    channel, B and C (float32) a state element."""
    s, n = sizes(cfg), layer_counts(cfg)["ssm"]
    state = 2 * s["Ds"] * s["Di"] * 4
    stream = s["Di"] * (2 + 2 + 2 + 4) + 2 * s["Ds"] * 4
    return n * (programs_lanes * state + tokens * stream)


def scan_floor_seconds(cfg: dict, tokens: int, programs_lanes: int,
                       peak: dict) -> float:
    ops = layer_counts(cfg)["ssm"] * scan_ops_per_token(cfg) * tokens
    return max(scan_bytes(cfg, tokens, programs_lanes)
               / peak["hbm_bytes_per_s"], ops / peak["flops_bf16"])


# --- grouped paged attention's floor -------------------------------------- #
def paged_bytes(positions: int, cfg: dict, itemsize: int = 2) -> int:
    """K and V at the positions read (`positions`: summed over decode
    lanes and prefill chunks, what each reads), by KV heads, every
    attention layer."""
    s = sizes(cfg)
    return (2 * positions * s["Hkv"] * s["hd"] * itemsize
            * layer_counts(cfg)["attn"])


def paged_flops(query_positions: int, cfg: dict) -> int:
    """QK^T and PV by query heads: 4 Hq hd a position, query and
    attention layer."""
    s = sizes(cfg)
    return (4 * query_positions * s["Hq"] * s["hd"]
            * layer_counts(cfg)["attn"])


def paged_floor_seconds(positions: int, query_positions: int, cfg: dict,
                        peak: dict) -> float:
    return max(paged_bytes(positions, cfg) / peak["hbm_bytes_per_s"],
               paged_flops(query_positions, cfg) / peak["flops_bf16"])


# --- finding a kernel in a reduced trace ---------------------------------- #
def kernel_time(reduced: dict, kernel: str):
    """{"seconds", "count"} of the device operations named `kernel` (the
    `name=` of its `pallas_call`: the compiled instruction is
    `%<kernel>.N`), None where none ran.  By name alone, so a program may
    hold any number of other kernels."""
    sec, cnt = 0.0, 0
    for name, op in reduced["ops"].items():
        if name == kernel or (name.startswith(kernel + ".")
                              and name[len(kernel) + 1:].isdigit()):
            sec += op["seconds"]
            cnt += op["count"]
    return {"seconds": sec, "count": cnt} if cnt else None


def programs_run(reduced: dict, needle: str) -> int:
    return sum(m["count"] for name, m in reduced["modules"].items()
               if needle in name)
