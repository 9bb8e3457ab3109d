"""Plain reference of MiMo-V2-Flash's decoder block (`config.json` of
XiaomiMiMo/MiMo-V2-Flash, `model_type: mimo_v2_flash`), as one chip of an
expert-parallel group holds it: the equations of ISSUE 36, item 1, each
assumption listed under `assumed` in `perf/configs/mimo-v2-flash.json`.
Float32 `jax.numpy`, a whole-sequence forward: no cache, no pages, no ring,
no chunks, no kernel, no batching; imports nothing of the program.

  * RMSNorm with a gain before every attention and feed-forward, and a
    last one before the untied head; residual sums.
  * Attention: 64 query heads, keys 192 and values 128 wide, on 4 KV heads
    in a full layer (`hybrid_layer_pattern` 0) and 8 in a sliding-window
    layer (1); rotary positions on the first 64 lanes of every query and
    key head (`partial_rotary_factor` of `head_dim`, to the nearest even
    number), halves-rotated, base `rope_theta` or `swa_rope_theta`; scores
    / sqrt(192); causal, and in a window layer only the `sliding_window`
    positions up to the query's own; in a window layer a learned logit a
    head joins the softmax's denominator and carries no value
    (`add_swa_attention_sink_bias`); the weighted values times
    `attention_value_scale`; then the output matrix.
  * Feed-forward: `moe_layer_freq` 0 a dense gated SiLU MLP of
    `intermediate_size`; 1 a routed one: sigmoid scores over all
    `n_routed_experts_published` experts, the `num_experts_per_tok`
    largest of score + selection bias, weights the selected scores over
    their sum (all the selected, held here or not), and the sum of
    `w_e * down_e(silu(gate_e x) * up_e x)` over the selected experts that
    are HELD HERE: `n_routed_experts` of them from `experts_held`'s first
    on.  What the others would add is left out, as in the program.

Departures, none in the arithmetic: leaves are a layer's own (`l3.q_w`),
as `perf/weights.py` draws them; attention runs a query head and a block
of queries at a time (all heads at once is 22 GB of scores at 9,216
positions); the experts run one at a time over all tokens, each weighted
by the share of the tokens that chose it (zero for the others), added up
in float32; weights are widened to float32 where they are used; the head
multiplies 128 positions at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perf.reference import common as C


def sizes(cfg: dict) -> dict:
    Dk = cfg["head_dim"]
    held = cfg["n_routed_experts"]
    return dict(
        D=cfg["hidden_size"], V=cfg["vocab_size"],
        Hq=cfg["num_attention_heads"], Dk=Dk, Dv=cfg["v_head_dim"],
        rope=2 * round(cfg["partial_rotary_factor"] * Dk / 2),
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        E_all=cfg.get("n_routed_experts_published", held), E=held,
        first=int(str(cfg.get("experts_held", "0")).split("-")[0]),
        W=cfg["sliding_window"])


def layers_of(cfg: dict) -> list:
    """(KV heads, windowed, rotary base, routed) a layer, by depth."""
    return [(cfg["swa_num_key_value_heads"] if win
             else cfg["num_key_value_heads"], bool(win),
             float(cfg["swa_rope_theta"] if win else cfg["rope_theta"]),
             bool(moe))
            for win, moe in zip(cfg["hybrid_layer_pattern"],
                                cfg["moe_layer_freq"])]


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    D, Hq, Dk, Dv = s["D"], s["Hq"], s["Dk"], s["Dv"]
    shapes = {"embed": (s["V"], D), "head": (s["V"], D), "ln_g": (D,)}
    for i, (Hkv, win, _, moe) in enumerate(layers_of(cfg)):
        at = f"l{i}."
        shapes.update({
            at + "ln1_g": (D,), at + "ln2_g": (D,),
            at + "q_w": (Hq * Dk, D), at + "k_w": (Hkv * Dk, D),
            at + "v_w": (Hkv * Dv, D), at + "o_w": (D, Hq * Dv)})
        if win and cfg["add_swa_attention_sink_bias"]:
            shapes[at + "sink"] = (Hq,)
        if moe:
            shapes.update({
                at + "router_w": (s["E_all"], D),
                at + "router_bias": (s["E_all"],),
                at + "gate_e": (s["E"], s["Fe"], D),
                at + "up_e": (s["E"], s["Fe"], D),
                at + "down_e": (s["E"], D, s["Fe"])})
        else:
            shapes.update({at + "gate_w": (s["F"], D),
                           at + "up_w": (s["F"], D),
                           at + "down_w": (D, s["F"])})
    return shapes


# --- the pieces common.py does not have --------------------------------- #
def rms_norm(x, g, eps: float, prec: str):
    return C.lower(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * g, prec)


def silu(x, prec: str):
    return C.lower(x * jax.nn.sigmoid(x), prec)


def rotary(x, width: int, base: float, prec: str):
    """x (T, H, D): lane j < width/2 of every head pairs with lane j +
    width/2 and turns by `t * base**(-2j/width)` at position t; the lanes
    from `width` on pass through."""
    T, half = x.shape[0], width // 2
    theta = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                    * (-2.0 / width * math.log(base)))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:width]
    return C.lower(jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., width:]], -1), prec)


def attention(x, w, layer, cfg: dict, prec: str):
    """x (T, D) -> (T, D): a query head and a block of queries at a
    time."""
    s = sizes(cfg)
    Hkv, windowed, base, _ = layer
    T, Hq, Dk, Dv, W = x.shape[0], s["Hq"], s["Dk"], s["Dv"], s["W"]
    q = C.dense(x, w["q_w"], 0.0, prec).reshape(T, Hq, Dk)
    k = C.dense(x, w["k_w"], 0.0, prec).reshape(T, Hkv, Dk)
    v = C.dense(x, w["v_w"], 0.0, prec).reshape(T, Hkv, Dv)
    q, k = rotary(q, s["rope"], base, prec), rotary(k, s["rope"], base, prec)
    block = 1024 if T % 1024 == 0 else T
    at_k = jnp.arange(T)

    def head(h):
        kv = h // (Hq // Hkv)

        def queries(b):
            at_q = b * block + jnp.arange(block)
            sc = C.einsum("qd,kd->qk",
                          jax.lax.dynamic_slice_in_dim(q[:, h], b * block,
                                                       block), k[:, kv],
                          prec) / jnp.sqrt(jnp.float32(Dk))
            gap = at_q[:, None] - at_k[None, :]
            seen = (gap >= 0) & (gap < W) if windowed else gap >= 0
            sc = jnp.where(seen, sc, -jnp.inf)
            m = jnp.max(sc, -1, keepdims=True)
            e = jnp.exp(sc - m)
            den = jnp.sum(e, -1, keepdims=True)
            if "sink" in w:     # the logit joins the denominator alone
                den = den + jnp.exp(w["sink"][h] - m)
            return C.lower(cfg["attention_value_scale"] * C.einsum(
                "qk,kd->qd", e / den, v[:, kv], prec), prec)

        return jax.lax.map(queries, jnp.arange(T // block)).reshape(T, Dv)

    a = jax.lax.map(head, jnp.arange(Hq))                   # (Hq, T, Dv)
    return C.dense(jnp.swapaxes(a, 0, 1).reshape(T, Hq * Dv), w["o_w"], 0.0,
                   prec)


def mlp(x, gate, up, down, prec: str):
    g = silu(C.dense(x, gate, 0.0, prec), prec)
    return C.dense(C.lower(g * C.dense(x, up, 0.0, prec), prec), down, 0.0,
                   prec)


def route(x, w, cfg: dict, prec: str):
    """(T, E_all) float32: each token's weight for each expert it selected,
    0 for the others.  The top-k of score + bias; the weights are the
    scores alone, over the sum of the selected."""
    g = jax.nn.sigmoid(C.einsum("td,ed->te", x, w["router_w"], prec))
    _, idx = jax.lax.top_k(g + w["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.any(idx[..., None] == jnp.arange(g.shape[1]), axis=1)
    picked = jnp.where(chosen, g, 0.0)
    return picked / jnp.sum(picked, -1, keepdims=True)


def routed_ffn(x, w, cfg: dict, prec: str):
    """x (T, D) -> (T, D): the selected experts that are held here, one at
    a time over all tokens."""
    s = sizes(cfg)
    share = jax.lax.dynamic_slice_in_dim(route(x, w, cfg, prec), s["first"],
                                         s["E"], axis=1)    # (T, E)
    f32 = jnp.float32

    def expert(y, e):
        out = mlp(x, w["gate_e"][e].astype(f32), w["up_e"][e].astype(f32),
                  w["down_e"][e].astype(f32), prec)
        return y + share[:, e][:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(s["E"]))
    return C.lower(y, prec)


def hidden(p: dict, tok, cfg: dict, prec: str = "fp32"):
    """tok (T,) -> the final norm's output (T, D), what the head
    multiplies."""
    f32, eps = jnp.float32, cfg["layernorm_epsilon"]
    h = C.lower(p["embed"][tok].astype(f32), prec)
    for i, layer in enumerate(layers_of(cfg)):
        at = f"l{i}."
        w = {k[len(at):]: v for k, v in p.items() if k.startswith(at)}
        small = {k: v.astype(f32) for k, v in w.items()
                 if not k.endswith("_e")}
        h = C.add(h, attention(rms_norm(h, small["ln1_g"], eps, prec), small,
                               layer, cfg, prec), prec)
        x = rms_norm(h, small["ln2_g"], eps, prec)
        if layer[3]:
            h = C.add(h, routed_ffn(x, dict(w, **small), cfg, prec), prec)
        else:
            h = C.add(h, mlp(x, small["gate_w"], small["up_w"],
                             small["down_w"], prec), prec)
    return rms_norm(h, p["ln_g"].astype(f32), eps, prec)


def head_matrix(p: dict, prec: str = "fp32"):
    """The head's matrix as it is multiplied: rounded to `prec` a block of
    rows at a time, kept in the leaves' own dtype (which holds every value
    of a lower precision exactly)."""
    E = p["head"]
    if prec == "fp32":
        return E
    blocks = 16 if E.shape[0] % 16 == 0 else 1
    return jax.lax.map(
        lambda b: C.lower(b.astype(jnp.float32), prec).astype(E.dtype),
        E.reshape(blocks, E.shape[0] // blocks, -1)).reshape(E.shape)


def head(e, h, prec: str = "fp32"):
    return jnp.einsum("td,vd->tv", C.lower(h, prec), e.astype(jnp.float32),
                      precision=C.HI)


def logits(p: dict, tokens, cfg: dict, prec: str = "fp32"):
    """tokens (B, T) -> float32 logits (B, T, V): row t scores the token
    that follows tokens[:, :t+1]."""
    e = head_matrix(p, prec)
    return jnp.stack([head(e, hidden(p, t, cfg, prec), prec)
                      for t in tokens])


def served_gaps(p: dict, rows: list, cfg: dict, pad_to: int,
                control: str = None):
    """For each `(prompt, served tokens)`: at every served position, how far
    the served token's logit lies below the reference's best there.  With
    `control`, instead the gap of the token that the reference computed in
    that precision puts first.  One compiled program for every row (padded
    to `pad_to`: attention is causal, so the padding never reaches a
    compared position)."""
    import numpy as np

    rows_at_once = 128      # of the (T, V) logits

    @jax.jit
    def one(p, tokens, n_prompt, n_total):
        T = tokens.shape[0]
        h = hidden(p, tokens, cfg)                             # (T, D)
        nxt = jnp.roll(tokens, -1)
        hc = hidden(p, tokens, cfg, control) if control is not None else h
        e = head_matrix(p)
        ec = head_matrix(p, control) if control is not None else e

        def block(x):
            h_b, hc_b, nxt_b = x
            lg = head(e, h_b)                                  # (rows, V)
            if control is not None:
                nxt_b = jnp.argmax(head(ec, hc_b, control), -1)
            got = jnp.take_along_axis(lg, nxt_b[:, None], -1)[:, 0]
            return jnp.max(lg, -1) - got

        pad = (-T) % rows_at_once
        blocks = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       .reshape((-1, rows_at_once) + a.shape[1:])
                       for a in (h, hc, nxt))
        gap = jax.lax.map(block, blocks).reshape(-1)[:T]
        t = jnp.arange(T)
        at = (t >= n_prompt - 1) & (t < n_total - 1)
        return jnp.where(at, gap, -1.0)

    out = []
    for prompt, served in rows:
        seq = np.zeros((pad_to,), np.int32)
        n = len(prompt) + len(served)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = served
        g = np.asarray(one(p, jnp.asarray(seq), len(prompt), n))
        out.append(g[g >= 0].tolist())
    return out
