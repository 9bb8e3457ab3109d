"""Plain reference of AI21-Jamba2-3B (`config.json` of
ai21labs/AI21-Jamba2-3B, `model_type: jamba`; the equations are those of
HF `modeling_jamba.py`'s slow path): a decoder of Mamba-1 layers with an
attention layer every `attn_layer_period`, each followed by a gated SiLU
MLP, RMSNorm before every mixer and MLP, no positions of any kind, the
logits by the embedding's transpose.  Float32 `jax.numpy`, a
whole-sequence forward: the recurrence is a `lax.scan` over tokens from a
zero state, the attention a full causal softmax.  No cache, no pages, no
chunks, no kernel, no batching; imports nothing of the program.

Layer `i` is an attention layer when `i % attn_layer_period ==
attn_layer_offset` (HF `JambaConfig.layers_block_type`), else a Mamba
layer.  `num_experts` is 1, so every layer's feed-forward is the dense MLP.

Departures from `modeling_jamba.py`, none of them in the arithmetic:
  * the leaves are stacked by kind (`ssm.*` over the Mamba layers,
    `attn.*` over the attention layers, `layers.*` over all layers by
    depth), which is how `perf/weights.py` draws them;
  * the depthwise conv's weight is `(d_inner, d_conv)` (HF holds
    `(d_inner, 1, d_conv)`), applied as `d_conv` shifted products;
  * the conv window keeps no state between calls: the whole sequence is
    convolved at once, left-padded with zeros;
  * attention runs a query head at a time, each reading its KV head (HF
    repeats K and V over the group and takes all heads at once: 0.6 GB
    of scores at 2816 positions);
  * the head multiplies by the embedding a block of rows at a time, and
    `served_gaps` takes the logits 128 positions at a time (all of them
    are 0.7 GB);
  * weights are widened from bfloat16 to float32 a layer at a time,
    inside the loop over layers (all at once is 12 GB);
  * `A_log`, `D` and the `dt` bias are drawn N(0, 0.02) like every other
    leaf (the configuration file says what that does to the memory's
    length); HF initialises them to log(1..d_state), 1 and an inverse
    softplus.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import common as C


def layer_kinds(cfg: dict) -> list:
    """"attn" or "ssm" for every layer, by depth."""
    return ["attn" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            else "ssm" for i in range(cfg["num_hidden_layers"])]


def sizes(cfg: dict) -> dict:
    D = cfg["hidden_size"]
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"],
                Hq=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
                hd=D // cfg["num_attention_heads"],
                Di=cfg["mamba_expand"] * D, Ds=cfg["mamba_d_state"],
                K=cfg["mamba_d_conv"], R=cfg["mamba_dt_rank"])


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    kinds = layer_kinds(cfg)
    L, Ls, La = len(kinds), kinds.count("ssm"), kinds.count("attn")
    D, F, Di, Ds, K, R = s["D"], s["F"], s["Di"], s["Ds"], s["K"], s["R"]
    shapes = {
        "embed": (s["V"], D),
        "layers.ln1_g": (L, D), "layers.ln2_g": (L, D),
        "layers.gate_w": (L, F, D), "layers.up_w": (L, F, D),
        "layers.down_w": (L, D, F),
        "ssm.in_proj_w": (Ls, 2 * Di, D),
        "ssm.conv_w": (Ls, Di, K),
        "ssm.x_proj_w": (Ls, R + 2 * Ds, Di),
        "ssm.dt_norm_g": (Ls, R), "ssm.b_norm_g": (Ls, Ds),
        "ssm.c_norm_g": (Ls, Ds),
        "ssm.dt_proj_w": (Ls, Di, R), "ssm.dt_proj_b": (Ls, Di),
        "ssm.a_log": (Ls, Di, Ds), "ssm.d": (Ls, Di),
        "ssm.out_proj_w": (Ls, D, Di),
        "attn.q_w": (La, s["Hq"] * s["hd"], D),
        "attn.k_w": (La, s["Hkv"] * s["hd"], D),
        "attn.v_w": (La, s["Hkv"] * s["hd"], D),
        "attn.o_w": (La, D, s["Hq"] * s["hd"]),
        "ln_g": (D,),
    }
    if cfg["mamba_conv_bias"]:
        shapes["ssm.conv_b"] = (Ls, Di)
    if cfg["mamba_proj_bias"]:
        shapes["ssm.in_proj_b"] = (Ls, 2 * Di)
        shapes["ssm.out_proj_b"] = (Ls, D)
    return shapes


# --- the pieces common.py does not have --------------------------------- #
def rms_norm(x, g, eps: float, prec: str):
    return C.lower(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * g, prec)


def silu(x, prec: str):
    return C.lower(x * jax.nn.sigmoid(x), prec)


def softplus(x, prec: str):
    return C.lower(jnp.logaddexp(x, 0.0), prec)


def causal_conv(u, w, b, prec: str):
    """Depthwise causal conv over time: u (T, Di), w (Di, K); output t
    sees inputs t-K+1 .. t, zeros before the sequence."""
    T, K = u.shape[0], w.shape[1]
    pad = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    out = sum(C.lower(pad[k:k + T] * C.lower(w[:, k], prec), prec)
              for k in range(K))
    return C.lower(out + b, prec)


def recurrence(u, dt, A, Bm, Cm, prec: str):
    """s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t, y_t = s_t . C_t,
    from a zero state: u, dt (T, Di); A (Di, Ds); Bm, Cm (T, Ds)."""
    def step(s, x):
        u_t, dt_t, b_t, c_t = x
        s = C.lower(jnp.exp(dt_t[:, None] * A) * s
                    + (dt_t * u_t)[:, None] * b_t[None, :], prec)
        return s, C.lower(jnp.sum(s * c_t[None, :], -1), prec)

    s0 = jnp.zeros(A.shape, jnp.float32)
    return jax.lax.scan(step, s0, (u, dt, Bm, Cm))[1]


def mamba_mixer(x, w, cfg: dict, prec: str):
    """x (T, D) -> (T, D)."""
    s = sizes(cfg)
    Di, Ds, R, eps = s["Di"], s["Ds"], s["R"], cfg["rms_norm_eps"]
    uz = C.dense(x, w["in_proj_w"], w.get("in_proj_b", 0.0), prec)
    u, z = uz[:, :Di], uz[:, Di:]
    u = silu(causal_conv(u, w["conv_w"], w.get("conv_b", 0.0), prec), prec)
    dbc = C.dense(u, w["x_proj_w"], 0.0, prec)
    dt = rms_norm(dbc[:, :R], w["dt_norm_g"], eps, prec)
    Bm = rms_norm(dbc[:, R:R + Ds], w["b_norm_g"], eps, prec)
    Cm = rms_norm(dbc[:, R + Ds:], w["c_norm_g"], eps, prec)
    dt = softplus(C.dense(dt, w["dt_proj_w"], w["dt_proj_b"], prec), prec)
    A = -jnp.exp(w["a_log"])
    y = recurrence(u, dt, A, Bm, Cm, prec)
    y = C.lower(y + C.lower(w["d"] * u, prec), prec)
    return C.dense(C.lower(y * silu(z, prec), prec), w["out_proj_w"],
                   w.get("out_proj_b", 0.0), prec)


def attention_mixer(x, w, cfg: dict, prec: str):
    """Causal softmax(q k^T / sqrt(hd)) v, a query head at a time (all
    heads at once is a (Hq, T, T) float32 array: 0.6 GB at 2816
    positions); query head h reads KV head h // (Hq / Hkv)."""
    s = sizes(cfg)
    T, Hq, Hkv, hd = x.shape[0], s["Hq"], s["Hkv"], s["hd"]
    q = C.dense(x, w["q_w"], 0.0, prec).reshape(T, Hq, hd)
    k = C.dense(x, w["k_w"], 0.0, prec).reshape(T, Hkv, hd)
    v = C.dense(x, w["v_w"], 0.0, prec).reshape(T, Hkv, hd)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(h):
        kv = h // (Hq // Hkv)
        sc = C.einsum("qd,kd->qk", q[:, h], k[:, kv], prec) \
            / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return C.lower(C.einsum("qk,kd->qd", p, v[:, kv], prec), prec)

    a = jax.lax.map(head, jnp.arange(Hq))                   # (Hq, T, hd)
    return C.dense(jnp.swapaxes(a, 0, 1).reshape(T, Hq * hd), w["o_w"], 0.0,
                   prec)


def mlp(x, w, prec: str):
    g = silu(C.dense(x, w["gate_w"], 0.0, prec), prec)
    up = C.dense(x, w["up_w"], 0.0, prec)
    return C.dense(C.lower(g * up, prec), w["down_w"], 0.0, prec)


def _layer(h, w, kind: str, cfg: dict, prec: str):
    eps = cfg["rms_norm_eps"]
    mixer = attention_mixer if kind == "attn" else mamba_mixer
    h = C.add(h, mixer(rms_norm(h, w["ln1_g"], eps, prec), w, cfg, prec),
              prec)
    return C.add(h, mlp(rms_norm(h, w["ln2_g"], eps, prec), w, prec), prec)


def hidden(p: dict, tok, cfg: dict, prec: str = "fp32"):
    """tok (T,) -> the final norm's output (T, D), what the head
    multiplies.  Runs of layers of one kind are one `fori_loop` each,
    which takes layer i's leaves from the stacks and widens them to
    float32 there."""
    kinds = layer_kinds(cfg)
    f32 = jnp.float32

    def stack(prefix):
        return {k[len(prefix):]: v for k, v in p.items()
                if k.startswith(prefix)}

    by_depth = stack("layers.")
    by_kind = {"ssm": stack("ssm."), "attn": stack("attn.")}

    h = C.lower(p["embed"][tok].astype(f32), prec)
    lo, seen = 0, {"ssm": 0, "attn": 0}
    while lo < len(kinds):
        kind = kinds[lo]
        hi = lo
        while hi < len(kinds) and kinds[hi] == kind:
            hi += 1
        first = seen[kind] - lo           # index within the kind, by depth

        def body(i, h, kind=kind, first=first):
            w = {k: v[i].astype(f32) for k, v in by_depth.items()}
            w.update({k: v[i + first].astype(f32)
                      for k, v in by_kind[kind].items()})
            return _layer(h, w, kind, cfg, prec)

        h = jax.lax.fori_loop(lo, hi, body, h)
        seen[kind] += hi - lo
        lo = hi
    return rms_norm(h, p["ln_g"].astype(f32), cfg["rms_norm_eps"], prec)


def head_matrix(p: dict, prec: str = "fp32"):
    """The embedding as the head multiplies it: rounded to `prec` a block
    of rows at a time (the whole table's rounding holds four float32
    copies of it) and kept in the leaves' own dtype, which holds every
    value of a lower precision exactly."""
    E = p["embed"]
    if prec == "fp32":
        return E
    blocks = 16 if E.shape[0] % 16 == 0 else 1
    return jax.lax.map(
        lambda b: C.lower(b.astype(jnp.float32), prec).astype(E.dtype),
        E.reshape(blocks, E.shape[0] // blocks, -1)).reshape(E.shape)


def head(e, h, prec: str = "fp32"):
    """(T, D) -> (T, V) by the transpose of `head_matrix`'s `e`."""
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
    to `pad_to`: attention is causal and the recurrence runs forward, so
    the padding never reaches a compared position)."""
    import numpy as np

    rows_at_once = 128      # of the (T, V) logits: all of them are 0.7 GB

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
