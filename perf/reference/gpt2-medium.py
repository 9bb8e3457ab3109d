"""Plain reference of a GPT-2-shaped decoder at GPT-2-medium's sizes
(Radford et al. 2019; `config.json` of openai-community/gpt2-medium):
pre-LN blocks of causal self-attention and a GELU FFN, a final LayerNorm
and an output head.  Float32 `jax.numpy`, a whole-sequence forward with no
cache, no pages and no batching: what prefill-then-decode through the
paged cache has to agree with.

Departures from GPT-2, as the program's only decoder class has them and
the configuration's `assumed` lists them: sinusoidal positions added to an
embedding scaled by sqrt(D) instead of learned positions, an output head
that is not tied to the embedding and has a bias, tanh-GELU, LayerNorm eps
1e-5.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import common as C


def param_shapes(cfg: dict) -> dict:
    V, D, F, L = cfg["vocab_size"], cfg["n_embd"], cfg["n_inner"], \
        cfg["n_layer"]
    return {
        "embed": (V, D),
        "layers.ln1_g": (L, D), "layers.ln1_b": (L, D),
        "layers.qkv_w": (L, 3 * D, D), "layers.qkv_b": (L, 3 * D),
        "layers.proj_w": (L, D, D), "layers.proj_b": (L, D),
        "layers.ln2_g": (L, D), "layers.ln2_b": (L, D),
        "layers.ffn1_w": (L, F, D), "layers.ffn1_b": (L, F),
        "layers.ffn2_w": (L, D, F), "layers.ffn2_b": (L, D),
        "ln_g": (D,), "ln_b": (D,),
        "head_w": (V, D), "head_b": (V,),
    }


def positions(T: int, D: int):
    """Sinusoidal table (Vaswani et al. 2017): sin on even, cos on odd."""
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, D, 2, dtype=jnp.float32)
    angle = pos / jnp.power(10000.0, dim / D)
    return jnp.stack([jnp.sin(angle), jnp.cos(angle)], -1).reshape(T, D)


def _layer(h, w, H: int, prec: str):
    B, T, D = h.shape
    x = C.layer_norm(h, w["ln1_g"], w["ln1_b"], prec)
    qkv = C.dense(x, w["qkv_w"], w["qkv_b"], prec)
    q, k, v = (a.reshape(B, T, H, D // H) for a in jnp.split(qkv, 3, -1))
    a = C.attention(q, k, v, prec).reshape(B, T, D)
    h = C.add(h, C.dense(a, w["proj_w"], w["proj_b"], prec), prec)
    x = C.layer_norm(h, w["ln2_g"], w["ln2_b"], prec)
    f = C.gelu(C.dense(x, w["ffn1_w"], w["ffn1_b"], prec), prec)
    return C.add(h, C.dense(f, w["ffn2_w"], w["ffn2_b"], prec), prec)


def logits(p: dict, tokens, cfg: dict, prec: str = "fp32"):
    """tokens (B, T) -> float32 logits (B, T, V): row t scores the token
    that follows tokens[:, :t+1]."""
    D, H = cfg["n_embd"], cfg["n_head"]
    T = tokens.shape[1]
    h = C.add(p["embed"][tokens] * jnp.sqrt(jnp.float32(D)),
              positions(T, D)[None], prec)
    stack = {k[len("layers."):]: v for k, v in p.items()
             if k.startswith("layers.")}
    h, _ = jax.lax.scan(lambda x, w: (_layer(x, w, H, prec), None), h, stack)
    return C.dense(C.layer_norm(h, p["ln_g"], p["ln_b"], prec),
                   p["head_w"], p["head_b"], prec)


def served_gaps(p: dict, rows: list, cfg: dict, pad_to: int,
                control: str = None):
    """For each `(prompt, served tokens)`: at every served position, how far
    the served token's logit lies below the reference's best there.  With
    `control`, instead the gap of the token that the reference computed in
    that precision puts first.  One compiled program for every row (padded
    to `pad_to`; the causal mask keeps the padding out)."""
    import numpy as np

    p = {k: v.astype(jnp.float32) for k, v in p.items()}

    @jax.jit
    def one(p, tokens, n_prompt, n_total):
        lg = logits(p, tokens[None], cfg)[0]                   # (T, V)
        nxt = jnp.roll(tokens, -1)
        if control is not None:
            nxt = jnp.argmax(logits(p, tokens[None], cfg, control)[0], -1)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
        t = jnp.arange(tokens.shape[0])
        at = (t >= n_prompt - 1) & (t < n_total - 1)
        return jnp.where(at, best - got, -1.0)

    out = []
    for prompt, served in rows:
        seq = np.zeros((pad_to,), np.int32)
        n = len(prompt) + len(served)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = served
        g = np.asarray(one(p, jnp.asarray(seq), len(prompt), n))
        out.append(g[g >= 0].tolist())
    return out
