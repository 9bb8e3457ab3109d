"""Plain reference of the language model of Keye-VL-2.0-30B-A3B
(`config.json` of Kwai-Keye/Keye-VL-2.0-30B-A3B, `model_type: KeyeVL2`), as
one chip of an expert-parallel group of eight holds it: the equations of
ISSUE 38, item 1, each assumption listed under `assumed` in
`perf/configs/keye-vl-2-30b-a3b.json`.  Float32 `jax.numpy`, a
whole-sequence forward: no cache, no pages, no chunks, no kernel, no
batching; imports nothing of the program.

  * RMSNorm with a gain before every attention and feed-forward, and a
    last one before the untied head; residual sums.
  * Attention: 32 query heads on 4 KV heads of 128, no bias; an RMSNorm
    with a gain over the 128 lanes of every query head and every key head;
    then rotary positions on all 128 lanes, halves-rotated, base
    `rope_theta` (a text token has one position in all three
    `mrope_section`s, which is the ordinary rotary).
  * Index (`sa_config`): `qI_t = rope(WqI x_t)`, 16 heads of 64; `kI_s =
    rope(WkI x_s)`, one head of 64; `w_t = Ww x_t` (16); `I(t, s) = sum_j
    w_tj relu(qI_tj . kI_s)`, all from the layer's normed input x.
  * Selection: `S_t` = the `topk` positions `s <= t` with the largest
    `I(t, s)`, ties to the lower position (`jax.lax.top_k`'s order); every
    position while `t < topk`.  One selection a query token, shared by all
    its heads.
  * `o_th = sum over S_t of softmax(q_th . k_s,g(h) / sqrt(128)) v_s,g(h)`,
    `g(h) = h // 8`; no sink, no value scale, no window.  Then `Wo`.
  * Feed-forward: softmax over all `num_experts_published` router logits,
    the `num_experts_per_tok` largest, weights the selected probabilities
    over their sum (`norm_topk_prob`), and the sum of `w_e * down_e(silu(
    gate_e y) * up_e y)` over the selected experts HELD HERE (`num_experts`
    of them from `experts_held`'s first on).  What the others would add is
    left out, as in the program.

Departures, none in the arithmetic: leaves are a layer's own (`l3.q_w`), as
`perf/weights.py` draws them; index scores, selection and attention run a
block of 512 queries at a time, the index a head at a time and the
attention a query head at a time (all at once is 4.6 GB of scores a head
at 33,792 positions); the selection is a mask scattered from `top_k`'s
positions; the experts run one at a time over all tokens, each weighted by
the share of the tokens that chose it; weights are widened to float32
where they are used; the head multiplies 128 positions at a time.

`select="recent"` (the control `"recent"` of `served_gaps`) replaces `S_t`
by the `topk` latest positions: what a program would compute that ignored
its index.

`served_gaps` measures a served token against the closest of the paths
that the stated precision cannot tell from the reference's own (`closest`,
`variants`, `one_query`: the same equations for single positions on the
sequence's keys, with a router's near tie taken the other way).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perf.reference import common as C

QUERIES_AT_ONCE = 512


def sizes(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    held = cfg["num_experts"]
    return dict(
        D=cfg["hidden_size"], V=cfg["vocab_size"],
        Hq=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], Fe=cfg["moe_intermediate_size"],
        E_all=cfg.get("num_experts_published", held), E=held,
        first=int(str(cfg.get("experts_held", "0")).split("-")[0]),
        Hi=sa["indexer_num_heads"], Di=sa["indexer_head_dim"],
        topk=sa["topk"], L=cfg["num_hidden_layers"])


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    D, Hq, Hkv, Dh = s["D"], s["Hq"], s["Hkv"], s["Dh"]
    shapes = {"embed": (s["V"], D), "head": (s["V"], D), "ln_g": (D,)}
    for i in range(s["L"]):
        at = f"l{i}."
        shapes.update({
            at + "ln1_g": (D,), at + "ln2_g": (D,),
            at + "q_w": (Hq * Dh, D), at + "k_w": (Hkv * Dh, D),
            at + "v_w": (Hkv * Dh, D), at + "o_w": (D, Hq * Dh),
            at + "q_norm_g": (Dh,), at + "k_norm_g": (Dh,),
            at + "index_q_w": (s["Hi"] * s["Di"], D),
            at + "index_k_w": (s["Di"], D), at + "index_w_w": (s["Hi"], D),
            at + "router_w": (s["E_all"], D),
            at + "gate_e": (s["E"], s["Fe"], D),
            at + "up_e": (s["E"], s["Fe"], D),
            at + "down_e": (s["E"], D, s["Fe"])})
    return shapes


# --- the pieces common.py does not have --------------------------------- #
def rms_norm(x, g, eps: float, prec: str):
    return C.lower(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * g, prec)


def silu(x, prec: str):
    return C.lower(x * jax.nn.sigmoid(x), prec)


def rotary(x, base: float, prec: str, at=None):
    """x (T, H, D): lane j < D/2 of every head pairs with lane j + D/2 and
    turns by `t * base**(-2j/D)` at position t (`at`, else 0..T-1)."""
    T, D = x.shape[0], x.shape[-1]
    half = D // 2
    theta = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                    * (-2.0 / D * math.log(base)))
    at = jnp.arange(T) if at is None else at
    ang = at.astype(jnp.float32)[:, None, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return C.lower(jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1), prec)


def selection(qi, ki, wi, at_q, topk: int, prec: str, select: str):
    """bool (queries, T): `S_t` of the queries at positions `at_q`, from
    their index queries `qi` (queries, Hi, Di) and weights `wi` (queries,
    Hi) against every index key `ki` (T, Di)."""
    T = ki.shape[0]
    at_k = jnp.arange(T)
    causal = at_q[:, None] >= at_k[None, :]
    if select == "recent":
        return causal & (at_q[:, None] - at_k[None, :] < topk)

    def head(score, j):
        dots = C.einsum("qd,kd->qk", qi[:, j], ki, prec)
        return score + wi[:, j][:, None] * jax.nn.relu(dots), None

    score, _ = jax.lax.scan(head, jnp.zeros((qi.shape[0], T), jnp.float32),
                            jnp.arange(qi.shape[1]))
    _, best = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), min(topk, T))
    chosen = jnp.zeros(causal.shape, bool).at[
        jnp.arange(qi.shape[0])[:, None], best].set(True)
    return chosen & causal          # while t < topk: every position


def projections(x, w, cfg: dict, prec: str, at=None):
    """x (T, D), the layer's normed input at the positions `at` (else
    0..T-1) -> q (T, Hq, Dh), k and v (T, Hkv, Dh), the index's qi (T, Hi,
    Di), ki (T, Di) and wi (T, Hi)."""
    s = sizes(cfg)
    T, Hq, Hkv, Dh = x.shape[0], s["Hq"], s["Hkv"], s["Dh"]
    eps, base = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = rms_norm(C.dense(x, w["q_w"], 0.0, prec).reshape(T, Hq, Dh),
                 w["q_norm_g"], eps, prec)
    k = rms_norm(C.dense(x, w["k_w"], 0.0, prec).reshape(T, Hkv, Dh),
                 w["k_norm_g"], eps, prec)
    v = C.dense(x, w["v_w"], 0.0, prec).reshape(T, Hkv, Dh)
    q, k = rotary(q, base, prec, at), rotary(k, base, prec, at)
    qi = rotary(C.dense(x, w["index_q_w"], 0.0, prec)
                .reshape(T, s["Hi"], s["Di"]), base, prec, at)
    ki = rotary(C.dense(x, w["index_k_w"], 0.0, prec)
                .reshape(T, 1, s["Di"]), base, prec, at)[:, 0]
    wi = C.einsum("td,hd->th", x, w["index_w_w"], prec)
    return q, k, v, qi, ki, wi


def attention(x, w, cfg: dict, prec: str, select: str = "index",
              keep: bool = False):
    """x (T, D) -> ((T, D), with `keep` the selection (T, T) bool, else
    None, and the layer's keys (k, v, ki))."""
    s = sizes(cfg)
    T, Hq, Hkv, Dh = x.shape[0], s["Hq"], s["Hkv"], s["Dh"]
    q, k, v, qi, ki, wi = projections(x, w, cfg, prec)
    block = QUERIES_AT_ONCE if T % QUERIES_AT_ONCE == 0 else T

    def queries(b):
        at_q = b * block + jnp.arange(block)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, b * block, block)
        seen = selection(cut(qi), ki, cut(wi), at_q, s["topk"], prec, select)
        q_b = cut(q)

        def head(h):
            kv = h // (Hq // Hkv)
            sc = C.einsum("qd,kd->qk", q_b[:, h], k[:, kv], prec) \
                / jnp.sqrt(jnp.float32(Dh))
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
            return C.lower(C.einsum("qk,kd->qd", p, v[:, kv], prec), prec)

        a = jax.lax.map(head, jnp.arange(Hq))           # (Hq, block, Dh)
        return (a, seen) if keep else (a, None)

    a, seen = jax.lax.map(queries, jnp.arange(T // block))
    a = jnp.moveaxis(a, 1, 2).reshape(T, Hq * Dh)
    return C.dense(a, w["o_w"], 0.0, prec), \
        seen.reshape(T, T) if keep else None, (k, v, ki)


def mlp(x, gate, up, down, prec: str):
    g = silu(C.dense(x, gate, 0.0, prec), prec)
    return C.dense(C.lower(g * C.dense(x, up, 0.0, prec), prec), down, 0.0,
                   prec)


def route(x, w, cfg: dict, prec: str, swap=None):
    """(T, E_all) float32: each token's weight for each expert it selected,
    0 for the others: the softmax's top-k over their sum.  With `swap` (T,)
    bool also the router's margin (T,), the logit of the last expert taken
    less the first one left out's, and where `swap` is set that pair is
    taken the other way round."""
    K = cfg["num_experts_per_tok"]
    lg = C.einsum("td,ed->te", x, w["router_w"], prec)
    g = jax.nn.softmax(lg, -1)
    if swap is None:
        _, idx = jax.lax.top_k(g, K)
    else:
        _, order = jax.lax.top_k(g, K + 1)
        pair = jnp.take_along_axis(lg, order[:, K - 1:], -1)
        idx = jnp.where(swap[:, None] & (jnp.arange(K) == K - 1),
                        order[:, K:], order[:, :K])
    chosen = jnp.any(idx[..., None] == jnp.arange(g.shape[1]), axis=1)
    picked = jnp.where(chosen, g, 0.0)
    share = picked / jnp.sum(picked, -1, keepdims=True)
    return share if swap is None else (share, pair[:, 0] - pair[:, 1])


def routed_ffn(x, w, cfg: dict, prec: str, swap=None):
    """x (T, D) -> (T, D): the selected experts that are held here, one at
    a time over all tokens (`swap`: see `route`, whose margin then comes
    back too)."""
    s = sizes(cfg)
    share = route(x, w, cfg, prec, swap)
    if swap is not None:
        share, margin = share
    share = jax.lax.dynamic_slice_in_dim(share, s["first"], s["E"], axis=1)
    f32 = jnp.float32

    def expert(y, e):
        out = mlp(x, w["gate_e"][e].astype(f32), w["up_e"][e].astype(f32),
                  w["down_e"][e].astype(f32), prec)
        return y + share[:, e][:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(s["E"]))
    return C.lower(y, prec) if swap is None else (C.lower(y, prec), margin)


def layer_leaves(p: dict, i: int) -> dict:
    at = f"l{i}."
    return {k[len(at):]: (v if k.endswith("_e") else v.astype(jnp.float32))
            for k, v in p.items() if k.startswith(at)}


def hidden(p: dict, tok, cfg: dict, prec: str = "fp32",
           select: str = "index", keep: bool = False, at=None):
    """tok (T,) -> the final norm's output (T, D), what the head
    multiplies; with `keep` also every layer's selection (L, T, T); with
    `at` (A,) also what `variants` starts from: the state before every
    layer and behind the last at those positions (L + 1, A, D), and every
    layer's keys, values and index keys."""
    f32, eps = jnp.float32, cfg["rms_norm_eps"]
    h = C.lower(p["embed"][tok].astype(f32), prec)
    kept, states, keys = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        w = layer_leaves(p, i)
        if at is not None:
            states.append(h[at])
        a, seen, kvi = attention(rms_norm(h, w["ln1_g"], eps, prec), w, cfg,
                                 prec, select, keep)
        if keep:
            kept.append(seen)
        if at is not None:
            keys.append(kvi)
        h = C.add(h, a, prec)
        h = C.add(h, routed_ffn(rms_norm(h, w["ln2_g"], eps, prec), w, cfg,
                                prec), prec)
    out = rms_norm(h, p["ln_g"].astype(f32), eps, prec)
    if at is not None:
        return out, (jnp.stack(states + [h[at]]), keys)
    return (out, jnp.stack(kept)) if keep else out


def one_query(q, k, v, qi, ki, wi, t, K, V, KI, topk: int):
    """The attention of `attention` for the one query at position `t`,
    given its own projections and the layer's keys (K, V, KI) of every
    position: the row `t` of those is not read, the query's own `k`, `v`,
    `ki` stand in its place.  -> (Hq, Dh)."""
    T, (Hq, Dh), Hkv = K.shape[0], q.shape, K.shape[1]
    at, hi = jnp.arange(T), C.HI
    own = at == t

    def head(score, j):
        dots = jnp.where(own, jnp.dot(qi[j], ki, precision=hi),
                         jnp.einsum("d,kd->k", qi[j], KI, precision=hi))
        return score + wi[j] * jax.nn.relu(dots), None

    score, _ = jax.lax.scan(head, jnp.zeros((T,), jnp.float32),
                            jnp.arange(qi.shape[0]))
    _, best = jax.lax.top_k(jnp.where(at <= t, score, -jnp.inf),
                            min(topk, T))
    seen = jnp.zeros((T,), bool).at[best].set(True) & (at <= t)
    qg = q.reshape(Hkv, Hq // Hkv, Dh)
    sc = jnp.where(own,
                   jnp.einsum("kgd,kd->kg", qg, k, precision=hi)[..., None],
                   jnp.einsum("kgd,skd->kgs", qg, K, precision=hi)) \
        / jnp.sqrt(jnp.float32(Dh))
    pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
    o = jnp.einsum("kgs,skd->kgd", pr, V, precision=hi) \
        + jnp.take(pr, t, axis=-1)[..., None] * (v - V[t])[:, None, :]
    return o.reshape(Hq, Dh)


def variants(p: dict, start, keys, at, swaps, cfg: dict):
    """What the model puts behind its last norm at the positions `at` (n,)
    where, in the layers `swaps` (n, L; bool) names, that position's
    router takes the first expert it left out for the last one it took,
    the positions before it as they are.  `start` (L + 1, n, D) and `keys`
    are `hidden`'s at those positions.  -> ((n, D), every layer's router
    margin on that path (n, L): the logit of the last expert of the
    ranking's first `num_experts_per_tok` less the next one's)."""
    f32, eps, s = jnp.float32, cfg["rms_norm_eps"], sizes(cfg)
    L = cfg["num_hidden_layers"]
    first = jnp.where(jnp.any(swaps, -1), jnp.argmax(swaps, -1), L)
    h, margins = start[0], []
    for i in range(L):
        w = layer_leaves(p, i)
        q, k, v, qi, ki, wi = projections(rms_norm(h, w["ln1_g"], eps, "fp32"),
                                          w, cfg, "fp32", at)
        K, V, KI = keys[i]
        a = jax.vmap(lambda *x: one_query(*x, K, V, KI, s["topk"]))(
            q, k, v, qi, ki, wi, at)
        hm = h + C.dense(a.reshape(a.shape[0], -1), w["o_w"], 0.0, "fp32")
        f, margin = routed_ffn(rms_norm(hm, w["ln2_g"], eps, "fp32"), w, cfg,
                               "fp32", swaps[:, i])
        margins.append(margin)
        # a layer before the first swap is the sequence's own
        h = jnp.where((first <= i)[:, None], hm + f, start[i + 1])
    return rms_norm(h, p["ln_g"].astype(f32), eps, "fp32"), \
        jnp.stack(margins, 1)


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


def selections(p: dict, tokens, cfg: dict):
    """tokens (T,) -> bool (L, T, T): `S_t` of every layer and query."""
    return hidden(p, tokens, cfg, keep=True)[1]


CLOSEST_OF = 32     # served positions of a sequence whose near ties are tried
TRIED_AT_ONCE = 8   # of them whose paths `variants` takes in one call
SWAPS = 3           # near ties taken the other way on one path, at most


def closest(p: dict, gap, nxt, start, keys, at, cfg: dict, tie: float):
    """`gap` (S,) with, at the `CLOSEST_OF` positions where it is widest,
    the smallest gap over the paths the stated precision cannot tell from
    the reference's own: a router whose last expert taken and first one
    left out lie less than `tie` apart (in logits) may take either, in up
    to `SWAPS` layers of a position, every swap a near tie on the path it
    lies on.  Found greedily, a swap a turn: the one that narrows the gap
    most."""
    L = cfg["num_hidden_layers"]
    e = head_matrix(p)
    wide, ci = jax.lax.top_k(gap, CLOSEST_OF)
    rows = jnp.repeat(ci, L)                            # (CLOSEST_OF * L,)

    def tried(swaps):
        """(n, L) -> the served token's gap on each path (n,), inf where
        a swap of the path is no near tie."""
        def some(x):
            r, sw = x
            h, margin = variants(p, start[:, r], keys, at[r], sw, cfg)
            lg = head(e, h)
            g = jnp.max(lg, -1) - jnp.take_along_axis(
                lg, nxt[r][:, None], -1)[:, 0]
            return jnp.where(jnp.all(~sw | (margin < tie), -1), g, jnp.inf)

        n = swaps.shape[0]
        return jax.lax.map(some, (rows.reshape(-1, TRIED_AT_ONCE * L),
                                  swaps.reshape(-1, TRIED_AT_ONCE * L, L))
                           ).reshape(n)

    def turn(_, state):
        swaps, best = state                             # (C, L), (C,)
        more = swaps[:, None, :] | jnp.eye(L, dtype=bool)[None]
        g = jnp.where(swaps, jnp.inf,
                      tried(more.reshape(-1, L)).reshape(-1, L))
        layer, g = jnp.argmin(g, -1), jnp.min(g, -1)
        better = g < best
        return (swaps | (better[:, None] & (jnp.arange(L) == layer[:, None])),
                jnp.where(better, g, best))

    _, best = jax.lax.fori_loop(
        0, SWAPS, turn, (jnp.zeros((CLOSEST_OF, L), bool), wide))
    return gap.at[ci].set(jnp.where(wide > 0, best, wide))


def served_gaps(p: dict, rows: list, cfg: dict, pad_to: int,
                control: str = None):
    """For each `(prompt, served tokens)`: at every served position, how far
    the served token's logit lies below the reference's best there.  With
    `control`, instead the gap of the token that the reference puts first
    when computed in that precision ("fp8", "bf16") or, in float32, with
    the `topk` latest positions in every selection's place ("recent").  One
    compiled program for every row (padded to `pad_to`: attention is
    causal, so the padding never reaches a compared position).

    The reference's best is the best of the paths that the stated
    precision cannot tell apart (`closest`, with the configuration's
    `check.router_tie`; 0 or absent: the reference's own path alone): the
    choice of `num_experts_per_tok` experts jumps where two router logits
    cross, one such jump in an early layer moves a logit by more than the
    precision below the stated one does (PERF.md section 6, PR 38), and a
    program that holds its state in the stated precision takes the jump or
    not by its rounding."""
    import numpy as np

    rows_at_once = 128      # of the (served positions, V) logits
    prec = "fp32" if control in (None, "recent") else control
    select = "recent" if control == "recent" else "index"
    tie = float(cfg.get("check", {}).get("router_tie", 0.0))
    S = -(-max([len(t) for _, t in rows] + [CLOSEST_OF])
          // rows_at_once) * rows_at_once

    @jax.jit
    def one(p, tokens, n_prompt, n_total):
        T = tokens.shape[0]
        served = n_prompt - 1 + jnp.arange(S)
        at = jnp.clip(served, 0, T - 1)
        h, (start, keys) = hidden(p, tokens, cfg, at=at)
        nxt = jnp.roll(tokens, -1)[at]
        e = head_matrix(p)
        if control is not None:
            hc = hidden(p, tokens, cfg, prec, select)[at]
            ec = head_matrix(p, prec)

        def block(x):
            h_b, hc_b, nxt_b = x
            lg = head(e, h_b)                                  # (rows, V)
            if control is not None:
                nxt_b = jnp.argmax(head(ec, hc_b, prec), -1)
            got = jnp.take_along_axis(lg, nxt_b[:, None], -1)[:, 0]
            return jnp.max(lg, -1) - got, nxt_b

        cut = lambda a: a.reshape((-1, rows_at_once) + a.shape[1:])
        gap, nxt = jax.lax.map(
            block, (cut(h[at]), cut(hc if control is not None else h[at]),
                    cut(nxt)))
        gap = jnp.where(served < n_total - 1, gap.reshape(-1), -1.0)
        if tie > 0:
            gap = closest(p, gap, nxt.reshape(-1), start, keys, at, cfg, tie)
        return gap

    out = []
    for prompt, served in rows:
        seq = np.zeros((pad_to,), np.int32)
        n = len(prompt) + len(served)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = served
        g = np.asarray(one(p, jnp.asarray(seq), len(prompt), n))
        out.append(g[g >= 0].tolist())
    return out
