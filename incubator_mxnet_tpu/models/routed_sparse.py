"""A decoder of grouped-query attention layers that attend a learned
selection of their context, with routed experts for feed-forwards (the
published shape of several long-context sparse models): RMSNorm before
every mixer and feed-forward, an RMSNorm with a gain over every query and
key head, rotary positions on all of a head's lanes, and in every layer an
index: ``indexer_num_heads`` index query heads of ``indexer_head_dim``
against one index key a position score every earlier position, ``I(t, s) =
sum_j w_tj relu(qI_tj . kI_s)``, and a query attends the ``topk`` positions
that score highest (all of them while there are no more; ties to the lower
position), the same for all its heads.  The feed-forward is a softmax
router over all the experts, the top-k renormalised, no bias and no shared
expert.  The head is not tied to the embedding.

**A chip's share**, as `models.routed_window`: the net holds
``num_experts`` experts of every layer, those from ``first_expert`` on, and
routes over ``num_experts_published``; what the others would add is another
chip's and is left out.  **A parameter a layer and leaf** (``q_w3``,
``gate_e5``), for the reason given there.

Two ways in:

* `forward(tokens)` — the whole sequence at once in plain `jax.numpy`: the
  index scores of every pair of positions, `jax.lax.top_k` a query, full
  attention under the selection's mask.  No cache, no pages, no kernel: the
  oracle of the CPU tests.
* `decoder_spec()` / `decoder_params()` / `decoder_fingerprint()` — what
  `serving.ServingEngine` serves it by: K, V and index-key pages by block
  table, `ops.sparse_attention` for the scores, the selection and the
  attention (docs/serving.md, "An index over the pages").
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..gluon import nn
from .generation import _dense, _rms, _rope
from .hybrid_ssm import RMSNorm
from .routed_window import PerLayerLeaves, _held_experts

__all__ = ["RoutedSparseDecoder"]


def _attention(x, w, H, Hkv, D, index, base, eps):
    """One sequence x (T, C): the index picks each query's positions, then
    causal grouped-query attention over those alone."""
    heads, dim, topk = index
    T = x.shape[0]
    f32 = jnp.float32
    pos = jnp.arange(T)
    q = _rms(_dense(x, w["q_w"], None).reshape(T, H, D), w["q_norm_g"],
             eps=eps)
    k = _rms(_dense(x, w["k_w"], None).reshape(T, Hkv, D), w["k_norm_g"],
             eps=eps)
    v = _dense(x, w["v_w"], None).reshape(T, Hkv, D)
    q, k = _rope(q, pos, D, base), _rope(k, pos, D, base)
    qi = _rope(_dense(x, w["index_q_w"], None).reshape(T, heads, dim), pos,
               dim, base)
    ki = _rope(_dense(x, w["index_k_w"], None).reshape(T, 1, dim), pos, dim,
               base)[:, 0]
    wi = jnp.einsum("tc,hc->th", x, w["index_w_w"],
                    preferred_element_type=f32)
    score = jnp.einsum(
        "qhk,qh->qk", jax.nn.relu(jnp.einsum(
            "qhd,kd->qhk", qi, ki, preferred_element_type=f32)), wi)
    causal = pos[:, None] >= pos[None, :]
    _, best = jax.lax.top_k(jnp.where(causal, score, jnp.finfo(f32).min),
                            min(topk, T))
    seen = jnp.zeros((T, T), bool).at[pos[:, None], best].set(True) & causal
    s = jnp.einsum("qhgd,khd->hgqk", q.reshape(T, Hkv, H // Hkv, D), k,
                   preferred_element_type=f32) / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(seen, s, jnp.finfo(f32).min), -1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v, preferred_element_type=f32)
    return _dense(o.reshape(T, -1).astype(x.dtype), w["o_w"], None)


def _routed(x, w, top_k, first):
    """x (T, C): a softmax router over all the experts, no bias, then the
    held experts."""
    g = jax.nn.softmax(jnp.einsum("tc,ec->te", x, w["router"],
                                  preferred_element_type=jnp.float32), -1)
    sel, idx = jax.lax.top_k(g, top_k)
    return _held_experts(x, w, idx, sel, first)


@partial(jax.jit, static_argnums=(0,))
def _forward(static, tokens, p):
    """Logits of whole sequences (B, T) -> (B, T, V), float32."""
    H, Hkv, D, index, base, eps, top_k, first = static

    def one(tok):
        h = p["embed"][tok]
        for w in p["layers"]:
            h = h + _attention(_rms(h, w["ln1_g"], eps=eps), w, H, Hkv, D,
                               index, base, eps)
            h = h + _routed(_rms(h, w["ln2_g"], eps=eps), w, top_k, first)
        return jnp.einsum("td,vd->tv", _rms(h, p["ln"], eps=eps), p["head"],
                          preferred_element_type=jnp.float32)

    return jax.vmap(one)(tokens)


class RoutedSparseDecoder(PerLayerLeaves):
    """Keyword arguments are the published configuration's keys;
    ``sa_config`` is its group of the index's sizes (``indexer_num_heads``,
    ``indexer_head_dim``, ``indexer_num_kv_heads`` 1, ``topk``).
    ``num_experts`` is how many experts a layer holds HERE, from
    ``first_expert`` on; ``num_experts_published`` how many the router
    scores (default: those held).  ``dtype`` and ``grad_req="null"`` as
    for `RoutedWindowDecoder`.

    Parameters: ``embed.weight``, ``head_w``, ``ln.gamma`` and a layer's
    own leaves, the layer's number last: ``ln1_g<i>``, ``ln2_g<i>``,
    ``q_w<i>``, ``k_w<i>``, ``v_w<i>``, ``o_w<i>``, ``q_norm_g<i>``,
    ``k_norm_g<i>``, ``index_q_w<i>``, ``index_k_w<i>``, ``index_w_w<i>``,
    ``router<i>``, ``gate_e<i>``, ``up_e<i>``, ``down_e<i>`` (the experts
    stacked)."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 moe_intermediate_size, num_experts, num_experts_per_tok,
                 sa_config, num_experts_published=None, first_expert=0,
                 rope_theta=10000.0, rms_norm_eps=1e-6,
                 max_position_embeddings=4096, tie_word_embeddings=False,
                 norm_topk_prob=True, dtype="float32", grad_req="write",
                 **kwargs):
        super().__init__(**kwargs)
        if tie_word_embeddings:
            raise ValueError("RoutedSparseDecoder's head is its own matrix")
        if not norm_topk_prob:
            raise ValueError("RoutedSparseDecoder renormalises the selected "
                             "experts' weights (norm_topk_prob)")
        if sa_config.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("RoutedSparseDecoder's index has one key a "
                             "position (indexer_num_kv_heads 1)")
        C, H, Hkv, D = (hidden_size, num_attention_heads,
                        num_key_value_heads, head_dim)
        if H % Hkv:
            raise ValueError(f"{H} query heads are not a multiple of "
                             f"{Hkv} KV heads")
        E, Fe = num_experts, moe_intermediate_size
        E_all = num_experts_published or E
        if not 0 <= first_expert <= E_all - E:
            raise ValueError(f"experts {first_expert} .. {first_expert + E} "
                             f"are not among the {E_all} routed over")
        Hi, Di = sa_config["indexer_num_heads"], sa_config["indexer_head_dim"]
        self._units, self._max_len = C, max_position_embeddings
        self._heads, self._kv_heads, self._dim = H, Hkv, D
        self._index = (Hi, Di, int(sa_config["topk"]))
        self._base, self._eps = float(rope_theta), rms_norm_eps
        self._top_k, self._first = num_experts_per_tok, first_expert
        self._experts = (E_all, E, Fe)
        self._depth = num_hidden_layers
        self.embed = nn.Embedding(vocab_size, C, dtype=dtype)
        self.embed.weight.grad_req = grad_req
        self._leaves = []       # (layer or None, name without the layer)
        self._leaf_kw = (dtype, grad_req)
        self._leaf("head_w", (vocab_size, C))
        shapes = {"ln1_g": ((C,), "ones"), "ln2_g": ((C,), "ones"),
                  "q_w": ((H * D, C),), "k_w": ((Hkv * D, C),),
                  "v_w": ((Hkv * D, C),), "o_w": ((C, H * D),),
                  "q_norm_g": ((D,), "ones"), "k_norm_g": ((D,), "ones"),
                  "index_q_w": ((Hi * Di, C),), "index_k_w": ((Di, C),),
                  "index_w_w": ((Hi, C),), "router": ((E_all, C),),
                  "gate_e": ((E, Fe, C),), "up_e": ((E, Fe, C),),
                  "down_e": ((E, C, Fe),)}
        for i in range(num_hidden_layers):
            for name, given in shapes.items():
                self._leaf(name, *given, layer=i)
        self.ln = RMSNorm(C, rms_norm_eps, dtype, grad_req)

    def _forward_static(self):
        return _forward, (self._heads, self._kv_heads, self._dim, self._index,
                          self._base, self._eps, self._top_k, self._first)

    # -- what the serving programs read ---------------------------------- #
    def decoder_spec(self):
        from .generation import AttnSpec, DecoderSpec, IndexSpec, MoeSpec

        E_all, E, Fe = self._experts
        layer = AttnSpec(self._kv_heads, 0, False, self._base,
                         index=IndexSpec(*self._index), qk_norm=True)
        return DecoderSpec(
            kinds=("attn",) * self._depth, acts=("routed",) * self._depth,
            norm="rms", eps=self._eps, heads=self._heads,
            kv_heads=self._kv_heads, head_dim=self._dim, positions=False,
            embed_scale=1.0, ssm=None, vocab=self.embed.weight.shape[0],
            units=self._units, max_len=self._max_len,
            attn=(layer,) * self._depth, rope_dim=self._dim,
            moe=MoeSpec(E_all, self._first, E, self._top_k, Fe,
                        scoring="softmax"))

    def decoder_params(self, pe_width, dense):
        """The weight pytree of `decoder_spec`: ``layers`` a list, each
        layer's dict over this net's own buffers (nothing is copied, fused
        or stacked; ``dense``, the int8 packer of Dense layers, finds none
        here)."""
        layers = [
            {"ln1": (w["ln1_g"],), "ln2": (w["ln2_g"],),
             "q": (w["q_w"], None), "k": (w["k_w"], None),
             "v": (w["v_w"], None), "proj": (w["o_w"], None),
             "q_norm": (w["q_norm_g"],), "k_norm": (w["k_norm_g"],),
             "index_q": (w["index_q_w"], None),
             "index_k": (w["index_k_w"], None),
             "index_w": (w["index_w_w"], None),
             "router": (w["router"], None),
             "experts": (w["gate_e"], w["up_e"], w["down_e"])}
            for w in self._layer_leaves(lambda p: p.data()._data)]
        return {"embed": self.embed.weight.data()._data, "pe": None,
                "ln": (self.ln.gamma.data()._data,),
                "head": (self.head_w.data()._data, None), "layers": layers}
