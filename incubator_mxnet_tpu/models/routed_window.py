"""A decoder of full-attention and sliding-window attention layers whose
feed-forwards are routed experts (the published shape of several large
sparse models): RMSNorm before every mixer and feed-forward, rotary
positions on the leading lanes of every query and key head, keys wider
than values, more KV heads in the window layers than in the full ones, a
learned sink logit a head in the window layers' softmax, the attention's
output scaled, and after the leading dense gated-SiLU layers a routed
feed-forward: a sigmoid router over all the experts, the top-k of score +
selection bias, the selected scores renormalised, no shared expert.  Which
layer has a window and which a router is data (``hybrid_layer_pattern``,
``moe_layer_freq``).  The head is not tied to the embedding.

**A chip's share.**  The net holds ``n_routed_experts`` experts of every
routed layer, those from ``first_expert`` on, and routes over
``n_routed_experts_published`` (all of them).  A token's result is the sum
over the experts it chose *that are held here*; what the others would add
is another chip's and is left out, here and in whoever compares with this.
On one chip the layer runs without its exchange.

Two ways in, as `models.hybrid_ssm`:

* `forward(tokens)` — the whole sequence at once, one jitted function of
  the parameters, in plain `jax.numpy`: no cache, no pages, no kernel.  The
  oracle of the CPU tests.
* `decoder_spec()` / `decoder_params()` / `decoder_fingerprint()` — what
  `serving.ServingEngine` serves it by: K/V pages by block table for the
  full layers, a ring of pages a lane for the window layers, the
  ``moe_experts`` kernel for the experts (docs/serving.md, "Window layers
  and routed experts").

**A parameter a layer.**  Unlike `hybrid_ssm`'s stacked leaves, every
weight here is a layer's own Parameter (``q_w3``, ``gate_e5``: the
trailing number is the layer).  At the served size the experts of one
layer are 0.27 GB a leaf; a stack over the layers would be 1.6 GB, and a
benchmark's set-up holds the seed's weights beside the net's until the
last leaf is handed over, so the largest leaf is what its peak adds to
twice the weights.  The price is some 80 weight buffers a program call
(0.2 ms of the host's time, which the one-step-ahead loop hides).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import apply_op, wrap
from .generation import _dense, _rms, _rope
from .hybrid_ssm import RMSNorm

__all__ = ["RoutedWindowDecoder", "PerLayerLeaves"]


def _attention(x, w, H, Hkv, Dk, window, rope, base, scale):
    """One sequence x (T, C): causal attention of ``H`` query heads over
    ``Hkv`` KV heads, within ``window`` positions where it is not 0, the
    sink logit ``w["sink"]`` (where the layer has one) in the
    denominator."""
    T = x.shape[0]
    f32 = jnp.float32
    pos = jnp.arange(T)
    q = _dense(x, w["q"], None).reshape(T, H, Dk)
    k = _dense(x, w["k"], None).reshape(T, Hkv, Dk)
    v = _dense(x, w["v"], None).reshape(T, Hkv, -1)
    # tpulint: disable-next=TPU004 -- the rotary width and base are static Python numbers
    if rope and base:
        q, k = _rope(q, pos, rope, base), _rope(k, pos, rope, base)
    q = q.reshape(T, Hkv, H // Hkv, Dk)
    s = jnp.einsum("qhgd,khd->hgqk", q, k,
                   preferred_element_type=f32) / math.sqrt(Dk)
    gap = pos[:, None] - pos[None, :]
    seen = gap >= 0
    # tpulint: disable-next=TPU004 -- the window is a static Python int
    if window:
        seen &= gap < window
    s = jnp.where(seen, s, jnp.finfo(f32).min)
    # tpulint: disable-next=TPU004 -- dict KEY membership is static pytree structure
    if "sink" in w:
        b = jnp.broadcast_to(
            w["sink"].astype(f32).reshape(Hkv, H // Hkv, 1, 1),
            s.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, b], -1), -1)[..., :-1]
    else:
        p = jax.nn.softmax(s, -1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v, preferred_element_type=f32)
    return _dense((o * scale).reshape(T, -1).astype(x.dtype), w["o"], None)


def _gated(x, gate, up, down):
    f32 = jnp.float32
    act = (jax.nn.silu(_dense(x, gate, None).astype(f32))
           * _dense(x, up, None).astype(f32)).astype(x.dtype)
    return _dense(act, down, None)


def _routed(x, w, top_k, first):
    """x (T, C): a sigmoid router with a selection bias, then the held
    experts."""
    f32 = jnp.float32
    g = jax.nn.sigmoid(jnp.einsum("tc,ec->te", x, w["router"],
                                  preferred_element_type=f32))
    _, idx = jax.lax.top_k(g + w["router_bias"].astype(f32), top_k)
    return _held_experts(x, w, idx, jnp.take_along_axis(g, idx, 1), first)


def _held_experts(x, w, idx, sel, first):
    """x (T, C): every held expert over every token, weighted where the
    token chose it (``idx``, ``sel`` (T, K): the experts each token chose
    and their scores, renormalised here over all K)."""
    f32 = jnp.float32
    wts = sel / jnp.sum(sel, 1, keepdims=True)
    held = w["gate_e"].shape[0]
    share = jnp.sum(jnp.where(
        (idx - first)[..., None] == jnp.arange(held), wts[..., None], 0.0),
        axis=1)                                             # (T, held)
    h = (jax.nn.silu(jnp.einsum("tc,efc->tef", x, w["gate_e"],
                                preferred_element_type=f32))
         * jnp.einsum("tc,efc->tef", x, w["up_e"],
                      preferred_element_type=f32)).astype(x.dtype)
    y = jnp.einsum("tef,ecf->tec", h, w["down_e"],
                   preferred_element_type=f32)
    return jnp.einsum("tec,te->tc", y, share).astype(x.dtype)


@partial(jax.jit, static_argnums=(0,))
def _forward(static, tokens, p):
    """Logits of whole sequences (B, T) -> (B, T, V), float32."""
    (layers, H, Dk, window, rope, scale, eps, top_k, first) = static

    def one(tok):
        h = p["embed"][tok]
        for (Hkv, windowed, base, routed), w in zip(layers, p["layers"]):
            x = _rms(h, w["ln1_g"], eps=eps)
            att = {n[:-2]: w[n] for n in ("q_w", "k_w", "v_w", "o_w")}
            if "sink" in w:
                att["sink"] = w["sink"]
            h = h + _attention(x, att, H, Hkv, Dk,
                               window if windowed else 0, rope, base, scale)
            x = _rms(h, w["ln2_g"], eps=eps)
            h = h + (_routed(x, w, top_k, first) if routed
                     else _gated(x, w["gate_w"], w["up_w"], w["down_w"]))
        return jnp.einsum("td,vd->tv", _rms(h, p["ln"], eps=eps), p["head"],
                          preferred_element_type=jnp.float32)

    return jax.vmap(one)(tokens)


class PerLayerLeaves(HybridBlock):
    """A decoder that holds a Parameter a layer and leaf (``q_w3``: the
    trailing number is the layer; the module's docstring says why), and
    what follows from that alone: the leaves' creation, a dict a layer,
    the eager forward over them, the fingerprint.  A subclass sets
    ``_depth``, ``_max_len`` and ``embed``, creates its leaves with
    `_leaf`, ``head_w`` first, and gives `_forward_static` (a jitted
    ``fn(static, tokens, p)`` and its hashable ``static``)."""

    def _leaf(self, name, shape, init=None, layer=None):
        at = name if layer is None else f"{name}{layer}"
        dtype, grad_req = self._leaf_kw
        setattr(self, at, self.params.get(
            at, shape=shape, dtype=dtype, init=init, grad_req=grad_req))
        self._leaves.append((layer, name))

    def _layer_leaves(self, get):
        """[{name: get(Parameter)}], a dict a layer."""
        out = [{} for _ in range(self._depth)]
        for layer, name in self._leaves:
            if layer is not None:
                out[layer][name] = get(getattr(self, f"{name}{layer}"))
        return out

    def forward(self, tokens):
        tokens = wrap(tokens)
        if tokens.shape[1] > self._max_len:
            raise ValueError(f"sequence {tokens.shape[1]} exceeds "
                             f"max_position_embeddings {self._max_len}")
        fn, static = self._forward_static()
        params = [getattr(self, name if layer is None else f"{name}{layer}")
                  for layer, name in self._leaves]

        def run(t, embed, ln, *leaves):
            at = dict(zip(map(id, params), leaves))
            return fn(static, t, {
                "embed": embed, "ln": ln, "head": leaves[0],
                "layers": self._layer_leaves(lambda p: at[id(p)])})

        return apply_op(run, tokens, self.embed.weight.data(),
                        self.ln.gamma.data(), *(p.data() for p in params))

    def serve(self, **kw):
        """This net's shared `serving.ServingEngine`, built on first use."""
        from ..serving import default_engine

        return default_engine(self, **kw)

    def decoder_fingerprint(self):
        leaves = self.__dict__.get("_decoder_leaves")
        if leaves is None:
            leaves = self._decoder_leaves = list(
                self.collect_params().values())
        return tuple(id(p.data()._data) for p in leaves)


class RoutedWindowDecoder(PerLayerLeaves):
    """Keyword arguments are the published configuration's keys
    (``num_key_value_heads`` / ``rope_theta`` of the full layers, the
    ``swa_`` ones of the window layers; a 1 in ``hybrid_layer_pattern`` is
    a window layer, a 1 in ``moe_layer_freq`` a routed feed-forward).
    ``n_routed_experts`` is how many experts a routed layer holds HERE,
    from ``first_expert`` on; ``n_routed_experts_published`` how many the
    router scores (default: those held).  ``dtype`` is what the parameters
    are created in and ``grad_req="null"`` leaves the gradient buffers
    out, as for `HybridSSMDecoder`.

    Parameters: ``embed.weight``, ``head_w``, ``ln.gamma`` and a layer's
    own leaves, the layer's number last: ``ln1_g<i>``, ``ln2_g<i>``,
    ``q_w<i>``, ``k_w<i>``, ``v_w<i>``, ``o_w<i>``, ``sink<i>`` (window
    layers with a sink), then ``gate_w<i>``, ``up_w<i>``, ``down_w<i>``
    (dense) or ``router<i>``, ``router_bias<i>``, ``gate_e<i>``,
    ``up_e<i>``, ``down_e<i>`` (routed; the experts stacked)."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads, num_key_value_heads,
                 head_dim, v_head_dim, hybrid_layer_pattern, moe_layer_freq,
                 sliding_window, swa_num_key_value_heads,
                 moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, n_routed_experts_published=None,
                 first_expert=0, rope_theta=10000.0, swa_rope_theta=10000.0,
                 partial_rotary_factor=1.0, attention_value_scale=1.0,
                 add_swa_attention_sink_bias=False, layernorm_epsilon=1e-5,
                 max_position_embeddings=4096, tie_word_embeddings=False,
                 dtype="float32", grad_req="write", **kwargs):
        super().__init__(**kwargs)
        if tie_word_embeddings:
            raise ValueError("RoutedWindowDecoder's head is its own matrix")
        L = num_hidden_layers
        if len(hybrid_layer_pattern) != L or len(moe_layer_freq) != L:
            raise ValueError(
                f"hybrid_layer_pattern ({len(hybrid_layer_pattern)}) and "
                f"moe_layer_freq ({len(moe_layer_freq)}) need one entry for "
                f"each of the {L} layers")
        C, H, Dk, Dv = hidden_size, num_attention_heads, head_dim, v_head_dim
        for Hkv in (num_key_value_heads, swa_num_key_value_heads):
            if H % Hkv:
                raise ValueError(f"{H} query heads are not a multiple of "
                                 f"{Hkv} KV heads")
        E = n_routed_experts
        E_all = n_routed_experts_published or E
        if not 0 <= first_expert <= E_all - E:
            raise ValueError(f"experts {first_expert} .. {first_expert + E} "
                             f"are not among the {E_all} routed over")
        # the rotary width: the even number of lanes nearest the factor
        rope = 2 * round(partial_rotary_factor * Dk / 2)
        self._units, self._max_len = C, max_position_embeddings
        self._heads, self._dk, self._dv = H, Dk, Dv
        self._window, self._rope = int(sliding_window), rope
        self._scale, self._eps = float(attention_value_scale), \
            layernorm_epsilon
        self._top_k, self._first = num_experts_per_tok, first_expert
        self._experts = (E_all, E, moe_intermediate_size)
        # (KV heads, windowed, rotary base, routed) a layer
        self._layers = tuple(
            (swa_num_key_value_heads if win else num_key_value_heads,
             bool(win), float(swa_rope_theta if win else rope_theta),
             bool(moe))
            for win, moe in zip(hybrid_layer_pattern, moe_layer_freq))
        self._sink = bool(add_swa_attention_sink_bias)
        self._depth = L
        self.embed = nn.Embedding(vocab_size, C, dtype=dtype)
        self.embed.weight.grad_req = grad_req
        F, Fe = intermediate_size, moe_intermediate_size
        self._leaves = []       # (layer or None, name without the layer)
        self._leaf_kw = (dtype, grad_req)
        leaf = self._leaf
        leaf("head_w", (vocab_size, C))
        for i, (Hkv, win, _, moe) in enumerate(self._layers):
            shapes = {"ln1_g": ((C,), "ones"), "ln2_g": ((C,), "ones"),
                      "q_w": ((H * Dk, C),), "k_w": ((Hkv * Dk, C),),
                      "v_w": ((Hkv * Dv, C),), "o_w": ((C, H * Dv),)}
            if win and self._sink:
                shapes["sink"] = ((H,), "zeros")
            if moe:
                shapes.update({
                    "router": ((E_all, C),),
                    "router_bias": ((E_all,), "zeros"),
                    "gate_e": ((E, Fe, C),), "up_e": ((E, Fe, C),),
                    "down_e": ((E, C, Fe),)})
            else:
                shapes.update({"gate_w": ((F, C),), "up_w": ((F, C),),
                               "down_w": ((C, F),)})
            for name, given in shapes.items():
                leaf(name, *given, layer=i)
        self.ln = RMSNorm(C, layernorm_epsilon, dtype, grad_req)

    def _forward_static(self):
        return _forward, (self._layers, self._heads, self._dk, self._window,
                          self._rope, self._scale, self._eps, self._top_k,
                          self._first)

    # -- what the serving programs read ---------------------------------- #
    def decoder_spec(self):
        from .generation import AttnSpec, DecoderSpec, MoeSpec

        E_all, E, Fe = self._experts
        routed = any(moe for *_, moe in self._layers)
        return DecoderSpec(
            kinds=("attn",) * len(self._layers),
            acts=tuple("routed" if moe else "silu_gated"
                       for *_, moe in self._layers),
            norm="rms", eps=self._eps, heads=self._heads,
            kv_heads=self._layers[0][0], head_dim=self._dk,
            positions=False, embed_scale=1.0, ssm=None,
            vocab=self.embed.weight.shape[0], units=self._units,
            max_len=self._max_len,
            attn=tuple(AttnSpec(Hkv, self._window if win else 0,
                                win and self._sink, base)
                       for Hkv, win, base, _ in self._layers),
            v_dim=self._dv, rope_dim=self._rope, value_scale=self._scale,
            moe=MoeSpec(E_all, self._first, E, self._top_k, Fe)
            if routed else None)

    def decoder_params(self, pe_width, dense):
        """The weight pytree of `decoder_spec`: ``layers`` a list, each
        layer's dict over this net's own buffers (nothing is copied,
        fused or stacked; ``dense``, the int8 packer of Dense layers, finds
        none here)."""
        layers = []
        for w in self._layer_leaves(lambda p: p.data()._data):
            lp = {"ln1": (w["ln1_g"],), "ln2": (w["ln2_g"],),
                  "q": (w["q_w"], None), "k": (w["k_w"], None),
                  "v": (w["v_w"], None), "proj": (w["o_w"], None)}
            if "sink" in w:
                lp["sink"] = w["sink"]
            if "router" in w:
                lp["router"] = (w["router"], w["router_bias"])
                lp["experts"] = (w["gate_e"], w["up_e"], w["down_e"])
            else:
                lp["ffn_gate"] = (w["gate_w"], None)
                lp["ffn1"] = (w["up_w"], None)
                lp["ffn2"] = (w["down_w"], None)
            layers.append(lp)
        return {"embed": self.embed.weight.data()._data, "pe": None,
                "ln": (self.ln.gamma.data()._data,),
                "head": (self.head_w.data()._data, None), "layers": layers}
