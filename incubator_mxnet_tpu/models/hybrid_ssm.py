"""A decoder that mixes Mamba-1 layers with attention layers (the Jamba
family's shape): RMSNorm before every mixer and every MLP, a gated SiLU
MLP, grouped-query attention without positional encoding, the Mamba mixer
with Jamba's three extra RMSNorms on ``dt``, ``B`` and ``C``, and a head
tied to the embedding.  Which layer holds which mixer is data
(``attn_layer_period`` / ``attn_layer_offset``).

Built from the keys of the published ``config.json``.  Two ways in:

* `forward(tokens)` — the whole sequence at once from a zero state, one
  jitted function of the parameters, so `autograd.record()` /
  `backward()` work; this is also what the tests hold against the plain
  reference.
* `decoder_spec()` / `decoder_params()` / `decoder_fingerprint()` — the
  description `serving.ServingEngine` serves it by: K/V pages for the
  attention layers, a recurrent state and a conv window a lane for the
  Mamba layers (docs/serving.md, "Two kinds of state").

**One array a kind of weight, not one a layer.**  Every weight that a
layer has is a row of a stacked Parameter: ``gate_w`` is ``(layers,
intermediate, hidden)``, ``in_proj_w`` ``(Mamba layers, 2 d_inner,
hidden)``, ``q_w`` ``(attention layers, heads * head_dim, hidden)``: the
j-th row of a Mamba or attention leaf belongs to the j-th layer of that
kind.  A served program is handed every weight buffer on every call, and
the runtime's cost of a call grows with their number (1.4 us a buffer to
dispatch and 1 us more before the result is back, my chip runs, PR 30):
463 buffers a layer-wise net of 28 layers would hold cost 1.1 ms a call
on the host, which the device waits out twice an iteration.  Stacked they
are two dozen, and XLA reads a layer's rows in place (a static slice of a
parameter fuses into the matmul that consumes it: no copy).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .. import initializer as init_mod
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import apply_op, wrap
from ..ops.selective_scan import selective_scan
from .generation import _dense, _rms

__all__ = ["HybridSSMDecoder", "RMSNorm"]


class RMSNorm(HybridBlock):
    def __init__(self, units, eps, dtype="float32", grad_req="write",
                 **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        self.gamma = self.params.get("gamma", shape=(units,), dtype=dtype,
                                     init="ones", grad_req=grad_req)

    def forward(self, x):
        return apply_op(lambda x, g: _rms(x, g, eps=self._eps), wrap(x),
                        self.gamma.data())


# the stacked leaves by the kind of layer whose rows they hold
_LEAVES = {
    "all": ("ln1_g", "ln2_g", "gate_w", "up_w", "down_w"),
    "ssm": ("in_proj_w", "in_proj_b", "conv_w", "conv_b", "x_proj_w",
            "dt_norm_g", "b_norm_g", "c_norm_g", "dt_proj_w", "dt_proj_b",
            "a_log", "d", "out_proj_w", "out_proj_b"),
    "attn": ("q_w", "k_w", "v_w", "o_w"),
}


def _mlp(x, gate, up, down):
    """down(silu(gate x) * up x), no biases."""
    f32 = jnp.float32
    act = (jax.nn.silu(_dense(x, gate, None).astype(f32))
           * _dense(x, up, None).astype(f32)).astype(x.dtype)
    return _dense(act, down, None)


def _attention(x, q_w, k_w, v_w, o_w, H, Hkv):
    """Causal attention of ``H`` query heads over ``Hkv`` KV heads, no
    biases, no positions."""
    B, T = x.shape[:2]
    D = q_w.shape[0] // H
    f32 = jnp.float32
    q = _dense(x, q_w, None).reshape(B, T, Hkv, H // Hkv, D).astype(f32)
    k = _dense(x, k_w, None).reshape(B, T, Hkv, D).astype(f32)
    v = _dense(x, v_w, None).reshape(B, T, Hkv, D).astype(f32)
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, jnp.finfo(f32).min)
    o = jnp.einsum("bkgqt,btkd->bqkgd", jax.nn.softmax(s, -1), v)
    return _dense(o.reshape(B, T, H * D).astype(x.dtype), o_w, None)


def _mamba(x, w, sizes, eps):
    """Mamba-1 with Jamba's RMSNorms on dt, B and C, from a zero state;
    ``w`` maps a leaf's name to this layer's row of it."""
    Di, Ds, K, R = sizes
    f32 = jnp.float32
    T = x.shape[1]
    uz = _dense(x, w["in_proj_w"], w.get("in_proj_b"))
    u, z = uz[..., :Di], uz[..., Di:]
    pad = jnp.pad(u.astype(f32), ((0, 0), (K - 1, 0), (0, 0)))
    c = sum(pad[:, k:k + T] * w["conv_w"][:, k].astype(f32)
            for k in range(K))
    bias = w.get("conv_b")
    if bias is not None:
        c = c + bias.astype(f32)
    u = jax.nn.silu(c).astype(x.dtype)
    dbc = _dense(u, w["x_proj_w"], None)
    dt = _rms(dbc[..., :R], w["dt_norm_g"], eps=eps)
    Bm = _rms(dbc[..., R:R + Ds], w["b_norm_g"], eps=eps).astype(f32)
    Cm = _rms(dbc[..., R + Ds:], w["c_norm_g"], eps=eps).astype(f32)
    dt = jax.nn.softplus(
        _dense(dt, w["dt_proj_w"], w["dt_proj_b"]).astype(f32))
    A = -jnp.exp(w["a_log"].astype(f32)).T
    state = jnp.zeros((x.shape[0], Ds, Di), f32)
    y = selective_scan(u, dt, z, Bm, Cm, A, w["d"].astype(f32), state,
                       impl="xla")[0]
    return _dense(y, w["out_proj_w"], w.get("out_proj_b"))


@partial(jax.jit, static_argnums=(0,))
def _forward(static, tokens, p):
    """Logits of whole sequences: ``static`` is (layer kinds, query heads,
    KV heads, the Mamba sizes, eps), ``p`` the parameters by attribute
    name."""
    kinds, H, Hkv, sizes, eps = static
    h = p["embed"][tokens]
    count = {"ssm": 0, "attn": 0}
    for i, kind in enumerate(kinds):
        j = count[kind]
        count[kind] += 1
        x = _rms(h, p["ln1_g"][i], eps=eps)
        if kind == "attn":
            h = h + _attention(x, *(p[n][j] for n in _LEAVES["attn"]),
                               H, Hkv)
        else:
            h = h + _mamba(x, {n: p[n][j] for n in _LEAVES["ssm"]
                               if n in p}, sizes, eps)
        h = h + _mlp(_rms(h, p["ln2_g"][i], eps=eps), p["gate_w"][i],
                     p["up_w"][i], p["down_w"][i])
    return jnp.einsum("btd,vd->btv", _rms(h, p["ln"], eps=eps), p["embed"],
                      preferred_element_type=jnp.float32)


class HybridSSMDecoder(HybridBlock):
    """Keyword arguments are the published configuration's keys.
    ``dtype`` is the dtype the parameters are CREATED in (a 3B-parameter
    model in float32 first would not fit beside its own bfloat16 cast);
    ``grad_req="null"`` leaves out the gradient buffers, a second copy of
    the weights that serving never reads.

    Parameters: ``embed.weight``, ``ln.gamma`` and the stacked leaves of
    `_LEAVES`, each an attribute of its name (``net.gate_w``,
    ``net.in_proj_w``, ``net.q_w``, ...), a row a layer of its kind."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads, num_key_value_heads,
                 attn_layer_period, attn_layer_offset, mamba_d_state=16,
                 mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=None,
                 mamba_conv_bias=True, mamba_proj_bias=False,
                 rms_norm_eps=1e-6, max_position_embeddings=4096,
                 tie_word_embeddings=True, dtype="float32",
                 grad_req="write", **kwargs):
        super().__init__(**kwargs)
        if not tie_word_embeddings:
            raise ValueError("HybridSSMDecoder ties its head to its "
                             "embedding")
        if num_attention_heads % num_key_value_heads:
            raise ValueError(
                f"{num_attention_heads} query heads are not a multiple of "
                f"{num_key_value_heads} KV heads")
        C, F = hidden_size, intermediate_size
        Di = mamba_expand * C
        Ds, K = mamba_d_state, mamba_d_conv
        R = mamba_dt_rank or math.ceil(C / 16)
        H, Hkv = num_attention_heads, num_key_value_heads
        D = C // H
        self._units, self._max_len = C, max_position_embeddings
        self._heads, self._kv_heads = H, Hkv
        self._eps = rms_norm_eps
        self._ssm = (Di, Ds, K, R)
        self._kinds = tuple(
            "attn" if i % attn_layer_period == attn_layer_offset else "ssm"
            for i in range(num_hidden_layers))
        L, M = num_hidden_layers, self._kinds.count("ssm")
        A = L - M
        self.embed = nn.Embedding(vocab_size, C, dtype=dtype)
        self.embed.weight.grad_req = grad_req
        # the published initialisation: A = -(1 .. d_state) a channel,
        # D = 1 (the dt bias's inverse softplus of a draw is left at 0)
        a_init = init_mod.Constant(jnp.log(jnp.arange(1.0, Ds + 1.0)))
        shapes = {
            "ln1_g": ((L, C), "ones"), "ln2_g": ((L, C), "ones"),
            "gate_w": ((L, F, C), None), "up_w": ((L, F, C), None),
            "down_w": ((L, C, F), None),
            "in_proj_w": ((M, 2 * Di, C), None),
            "in_proj_b": ((M, 2 * Di), "zeros") if mamba_proj_bias else None,
            "conv_w": ((M, Di, K), None),
            "conv_b": ((M, Di), "zeros") if mamba_conv_bias else None,
            "x_proj_w": ((M, R + 2 * Ds, Di), None),
            "dt_norm_g": ((M, R), "ones"), "b_norm_g": ((M, Ds), "ones"),
            "c_norm_g": ((M, Ds), "ones"),
            "dt_proj_w": ((M, Di, R), None), "dt_proj_b": ((M, Di), "zeros"),
            "a_log": ((M, Di, Ds), a_init), "d": ((M, Di), "ones"),
            "out_proj_w": ((M, C, Di), None),
            "out_proj_b": ((M, C), "zeros") if mamba_proj_bias else None,
            "q_w": ((A, H * D, C), None), "k_w": ((A, Hkv * D, C), None),
            "v_w": ((A, Hkv * D, C), None), "o_w": ((A, C, H * D), None),
        }
        self._stacked = []
        for name, given in shapes.items():
            if given is None or 0 in given[0]:
                continue            # no bias; or no layer of that kind
            setattr(self, name, self.params.get(
                name, shape=given[0], dtype=dtype, init=given[1],
                grad_req=grad_req))
            self._stacked.append(name)
        self.ln = RMSNorm(C, rms_norm_eps, dtype, grad_req)

    def forward(self, tokens):
        tokens = wrap(tokens)
        if tokens.shape[1] > self._max_len:
            raise ValueError(f"sequence {tokens.shape[1]} exceeds "
                             f"max_position_embeddings {self._max_len}")
        names = ["embed", "ln"] + self._stacked
        static = (self._kinds, self._heads, self._kv_heads, self._ssm,
                  self._eps)
        return apply_op(
            lambda t, *leaves: _forward(static, t, dict(zip(names, leaves))),
            tokens, self.embed.weight.data(), self.ln.gamma.data(),
            *(getattr(self, n).data() for n in self._stacked))

    def serve(self, **kw):
        """This net's shared `serving.ServingEngine`, built on first use."""
        from ..serving import default_engine

        return default_engine(self, **kw)

    # -- what the serving programs read ---------------------------------- #
    def decoder_spec(self):
        from .generation import DecoderSpec, SsmSpec

        return DecoderSpec(
            kinds=self._kinds, acts=("silu_gated",) * len(self._kinds),
            norm="rms", eps=self._eps, heads=self._heads,
            kv_heads=self._kv_heads, head_dim=self._units // self._heads,
            positions=False, embed_scale=1.0, ssm=SsmSpec(*self._ssm),
            vocab=self.embed.weight.shape[0], units=self._units,
            max_len=self._max_len)

    def decoder_params(self, pe_width, dense):
        """The weight pytree of `decoder_spec`, its ``layers`` a
        `generation.StackedLayers` over this net's own buffers (``dense``,
        the int8 packer of Dense layers, finds none here).  Three leaves
        are derived once a gather (the caller caches by fingerprint): the
        fused QKV matrix, the conv taps with channels last, and ``A =
        -exp(A_log)`` transposed to the state's layout, float32."""
        from .generation import StackedLayers

        f32 = jnp.float32
        p = {n: getattr(self, n).data()._data for n in self._stacked}

        def wb(w, b=None):
            return (p[w], p.get(b))

        groups = {"all": {
            "ln1": (p["ln1_g"],), "ln2": (p["ln2_g"],),
            "ffn_gate": wb("gate_w"), "ffn1": wb("up_w"),
            "ffn2": wb("down_w")}}
        if "in_proj_w" in p:
            groups["ssm"] = {
                "in_proj": wb("in_proj_w", "in_proj_b"),
                "conv": (jnp.swapaxes(p["conv_w"], 1, 2), p.get("conv_b")),
                "x_proj": wb("x_proj_w"),
                "dt_norm": (p["dt_norm_g"],), "b_norm": (p["b_norm_g"],),
                "c_norm": (p["c_norm_g"],),
                "dt_proj": wb("dt_proj_w", "dt_proj_b"),
                "A": -jnp.swapaxes(jnp.exp(p["a_log"].astype(f32)), 1, 2),
                "D": p["d"].astype(f32),
                "out_proj": wb("out_proj_w", "out_proj_b")}
        if "q_w" in p:
            groups["attn"] = {
                "qkv": (jnp.concatenate([p["q_w"], p["k_w"], p["v_w"]], 1),
                        None),
                "proj": wb("o_w")}
        embed = self.embed.weight.data()._data
        return {"embed": embed, "pe": None,
                "ln": (self.ln.gamma.data()._data,), "head": (embed, None),
                "layers": StackedLayers(groups, self._kinds)}

    def decoder_fingerprint(self):
        leaves = self.__dict__.get("_decoder_leaves")
        if leaves is None:
            leaves = self._decoder_leaves = list(
                self.collect_params().values())
        return tuple(id(p.data()._data) for p in leaves)
