"""Transformer encoder-decoder for WMT En-De (BASELINE config #4).

GluonNLP/Sockeye-shaped `transformer_big`: pre-LN enc-dec with shared
source/target embeddings, causal flash attention in the decoder, and
label-smoothed CE.  The reference exposed only the fused attention ops
(SURVEY.md §2.3); the full model is built Gluon-style here.
"""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp

from .. import ndarray as nd
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import NDArray, apply_op, wrap
from .bert import MultiHeadAttention, PositionwiseFFN

__all__ = ["Transformer", "TransformerEncoder", "TransformerDecoder",
           "TransformerLM", "transformer_base", "transformer_big",
           "LabelSmoothedCELoss"]


# above this max_len, TransformerLM computes pe in-program instead of
# precomputing a table (see __init__)
_PE_TABLE_MAX = 8192


def positional_encoding(T, C, dtype=jnp.float32):
    pos = jnp.arange(T)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, C, 2).astype(jnp.float32)
    angle = pos / jnp.power(10000.0, dim / C)
    pe = jnp.zeros((T, C))
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle[:, : (C // 2)]))
    return pe.astype(dtype)


class _CausalSelfAttention(MultiHeadAttention):
    _causal_attn = True

    def forward(self, x, mask=None):
        from ..ops.flash_attention import flash_attention

        if self._sp_mesh is not None:
            # ring-attention SP routing lives in the base class (the
            # causal flag rides on _causal_attn)
            return super().forward(x, mask)
        x = wrap(x)
        B, T, C = x.shape
        H, D = self._num_heads, C // self._num_heads
        qkv = self.qkv(x)

        def attend(qkv_raw):
            q, k, v = jnp.split(qkv_raw, 3, axis=-1)
            q = q.reshape(B, T, H, D).transpose(0, 2, 1, 3)
            k = k.reshape(B, T, H, D).transpose(0, 2, 1, 3)
            v = v.reshape(B, T, H, D).transpose(0, 2, 1, 3)
            out = flash_attention(q, k, v, causal=True)
            return out.transpose(0, 2, 1, 3).reshape(B, T, C)

        return self.proj(apply_op(attend, qkv))


class _CrossAttention(HybridBlock):
    def __init__(self, units, num_heads, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._num_heads = num_heads
        self.q_proj = nn.Dense(units, flatten=False, in_units=units)
        self.kv_proj = nn.Dense(2 * units, flatten=False, in_units=units)
        self.proj = nn.Dense(units, flatten=False, in_units=units)

    def forward(self, x, mem, mem_mask=None):
        import jax

        x, mem = wrap(x), wrap(mem)
        B, Tq, C = x.shape
        Tk = mem.shape[1]
        H, D = self._num_heads, C // self._num_heads
        q = self.q_proj(x)
        kv = self.kv_proj(mem)

        def attend(q_raw, kv_raw, *mask_raw):
            qh = q_raw.reshape(B, Tq, H, D).transpose(0, 2, 1, 3)
            k, v = jnp.split(kv_raw, 2, axis=-1)
            kh = k.reshape(B, Tk, H, D).transpose(0, 2, 1, 3)
            vh = v.reshape(B, Tk, H, D).transpose(0, 2, 1, 3)
            s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                           kh.astype(jnp.float32)) / math.sqrt(D)
            if mask_raw:
                m = mask_raw[0].reshape(B, 1, 1, Tk)
                s = jnp.where(m.astype(bool), s, jnp.finfo(jnp.float32).min)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
            return out.astype(q_raw.dtype).transpose(0, 2, 1, 3).reshape(B, Tq, C)

        if mem_mask is not None:
            out = apply_op(attend, q, kv, wrap(mem_mask))
        else:
            out = apply_op(attend, q, kv)
        return self.proj(out)


class _EncoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout, **kwargs):
        super().__init__(**kwargs)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.attn = MultiHeadAttention(units, num_heads, dropout)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout, activation="relu")
        self.drop_add = nn.DropoutAdd(dropout)

    def forward(self, x, mask=None):
        x = wrap(x)
        x = self.drop_add(self.attn(self.ln1(x), mask), x)
        return self.drop_add(self.ffn(self.ln2(x)), x)


class _DecoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout, **kwargs):
        super().__init__(**kwargs)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.self_attn = _CausalSelfAttention(units, num_heads, dropout)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.cross_attn = _CrossAttention(units, num_heads)
        self.ln3 = nn.LayerNorm(in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout, activation="relu")
        self.drop_add = nn.DropoutAdd(dropout)

    def forward(self, x, mem, mem_mask=None):
        x = wrap(x)
        x = self.drop_add(self.self_attn(self.ln1(x)), x)
        x = self.drop_add(self.cross_attn(self.ln2(x), mem, mem_mask), x)
        return self.drop_add(self.ffn(self.ln3(x)), x)


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout, **kwargs):
        super().__init__(**kwargs)
        self._layers = []
        for i in range(num_layers):
            l = _EncoderLayer(units, hidden_size, num_heads, dropout)
            setattr(self, f"layer{i}", l)
            self._layers.append(l)
        self.ln = nn.LayerNorm(in_channels=units)

    def forward(self, x, mask=None):
        for l in self._layers:
            x = l(x, mask)
        return self.ln(x)


class TransformerDecoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout, **kwargs):
        super().__init__(**kwargs)
        self._layers = []
        for i in range(num_layers):
            l = _DecoderLayer(units, hidden_size, num_heads, dropout)
            setattr(self, f"layer{i}", l)
            self._layers.append(l)
        self.ln = nn.LayerNorm(in_channels=units)

    def forward(self, x, mem, mem_mask=None):
        for l in self._layers:
            x = l(x, mem, mem_mask)
        return self.ln(x)


class _LMLayer(HybridBlock):
    """Decoder-only layer: pre-LN causal self-attention + FFN."""

    def __init__(self, units, hidden_size, num_heads, dropout, **kwargs):
        super().__init__(**kwargs)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.attn = _CausalSelfAttention(units, num_heads, dropout)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                   activation="gelu")
        self.drop_add = nn.DropoutAdd(dropout)

    def forward(self, x):
        x = wrap(x)
        x = self.drop_add(self.attn(self.ln1(x)), x)
        return self.drop_add(self.ffn(self.ln2(x)), x)


class TransformerLM(HybridBlock):
    """Decoder-only (GPT-style) language model — the long-context
    workhorse: on a mesh with seq>1 (`parallel.shard_params`), every
    causal attention routes through ring sequence parallelism, so
    context length scales linearly with the ring size (SURVEY.md §5.7).
    """

    def __init__(self, vocab=32000, units=512, hidden_size=2048,
                 num_layers=6, num_heads=8, max_len=4096, dropout=0.1,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._max_len = max_len
        self.embed = nn.Embedding(vocab, units)
        self._layers = []
        for i in range(num_layers):
            l = _LMLayer(units, hidden_size, num_heads, dropout)
            setattr(self, f"layer{i}", l)
            self._layers.append(l)
        self.ln = nn.LayerNorm(in_channels=units)
        self.head = nn.Dense(vocab, flatten=False, in_units=units)
        # Small max_len: build the table once (rebuilding per EAGER
        # forward costs several dispatches per step).  Long-context
        # models (max_len > _PE_TABLE_MAX) compute pe IN-PROGRAM
        # instead: the closed-over table would otherwise embed an
        # O(max_len*units) fp32 CONSTANT into every compiled program —
        # at max_len=65536 that is 256 MB of HLO literal, which every
        # deployment pays in program size; sin/cos over the slice is
        # VPU noise under jit.
        self._pe = positional_encoding(max_len, units) \
            if max_len <= _PE_TABLE_MAX else None

    def forward(self, tokens):
        tokens = wrap(tokens)
        T = tokens.shape[1]
        if T > self._max_len:
            raise ValueError(f"sequence {T} exceeds max_len {self._max_len}")
        h = self.embed(tokens) * math.sqrt(self._units)
        pe = self._pe
        C = self._units

        if pe is None:
            h = apply_op(
                lambda r: r + positional_encoding(T, C).astype(r.dtype), h)
        else:
            h = apply_op(lambda r: r + pe[:T].astype(r.dtype), h)
        for l in self._layers:
            h = l(h)
        return self.head(self.ln(h))

    # -- what the decode and serving programs read ----------------------- #
    def decoder_spec(self):
        """This decoder as `generation.DecoderSpec`: pre-LN layers of
        fused-QKV attention (as many KV heads as query heads) and a
        two-matrix FFN, sinusoidal positions on an embedding scaled by
        sqrt(units), a head of its own."""
        from .generation import DecoderSpec

        H = self._layers[0].attn._num_heads
        return DecoderSpec(
            kinds=("attn",) * len(self._layers),
            acts=tuple(lyr.ffn._act for lyr in self._layers),
            norm="layer", eps=1e-5, heads=H, kv_heads=H,
            head_dim=self._units // H, positions=True,
            embed_scale=math.sqrt(self._units), ssm=None,
            vocab=self.embed.weight.shape[0], units=self._units,
            max_len=self._max_len)

    def decoder_params(self, pe_width, dense):
        """The weight pytree of `decoder_spec` from the live parameter
        buffers; ``dense(layer)`` gives a Dense layer's ``(weight,
        bias)``, quantized where the caller says so."""
        from .generation import _pe_table

        def ln(layer):
            return (layer.gamma.data()._data, layer.beta.data()._data)

        layers = [{
            "ln1": ln(lyr.ln1),
            "qkv": dense(lyr.attn.qkv),
            "proj": dense(lyr.attn.proj),
            "ln2": ln(lyr.ln2),
            "ffn1": dense(lyr.ffn.ffn_dense1),
            "ffn2": dense(lyr.ffn.ffn_dense2),
        } for lyr in self._layers]
        # long-context nets (_pe=None) get an eagerly-built table of just
        # the width this program needs, cached on the net — pe enters the
        # compiled program as an ARGUMENT here, so the giant-constant
        # problem the in-program forward avoids does not apply
        pe = self._pe if self._pe is not None else _pe_table(self, pe_width)
        return {
            "embed": self.embed.weight.data()._data,
            "pe": pe,
            "ln": ln(self.ln),
            "head": dense(self.head),
            "layers": layers,
        }

    def decoder_fingerprint(self):
        """Identities of the buffers `decoder_params` gathers."""
        def ids(*params):
            return [0 if p is None else id(p.data()._data) for p in params]

        out = ids(self.embed.weight, self.ln.gamma, self.ln.beta,
                  self.head.weight, self.head.bias)
        for lyr in self._layers:
            out += ids(lyr.ln1.gamma, lyr.ln1.beta,
                       lyr.attn.qkv.weight, lyr.attn.qkv.bias,
                       lyr.attn.proj.weight, lyr.attn.proj.bias,
                       lyr.ln2.gamma, lyr.ln2.beta,
                       lyr.ffn.ffn_dense1.weight, lyr.ffn.ffn_dense1.bias,
                       lyr.ffn.ffn_dense2.weight, lyr.ffn.ffn_dense2.bias)
        return tuple(out)

    def generate(self, prompt, max_new_tokens, **kw):
        """KV-cache autoregressive decode — one compiled prefill+scan
        program; see `models.generation.lm_generate` for options
        (temperature / top_k / eos_id / seed)."""
        from .generation import lm_generate

        return lm_generate(self, prompt, max_new_tokens, **kw)

    def beam_search(self, prompt, max_new_tokens, **kw):
        """K-beam decode → (sequences (B, K, P+N), scores (B, K)),
        best-first; see `models.generation.lm_beam_search` (beam_size /
        eos_id / GNMT length-penalty alpha)."""
        from .generation import lm_beam_search

        return lm_beam_search(self, prompt, max_new_tokens, **kw)

    def score(self, tokens, **kw):
        """Teacher-forced per-token log-probs through the decode
        stack's numerics; see `models.generation.lm_score`."""
        from .generation import lm_score

        return lm_score(self, tokens, **kw)

    def serve(self, **kw):
        """This net's shared continuous-batching serving engine
        (paged KV cache, bounded admission queue, deadlines/eviction);
        built on first use, reused after.  See
        `serving.ServingEngine` for the config kwargs and
        `generation.lm_stream` for one-call streaming."""
        from ..serving import default_engine

        return default_engine(self, **kw)

    def quantize_for_decode(self, **kw):
        """Weight-quantize this net's transformer matmuls for decode
        (per-channel int8 + fp32 scales; int8 weights stream through
        the compiled generate/beam-search programs).  See
        `contrib.quantization.quantize_for_decode`."""
        from ..contrib.quantization import quantize_for_decode

        return quantize_for_decode(self, **kw)

    def dequantize_decode(self):
        """Drop the decode-quantization marking — generation goes back
        to the float path."""
        from ..contrib.quantization import dequantize_decode

        return dequantize_decode(self)


class Transformer(HybridBlock):
    def __init__(self, src_vocab=32000, tgt_vocab=32000, units=512,
                 hidden_size=2048, num_layers=6, num_heads=8, dropout=0.1,
                 max_length=1024, share_embed=True, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self.src_embed = nn.Embedding(src_vocab, units)
        self.tgt_embed = self.src_embed if (share_embed and src_vocab == tgt_vocab) \
            else nn.Embedding(tgt_vocab, units)
        if self.tgt_embed is self.src_embed:
            self._children["tgt_embed"] = self.src_embed
        self.encoder = TransformerEncoder(num_layers, units, hidden_size, num_heads, dropout)
        self.decoder = TransformerDecoder(num_layers, units, hidden_size, num_heads, dropout)
        self.out_proj = nn.Dense(tgt_vocab, flatten=False, in_units=units)
        self.drop = nn.Dropout(dropout)
        self._max_length = max_length

    def _embed(self, embed, tokens):
        tokens = wrap(tokens)
        B, T = tokens.shape
        x = embed(tokens) * math.sqrt(self._units)
        pe = NDArray(positional_encoding(T, self._units))
        return self.drop(x + pe)

    def translate(self, src, max_len, **kw):
        """KV-cache incremental translation — encoder once (public
        block), decoder as one compiled loop; greedy by default,
        K-beam via ``beam_size=K``.  See
        `models.generation.nmt_translate` for all options."""
        from .generation import nmt_translate

        return nmt_translate(self, src, max_len, **kw)

    def quantize_for_decode(self, **kw):
        """Weight-quantize the DECODER's matmuls for translation
        (per-channel int8 + fp32 scales; the encoder stays float).  See
        `contrib.quantization.quantize_for_decode`."""
        from ..contrib.quantization import quantize_for_decode

        return quantize_for_decode(self, **kw)

    def dequantize_decode(self):
        """Drop the decode-quantization marking — translation goes back
        to the float path."""
        from ..contrib.quantization import dequantize_decode

        return dequantize_decode(self)

    def forward(self, src_tokens, tgt_tokens, src_valid_length=None):
        src = self._embed(self.src_embed, src_tokens)
        mask = None
        if src_valid_length is not None:
            vl = wrap(src_valid_length)
            T = src.shape[1]
            mask = NDArray((jnp.arange(T)[None, :] < vl._data.reshape(-1, 1))
                           .astype(jnp.float32))
        mem = self.encoder(src, mask)
        tgt = self._embed(self.tgt_embed, tgt_tokens)
        dec = self.decoder(tgt, mem, mask)
        return self.out_proj(dec)


class LabelSmoothedCELoss(HybridBlock):
    def __init__(self, smoothing=0.1, ignore_index=-1, **kwargs):
        super().__init__(**kwargs)
        self._eps = smoothing
        self._ignore = ignore_index
        # hybridized like gluon.loss.*: `loss_fn(net(x), y)` chains into
        # the single fused train-step program instead of forcing the
        # net's pending step (block._try_chain)
        self.hybridize()

    def forward(self, logits, labels):
        import jax

        from ..ops.xent_kernel import fused_smoothed_xent, should_fuse

        def f(lg, lb):
            V = lg.shape[-1]
            lb_i = lb.astype(jnp.int32)
            if should_fuse(V):
                # streamed Pallas path: per-element smoothed CE without
                # the (N, V) fp32 log-prob tensor (ops/xent_kernel.py).
                # ignore_index rows contribute 0 via the valid mask and
                # get zero cotangent, so their in-range-wrapped label
                # lookup never leaks into loss or grads
                loss = fused_smoothed_xent(lg, lb_i, self._eps)
            else:
                logp = jax.nn.log_softmax(lg, axis=-1)
                nll = -jnp.take_along_axis(logp, lb_i[..., None],
                                           axis=-1)[..., 0]
                smooth = -jnp.mean(logp, axis=-1)
                loss = (1 - self._eps) * nll + self._eps * smooth
            valid = (lb_i != self._ignore).astype(jnp.float32)
            return jnp.sum(loss * valid) / jnp.maximum(jnp.sum(valid), 1.0)

        return apply_op(f, wrap(logits), wrap(labels))


def transformer_base(src_vocab=32000, tgt_vocab=32000, **kw):
    return Transformer(src_vocab, tgt_vocab, units=512, hidden_size=2048,
                       num_layers=6, num_heads=8, **kw)


def transformer_big(src_vocab=32000, tgt_vocab=32000, **kw):
    return Transformer(src_vocab, tgt_vocab, units=1024, hidden_size=4096,
                       num_layers=6, num_heads=16, **kw)
