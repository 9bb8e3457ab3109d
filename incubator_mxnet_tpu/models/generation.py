"""Autoregressive KV-cache generation for `models.TransformerLM`.

The reference ecosystem shipped decode tooling (GluonNLP
`BeamSearchSampler` / `SequenceSampler` era [UNVERIFIED — mount
empty]); this is its TPU-native counterpart: the ENTIRE generation —
prompt prefill + N decode steps — compiles into ONE XLA program.

TPU-first structure:
- Static shapes everywhere: the KV cache is preallocated at
  (B, H, P+N, D) per layer and decode attends over the full cache
  width with an iota mask `pos <= t` — no dynamic shapes to defeat
  XLA's tiling.
- The token loop is `lax.scan` (compiled once, no per-token
  dispatch).
- Sampling is counter-based (`fold_in(key, t)`), so the program stays
  key-parametric and a seeded run reproduces exactly.
- Weights enter the program as ARGUMENTS (a pytree gathered from the
  live Block parameters at call time — the same arrays training
  updates), so repeated calls with updated weights reuse the compiled
  program; it is cached per (shapes, sampling-config) signature.

Numerics mirror the model's XLA attention path (scores and softmax in
fp32, output cast back to the activation dtype), so greedy decode
agrees with the training forward's argmax — pinned by parity tests
prefix-by-prefix (`tests/test_generation.py`).
"""
from __future__ import annotations

import math
import os
import time
from collections import OrderedDict
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["lm_generate", "lm_beam_search", "lm_score", "lm_stream",
           "nmt_translate", "bucket_length", "DecoderSpec", "SsmSpec",
           "AttnSpec", "IndexSpec", "MoeSpec", "StackedLayers",
           "decoder_spec"]


class SsmSpec(NamedTuple):
    """Sizes of a Mamba-1 mixer (`ops.selective_scan`)."""
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int


class IndexSpec(NamedTuple):
    """The index of a learned sparse attention layer: ``heads`` index
    query heads of ``dim`` against one index key a position score every
    earlier position, ``sum_j w_j relu(qi_j . ki_s)``, and a query attends
    the ``topk`` positions that score highest (all, while there are no
    more; ties to the lower position).  The index keys are a third pool a
    layer beside K and V (`ops.sparse_attention`)."""
    heads: int
    dim: int
    topk: int


class AttnSpec(NamedTuple):
    """One attention layer where the layers differ: its KV heads, its
    window (0: every earlier position; W: positions ``t-W+1 .. t``, whose
    pages go back behind it), whether a learned logit a query head joins
    the softmax's denominator (``sink``), its rotary base, the index that
    picks the positions a query attends (None: all the mask admits) and
    whether an RMSNorm with a gain over the head's lanes goes over every
    query and key head before the rotary (``qk_norm``)."""
    kv_heads: int
    window: int
    sink: bool
    rope_base: float
    index: Optional[IndexSpec] = None
    qk_norm: bool = False


class MoeSpec(NamedTuple):
    """Sizes of a routed feed-forward ("routed" in ``acts``): the router
    scores ``experts`` of them and takes the ``top_k`` largest: by a
    sigmoid, of score + selection bias (``scoring`` "sigmoid"), or by a
    softmax over all of them, with no bias ("softmax"); weights are the
    selected scores over their sum; of the experts this program holds
    ``held`` from ``first`` on, each a gated SiLU MLP of ``width``.  What
    the others would add is left out (another chip's share)."""
    experts: int
    first: int
    held: int
    top_k: int
    width: int
    scoring: str = "sigmoid"


class DecoderSpec(NamedTuple):
    """What the served programs know of a decoder: a description the net
    hands over (`net.decoder_spec()`), static and hashable, beside the
    weight pytree of `net.decoder_params()`.  `serving/` reads this and
    names no model.

    kinds        per layer, its mixer: "attn" (K/V pages, paged attention)
                 or "ssm" (conv window, selective scan, recurrent state)
    acts         per layer, its feed-forward: "gelu" | "relu" (two
                 matrices), "silu_gated" (down(silu(gate x) * up x)) or
                 "routed" (``moe``: a router and the experts held here)
    norm, eps    "layer" (gain and shift) or "rms" (gain)
    heads, kv_heads, head_dim   query heads, KV heads (a divisor), width
    positions    a sinusoidal table is added to the embedding
    embed_scale  what the embedding is multiplied by (1.0: nothing)
    ssm          `SsmSpec` of the "ssm" layers, None without any
    vocab, units, max_len       sizes the engine checks requests against
    attn         an `AttnSpec` an attention layer, in depth order, where
                 they differ (``()``: each is ``kv_heads`` KV heads, no
                 window, no sink, no rotary).  With it a layer's weights
                 are ``q``, ``k``, ``v`` (three matrices, keys ``head_dim``
                 and values ``v_dim`` wide) and ``sink`` ((heads,), where
                 the layer has one) in place of ``qkv``; a layer with
                 ``qk_norm`` also ``q_norm`` and ``k_norm`` ((head_dim,)
                 gains), one with an ``index`` ``index_q`` ((heads * dim,
                 units)), ``index_k`` ((dim, units)) and ``index_w``
                 ((heads, units)), matrices over the layer's normed input
    v_dim        width of a value head (0: ``head_dim``)
    rope_dim     leading lanes of every query and key head that rotate
                 with the position (halves-rotated form), 0: none
    value_scale  what an attention layer's output is multiplied by
    moe          `MoeSpec` of the "routed" feed-forwards, None without any;
                 such a layer holds ``router`` ((experts, units) matrix,
                 (experts,) selection bias, None where the scoring has
                 none) and ``experts`` (gate, up
                 (held, width, units), down (held, units, width))

    The weight pytree: ``embed``, ``pe`` (None without positions), ``ln``,
    ``head`` (the embedding itself when tied) and ``layers``, a dict a
    layer: ``ln1``, ``ln2``, ``ffn1``, ``ffn2`` (and ``ffn_gate``) always;
    ``qkv`` (fused, query rows first) and ``proj`` for "attn"; for "ssm"
    ``in_proj``, ``conv`` ((d_conv, d_inner) taps, bias), ``x_proj``,
    ``dt_norm``, ``b_norm``, ``c_norm``, ``dt_proj``, ``A`` ((d_state,
    d_inner) float32, negative), ``D`` and ``out_proj``.  ``layers`` is a
    list of such dicts or a `StackedLayers`, which reads as one."""
    kinds: tuple
    acts: tuple
    norm: str
    eps: float
    heads: int
    kv_heads: int
    head_dim: int
    positions: bool
    embed_scale: float
    ssm: Optional[SsmSpec]
    vocab: int
    units: int
    max_len: int
    attn: tuple = ()
    v_dim: int = 0
    rope_dim: int = 0
    value_scale: float = 1.0
    moe: Optional[MoeSpec] = None

    @property
    def recurrent(self) -> bool:
        return "ssm" in self.kinds

    @property
    def window(self) -> int:
        """The window of the attention layers that have one (they share
        it), 0 where every layer attends every earlier position."""
        return max((a.window for a in self.attn), default=0)

    @property
    def index(self) -> Optional[IndexSpec]:
        """The index of the attention layers that have one (they share
        it), None where no layer selects what it attends."""
        return next((a.index for a in self.attn if a.index), None)

    @property
    def carried(self) -> bool:
        """Whether a sequence carries state a block table does not name
        (a recurrence, a window's ring), holds pages no prefix hit or
        scale pool knows of (an index's keys) or the programs carry counts
        (routed experts, an index): no prefix hit, no speculation, no int8
        K/V."""
        return self.recurrent or self.window > 0 or self.moe is not None \
            or self.index is not None


@jax.tree_util.register_pytree_node_class
class StackedLayers:
    """The per-layer weight dicts of `DecoderSpec`, held as one array a
    kind of leaf: ``groups["all"][name]`` has a row a layer,
    ``groups["ssm"]`` / ``groups["attn"]`` a row a layer of that kind, in
    depth order.  Iterating or indexing gives a layer's dict, each leaf
    the layer's row of its stack.  A program handed this takes a few dozen
    buffers instead of some 17 a layer (the runtime's cost of a call grows
    with their number), and a row read by a static index inside a jitted
    program fuses into its consumer: nothing is copied."""

    def __init__(self, groups: dict, kinds: tuple):
        self.groups, self.kinds = groups, tuple(kinds)

    def tree_flatten(self):
        return (self.groups,), self.kinds

    @classmethod
    def tree_unflatten(cls, kinds, children):
        return cls(children[0], kinds)

    def __len__(self):
        return len(self.kinds)

    def __getitem__(self, i):
        kind = self.kinds[i]
        j = self.kinds[:i].count(kind)
        out = jax.tree_util.tree_map(lambda a: a[i], self.groups["all"])
        out.update(jax.tree_util.tree_map(lambda a: a[j],
                                          self.groups[kind]))
        return out

    def __iter__(self):
        return (self[i] for i in range(len(self)))


def decoder_spec(net) -> DecoderSpec:
    """The net's own description of its decoder."""
    try:
        return net.decoder_spec()
    except AttributeError:
        raise TypeError(
            f"{type(net).__name__} does not describe a decoder the serving "
            "programs can run (decoder_spec / decoder_params / "
            "decoder_fingerprint)") from None

# LRU caps for the per-net compiled-program / pe-table caches (ADVICE
# r5 #3: exact-(B, P, N, sampling) keys grow without bound under
# variable-length traffic).  Override per net via
# `net._gen_program_cache_cap` / `net._pe_cache_cap`.
_PROGRAM_CACHE_CAP = int(os.environ.get("MXTPU_GEN_PROGRAM_CACHE", "32"))
_PE_CACHE_CAP = int(os.environ.get("MXTPU_GEN_PE_CACHE", "8"))


def _dense(x, w, b, out_dtype=None):
    """nn.Dense math on raw arrays: x @ W.T + b (weight is (out, in)).

    `w` is either a raw float array or a quantized-weight dict emitted
    by `_gather_params` for a `quantize_for_decode`-marked net:
    ``{"w8": int8 (out, in), "s": fp32 (out,)}`` (+ a leafless "dyn"
    marker selecting dynamic activation quantization).  The quantized
    path streams the int8 weight straight into the matmul and applies
    the per-channel scale in the EPILOGUE — to the (..., out) result,
    never to the weight — so no program-level float copy of the weight
    exists (the CI smoke gate pins this on the compiled HLO).
    """
    if isinstance(w, dict):
        cdim = x.ndim - 1
        # tpulint: disable-next=TPU004 -- dict KEY membership is static pytree structure (the strategy marker), not a traced value
        if "dyn" in w:
            # dynamic per-row activation int8: native INT8xINT8->INT32
            # dot (the PTQ machinery's MXU path); scale product in the
            # epilogue
            xf = x.astype(jnp.float32)
            sx = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                             1e-8) / 127.0
            xq = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(xq, w["w8"], (((cdim,), (1,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            y = acc.astype(jnp.float32) * (sx * w["s"])
        else:
            # weight-only: mixed-precision dot consumes the int8 weight
            # directly (bf16 activations upconvert in-register on TPU)
            acc = jax.lax.dot_general(x, w["w8"], (((cdim,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            y = acc * w["s"]
        if b is not None:
            y = y + b.astype(jnp.float32)
        return y.astype(x.dtype if out_dtype is None else out_dtype)
    y = x @ w.T.astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y if out_dtype is None else y.astype(out_dtype)


def _ln(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)
            * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _rms(x, g, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
            * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, width, base):
    """Rotary positions on the first ``width`` lanes of every head of
    ``x`` (..., H, D), halves-rotated form: lane j < width/2 pairs with
    lane j + width/2 and turns by ``pos * base**(-2j/width)``; the other
    lanes pass.  ``pos`` has ``x``'s leading dims.  Float32 angles, the
    result in ``x``'s dtype."""
    half = width // 2
    theta = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                    * (-2.0 / width * math.log(base)))
    ang = pos.astype(jnp.float32)[..., None, None] * theta  # (..., 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:width].astype(jnp.float32)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), x[..., width:]], axis=-1)


def _norm(spec, x, p):
    """The decoder's norm on one of its parameter tuples."""
    return _rms(x, *p, eps=spec.eps) if spec.norm == "rms" \
        else _ln(x, *p, eps=spec.eps)


def _qkv_heads(qkv, H, Hkv=None):
    """(..., (H + 2 Hkv) D) -> (..., H, D) queries and two (..., Hkv, D)
    tensors, query rows first; ``Hkv=None``: as many KV heads as query
    heads, the MHA split order."""
    # tpulint: disable-next=TPU004 -- head counts are static Python ints
    if Hkv is None or Hkv == H:
        q, k, v = jnp.split(qkv, 3, axis=-1)
        Hkv = H
    else:
        D = qkv.shape[-1] // (H + 2 * Hkv)
        q, k, v = jnp.split(qkv, [H * D, (H + Hkv) * D], axis=-1)
    D = q.shape[-1] // H
    lead = q.shape[:-1]
    return (q.reshape(lead + (H, D)), k.reshape(lead + (Hkv, D)),
            v.reshape(lead + (Hkv, D)))


def _wb(layer):
    """(weight, bias-or-None) raw arrays of an nn.Dense layer."""
    return (layer.weight.data()._data,
            None if layer.bias is None else layer.bias.data()._data)


def _lru_touch(cache, key):
    """LRU read: returns cache[key] (refreshing recency) or None."""
    val = cache.get(key)
    if val is not None:
        cache.move_to_end(key)
    return val


def _lru_put(net, cache, key, val, cap_attr, default_cap, gauge=None):
    """LRU insert with eviction beyond the cap (net attribute override
    `cap_attr`, else `default_cap`); mirrors the size into `gauge` and
    counts evictions into ``gen_program_cache_evictions_total``."""
    cache[key] = val
    cap = max(1, int(getattr(net, cap_attr, default_cap)))
    evicted = 0
    while len(cache) > cap:
        cache.popitem(last=False)
        evicted += 1
    if gauge is not None:
        from .. import telemetry

        if telemetry.enabled():
            telemetry.gauge(gauge).set(len(cache))
            if evicted:
                telemetry.counter("gen_program_cache_evictions_total") \
                    .inc(evicted)
    return val


def _program_cache(net):
    cache = getattr(net, "_gen_programs", None)
    if cache is None:
        cache = net._gen_programs = OrderedDict()
    return cache


def _cache_program(net, sig, fn):
    return _lru_put(net, _program_cache(net), sig, fn,
                    "_gen_program_cache_cap", _PROGRAM_CACHE_CAP,
                    gauge="gen_program_cache_size")


def _pe_table(net, width):
    """Eagerly-built positional-encoding table of `width` rows, cached
    per width on the net (the compiled decode programs consume pe as an
    argument, so only the rows they read are ever built)."""
    cache = getattr(net, "_pe_cache", None)
    if cache is None:
        cache = net._pe_cache = OrderedDict()
    pe = _lru_touch(cache, width)
    if pe is None:
        from .transformer import positional_encoding

        pe = _lru_put(net, cache, width,
                      positional_encoding(width, net._units),
                      "_pe_cache_cap", _PE_CACHE_CAP)
    return pe


def bucket_length(n: int, *, floor: int = 16) -> int:
    """Prompt-length bucketing rule: the smallest power of two >=
    max(n, floor).  ``lm_generate(..., pad_to_bucket=True)`` compiles
    one program per BUCKET (the true length rides in as a traced
    scalar), so variable-length traffic keeps the program cache at
    O(#buckets) instead of O(#distinct lengths)."""
    if n < 0:
        raise ValueError(f"length must be >= 0, got {n}")
    b = max(1, int(floor))
    while b < n:
        b *= 2
    return b


def _quant_config(net, quantized):
    """Resolve the effective DecodeQuantConfig for a generation call:
    quantized=None → whatever `quantize_for_decode` attached (float
    path if nothing); True → require it; False → force the float
    path."""
    qc = getattr(net, "_decode_quant", None)
    if quantized is False:
        return None
    if quantized and qc is None:
        raise ValueError(
            "quantized=True but the net has no decode-quantization "
            "state — run contrib.quantization.quantize_for_decode(net) "
            "first")
    return qc


def _gather_params(net, pe_width, qc=None):
    """The weight pytree the compiled program consumes — the live raw
    arrays of the Block's parameters, in the structure `DecoderSpec`
    sets out, gathered by the net itself (`net.decoder_params`).  With a
    DecodeQuantConfig `qc`, target matmul weights come out as int8+
    scale dicts instead (see `_dense`); stale quantized copies are
    refreshed here, keyed on weight-buffer identity."""
    def d(layer):
        if qc is not None:
            packed = qc.packed(layer)
            if packed is not None:
                return (packed, None if layer.bias is None
                        else layer.bias.data()._data)
        return _wb(layer)

    return net.decoder_params(pe_width, d)


def _params_fingerprint(net):
    """Identity key over the raw buffers `_gather_params` gathers.
    Training / `set_data` REPLACE parameter buffers, so a changed id
    means any gathered pytree (and lazy int8 copies) built from the old
    buffers is stale.  Sound as a cache key as long as the cached
    pytree is alive: it keeps the fingerprinted buffers referenced, so
    a fresh buffer can never recycle one of their ids.  Cost: a few
    id() calls per layer, no device work."""
    return net.decoder_fingerprint()


def _ffn_fwd(x, lp, act):
    h = _dense(x, *lp["ffn1"])
    if act == "silu_gated":
        g = _dense(x, *lp["ffn_gate"]).astype(jnp.float32)
        h = (jax.nn.silu(g) * h.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32),
                        approximate=True).astype(x.dtype) \
            if act == "gelu" else jax.nn.relu(h)
    return _dense(h, *lp["ffn2"])


def _logits_of(params, h_last, spec=None):
    """Final norm and head, float32 logits; without a `spec` the
    LayerNorm of the callers that know their net."""
    x = _ln(h_last, *params["ln"]) if spec is None \
        else _norm(spec, h_last, params["ln"])
    return _dense(x, *params["head"], out_dtype=jnp.float32)


def _weight_nbytes(params):
    """Bytes of weights a decode step STREAMS through its matmuls —
    layer matmul weights/biases + final ln + head (the embedding is a
    per-token row gather, not a streamed matmul, so it is excluded).
    Metadata-only (shape/dtype): never touches device data."""
    from ..telemetry import nbytes_of

    return sum(nbytes_of(a) for a in jax.tree_util.tree_leaves(
        (params["ln"], params["head"], params["layers"])))


def _record_decode_weight_bytes(params, qc):
    from .. import telemetry

    if telemetry.enabled():
        telemetry.gauge("decode_weight_bytes",
                        labels={"path": "int8" if qc is not None
                                else "float"}) \
            .set(_weight_nbytes(params))


def _decode_path(qc):
    """Roofline/SLO label of a generation call: which weight path ran."""
    return "int8" if qc is not None else "float"


def _timed_decode(program, path, n_tokens, fn, *args, slo=True):
    """Run compiled decode program `fn(*args)`; with telemetry enabled,
    attribute it for the roofline (cost/memory capture once per
    `program` name — AOT, the jit call cache is untouched) and record
    the serving SLO gauges:

    * ``decode_ttft_seconds{path=}`` — host wall time of the call.  The
      entire generation is ONE compiled program, so the first and last
      token become available together: TTFT equals whole-call latency
      by construction.
    * ``decode_tokens_per_second{path=}`` — emitted tokens / wall time.

    NO-HOST-SYNC: only host clocks are read — on an async backend the
    wall time is dispatch-side and becomes end-to-end once the caller
    consumes the tokens (serving always does, immediately); the gauges
    are exact there and never force a device sync here.  `slo=False`
    (lm_score) keeps the roofline attribution but skips the serving
    gauges — scores are not tokens.
    """
    from .. import telemetry

    if not telemetry.enabled():
        return fn(*args)
    telemetry.perf.capture(program, fn, *args)
    t0 = time.perf_counter()
    out = fn(*args)
    dt = time.perf_counter() - t0
    if slo and dt > 0:
        telemetry.gauge("decode_ttft_seconds", labels={"path": path}).set(dt)
        telemetry.gauge("decode_tokens_per_second", labels={"path": path}) \
            .set(n_tokens / dt)
    telemetry.perf.note_timing(program, dt)
    return out


def _prefill(params, prompt, acts, H, pad_to, valid_len=None,
             return_h=False):
    """Run the prompt through the model with the TRAINING path's causal
    attention; returns (h_last (B, C) activations at the final prompt
    position, per-layer K/V caches (B, H, pad_to, D)).

    `valid_len` (traced scalar) supports bucket-padded prompts: the
    prompt is RIGHT-padded, so under the causal mask every position
    < valid_len computes exactly its unpadded value (pad positions only
    pollute their own rows, which decode overwrites slot-by-slot as it
    emits tokens); h_last is read at valid_len-1.  `return_h=True`
    returns the full (B, P, C) hidden states instead of h_last
    (`lm_score`'s teacher-forced path — the unused caches DCE away).
    """
    from ..ops.flash_attention import flash_attention

    dt = params["embed"].dtype
    B, P = prompt.shape
    C = params["embed"].shape[1]
    h = params["embed"][prompt].astype(dt) * math.sqrt(C) \
        + params["pe"][:P].astype(dt)
    kcs, vcs = [], []
    for lp, act in zip(params["layers"], acts):
        x = _ln(h, *lp["ln1"])
        q, k, v = _qkv_heads(_dense(x, *lp["qkv"]), H)  # (B, P, H, D)
        kt = k.transpose(0, 2, 1, 3)  # (B, H, P, D) — cache layout
        vt = v.transpose(0, 2, 1, 3)
        # THE training path's causal attention (flash/XLA dispatch, fp32
        # softmax) — one kernel, one set of numerics for the
        # greedy-parity contract, no (B, H, P, P) materialization
        a = flash_attention(q.transpose(0, 2, 1, 3), kt, vt,
                            causal=True).transpose(0, 2, 1, 3)
        h = h + _dense(a.astype(dt).reshape(B, P, C), *lp["proj"])
        h = h + _ffn_fwd(_ln(h, *lp["ln2"]), lp, act)
        pad = ((0, 0), (0, 0), (0, pad_to - P), (0, 0))
        kcs.append(jnp.pad(kt, pad))
        vcs.append(jnp.pad(vt, pad))
    if return_h:
        return h, kcs, vcs
    if valid_len is None:
        return h[:, -1], kcs, vcs
    return jax.lax.dynamic_index_in_dim(
        h, valid_len - 1, axis=1, keepdims=False), kcs, vcs


def _cached_self_attn(lp, h, kcache, vcache, t, H):
    """The cached one-token self-attention sub-step shared by the LM
    and NMT decoders: pre-LN, qkv, cache write at position t, fp32
    iota-masked scores/softmax, PV product, output projection —
    returns (h + attn_out, new_kcache, new_vcache).  ONE definition so
    the numerics-sensitive step can never fork between families."""
    Bp, C = h.shape
    D = C // H
    dt = h.dtype
    x = _ln(h, *lp["ln1"])
    q, k, v = _qkv_heads(_dense(x, *lp["qkv"]), H)  # (B', H, D)
    kc = jax.lax.dynamic_update_slice_in_dim(
        kcache, k[:, :, None], t, axis=2)
    vc = jax.lax.dynamic_update_slice_in_dim(
        vcache, v[:, :, None], t, axis=2)
    s = jnp.einsum("bhd,bhkd->bhk", q, kc,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos <= t, s, jnp.finfo(jnp.float32).min)
    # p stays fp32 through the PV product (the training path's softmax
    # precision); the einsums upconvert the bf16 caches lazily
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhk,bhkd->bhd", p, vc,
                   preferred_element_type=jnp.float32).astype(dt)
    return h + _dense(a.reshape(Bp, C), *lp["proj"]), kc, vc


def _decode_token(params, acts, kcaches, vcaches, tok, t, H):
    """One transformer step for token `tok` at position `t` against the
    caches (per-layer (B', H, W, D)); returns (new_k, new_v, logits).
    fp32 scores and softmax through the PV product (the training path's
    precision); the einsums upconvert the bf16 caches lazily — no
    materialized fp32 cache copies."""
    dt = params["embed"].dtype
    C = params["embed"].shape[1]
    h = (params["embed"][tok].astype(dt) * math.sqrt(C)
         + jax.lax.dynamic_index_in_dim(params["pe"], t,
                                        keepdims=False).astype(dt))
    new_k, new_v = [], []
    for li, (lp, act) in enumerate(zip(params["layers"], acts)):
        h, kc, vc = _cached_self_attn(lp, h, kcaches[li], vcaches[li],
                                      t, H)
        h = h + _ffn_fwd(_ln(h, *lp["ln2"]), lp, act)
        new_k.append(kc)
        new_v.append(vc)
    return tuple(new_k), tuple(new_v), _logits_of(params, h)


def _make_pick(temperature, top_k):
    def pick(logits, t, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits / jnp.float32(temperature)
        if top_k > 0:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, jnp.finfo(jnp.float32).min, lg)
        return jax.random.categorical(
            jax.random.fold_in(key, t), lg, axis=-1).astype(jnp.int32)

    return pick


def _greedy_loop(first_logits, state0, step_fn, pick, key, t0, N, B,
                 eos_id):
    """Generic greedy/sampling token loop: emit N tokens at positions
    t0..t0+N-1, the first from `first_logits`, the rest by scanning
    `step_fn(state, tok, t) -> (state, logits)`.  The decode state is
    an arbitrary pytree riding the scan carry (per-layer cache tuples:
    each dynamic_update_slice aliases its buffer in place — a stacked
    cache copied itself every step, 17.9 -> 11.8 ms/token-step at
    B=64).  Returns (B, N) int32."""
    first = pick(first_logits, t0 - 1, key)

    def step(carry, t):
        state, tok, done = carry
        state, logits = step_fn(state, tok, t)
        nxt = pick(logits, t, key)
        if eos_id >= 0:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | (nxt == eos_id)
        return (state, nxt, done), tok

    done0 = (first == eos_id) if eos_id >= 0 else jnp.zeros((B,), bool)
    if N == 1:
        return first[:, None]
    # t0 may be a TRACED scalar (bucket-padded prompts: the true length
    # enters the program as an argument) — build positions around it
    (_, last, _), toks = jax.lax.scan(
        step, (state0, first, done0),
        jnp.arange(N - 1, dtype=jnp.int32) + t0)
    return jnp.concatenate([toks.T, last[:, None]], axis=1)


def _build_program(B, P, N, H, temperature, top_k, eos_id, acts,
                   bucketed=False):
    """The (jittable) prefill+scan generation program for one static
    signature.  `params` is `_gather_params`' pytree; `key` a PRNG key;
    `acts` the per-layer FFN activation names (static).

    `bucketed=True` builds the pad-to-bucket variant: P is the BUCKET
    width, the prompt arrives right-padded, and the true length rides
    in as a traced scalar (`valid_len`) — prefill reads h_last at
    valid_len-1 and decode writes/attends cache slots from valid_len
    on, so the emitted tokens are bit-identical to the exact-shape
    program's.  Returns only the generated (B, N) block (the caller
    re-attaches its unpadded prompt)."""
    pick = _make_pick(temperature, top_k)

    def core(params, prompt, valid_len, key):
        h_last, kcs, vcs = _prefill(params, prompt, acts, H, P + N,
                                    valid_len=valid_len)

        def step_fn(state, tok, t):
            new_k, new_v, logits = _decode_token(params, acts, state[0],
                                                 state[1], tok, t, H)
            return (new_k, new_v), logits

        return _greedy_loop(_logits_of(params, h_last),
                            (tuple(kcs), tuple(vcs)), step_fn, pick, key,
                            P if valid_len is None else valid_len,
                            N, B, eos_id)

    if bucketed:
        def run(params, prompt, valid_len, key):
            return core(params, prompt, valid_len, key)
    else:
        def run(params, prompt, key):
            return jnp.concatenate(
                [prompt, core(params, prompt, None, key)], axis=1)

    return run


def lm_generate(net, prompt, max_new_tokens: int, *, temperature: float = 0.0,
                top_k: int = 0, eos_id: int = -1, seed: int = 0,
                quantized=None, pad_to_bucket: bool = False):
    """Generate `max_new_tokens` continuations of `prompt` with
    `models.TransformerLM` `net` (initialized; generation runs in eval
    mode — dropout off).

    prompt: int32 (B, P) array/NDArray.  temperature=0 → greedy argmax;
    temperature>0 samples (optionally top_k-truncated) with a
    counter-based key from `seed`.  eos_id >= 0 freezes a sequence at
    eos (further positions emit eos_id).  Returns an int32 (B, P+N)
    jnp array — the prompt followed by the generated tokens.

    `quantized`: None (default) uses the int8 weight-quantized path iff
    `contrib.quantization.quantize_for_decode(net)` has been applied;
    True requires it; False forces the float path.  Programs for both
    paths coexist in the cache (keyed on the quant config).

    `pad_to_bucket=True` right-pads the prompt to its power-of-two
    length bucket and passes the true length as a program ARGUMENT —
    token-identical output, but variable-length traffic compiles one
    program per bucket instead of one per exact length (the program
    cache is additionally LRU-capped; see `bucket_length`).

    The compiled program is cached on the net per
    (B, P, N, temperature, top_k, eos_id, quant, bucketed) signature;
    weights are arguments, so training between calls does not
    recompile.

    ref: GluonNLP SequenceSampler/BeamSearchSampler role `[UNVERIFIED]`
    re-designed as a single compiled prefill+scan program (SURVEY.md
    §2.6 frontier; see module docstring).
    """
    from ..ndarray.ndarray import NDArray

    if isinstance(prompt, NDArray):
        prompt = prompt._data
    prompt = jnp.asarray(prompt, jnp.int32)
    B, P = prompt.shape
    N = int(max_new_tokens)
    if N < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {N}")
    if P + N > net._max_len:
        raise ValueError(
            f"prompt+new = {P + N} exceeds max_len {net._max_len}")
    H = net._layers[0].attn._num_heads
    qc = _quant_config(net, quantized)
    qkey = qc.cache_key() if qc is not None else None

    # pad-to-bucket: the program is shaped for the bucket (never past
    # max_len - N, so the guard above stays exact)
    Pp = min(bucket_length(P), net._max_len - N) if pad_to_bucket else P

    sig = (B, Pp, N, float(temperature), int(top_k), int(eos_id), qkey,
           bool(pad_to_bucket))
    cache = _program_cache(net)
    fn = _lru_touch(cache, sig)
    if fn is None:
        acts = tuple(lyr.ffn._act for lyr in net._layers)
        run = _build_program(B, Pp, N, H, float(temperature), int(top_k),
                             int(eos_id), acts, bucketed=pad_to_bucket)
        fn = _cache_program(net, sig, jax.jit(run))
    params = _gather_params(net, Pp + N, qc)
    _record_decode_weight_bytes(params, qc)
    path = _decode_path(qc)
    key = jax.random.PRNGKey(seed)
    if not pad_to_bucket:
        return _timed_decode(f"decode_{path}", path, B * N,
                             fn, params, prompt, key)
    padded = prompt if Pp == P else jnp.concatenate(
        [prompt, jnp.zeros((B, Pp - P), jnp.int32)], axis=1)
    gen = _timed_decode(f"decode_{path}", path, B * N,
                        fn, params, padded, jnp.int32(P), key)
    return jnp.concatenate([prompt, gen], axis=1)


def lm_stream(net, prompt, max_new_tokens: int, *, engine=None,
              deadline=None, seed: int = 0, **engine_kw):
    """Stream generated tokens one at a time through the net's shared
    continuous-batching engine (`serving.default_engine`): yields int
    token ids as the engine emits them, so concurrent `lm_stream`
    callers are CO-BATCHED into one decode program instead of running
    serial `lm_generate` calls.

    Abandoning the returned generator mid-stream (break / close / GC)
    CANCELS the request and releases its paged KV blocks back to the
    pool — streaming callers cannot leak cache memory (the regression
    test pins the pool's free-block count).

    ``deadline`` (seconds) bounds the request end-to-end — past it the
    engine evicts the sequence mid-batch and the generator raises
    `serving.RequestTimedOut`.  ``engine_kw`` (temperature, top_k,
    eos_id, max_batch, ...) configures the shared engine on first use;
    pass ``engine=`` to target an explicit `ServingEngine`.
    """
    from ..serving import default_engine

    eng = engine if engine is not None else default_engine(net, **engine_kw)
    req = eng.submit(prompt, max_new_tokens, deadline=deadline, seed=seed)
    return req.stream()


# --------------------------------------------------------------------- #
# beam search
# --------------------------------------------------------------------- #
_NEG = jnp.float32(-1e9)


def _beam_loop(first_logits, state0, step_fn, t0, N, B, K, eos_id, alpha):
    """Generic K-beam token loop: standard K·V candidate expansion per
    step, the decode-state pytree reordered by beam parent each step,
    sequences reconstructed by a REVERSE scan over the (token, parent)
    trace.  `state0` is the batch-B decode state (tiled K-fold here;
    `step_fn` runs at batch B*K); emits N tokens at positions
    t0..t0+N-1.  Returns (gen (B, K, N) best-first, normalized scores
    (B, K))."""
    logp0 = jax.nn.log_softmax(first_logits)         # (B, V)
    V = logp0.shape[-1]
    scores0, tok0 = jax.lax.top_k(logp0, K)          # (B, K)
    tok0 = tok0.astype(jnp.int32)
    # beams live as (B*K, ...): tile the state K-fold
    state0 = jax.tree_util.tree_map(
        lambda c: jnp.repeat(c, K, axis=0), state0)
    done0 = (tok0 == eos_id) if eos_id >= 0 else jnp.zeros((B, K), bool)
    lens0 = jnp.ones((B, K), jnp.int32)  # generated tokens so far

    def step(carry, t):
        state, scores, tok, done, lens = carry
        state, logits = step_fn(state, tok.reshape(B * K), t)
        logp = jax.nn.log_softmax(logits).reshape(B, K, V)
        if eos_id >= 0:
            # a finished beam may only extend with eos, at no cost —
            # its score and length freeze
            frozen = jnp.full((V,), _NEG).at[eos_id].set(0.0)
            logp = jnp.where(done[..., None], frozen, logp)
        cand = scores[..., None] + logp              # (B, K, V)
        new_scores, idx = jax.lax.top_k(cand.reshape(B, K * V), K)
        parent = idx // V                            # (B, K)
        nxt = (idx % V).astype(jnp.int32)
        gidx = (jnp.arange(B)[:, None] * K + parent).reshape(B * K)
        state = jax.tree_util.tree_map(lambda c: c[gidx], state)
        pdone = jnp.take_along_axis(done, parent, axis=1)
        plens = jnp.take_along_axis(lens, parent, axis=1)
        if eos_id >= 0:
            ndone = pdone | (nxt == eos_id)
            nlens = jnp.where(pdone, plens, plens + 1)
        else:
            ndone, nlens = pdone, plens + 1
        return (state, new_scores, nxt, ndone, nlens), (nxt, parent)

    if N > 1:
        carry0 = (state0, scores0, tok0, done0, lens0)
        (_, scores, _, _, lens), (toks, parents) = jax.lax.scan(
            step, carry0, jnp.arange(t0, t0 + N - 1, dtype=jnp.int32))

        # ---- backtrack: walk the parent pointers from the final beams
        # to the first expansion (reverse scan; ys stay
        # position-aligned) ----
        def back(ptr, xs):
            tk, par = xs
            tok_t = jnp.take_along_axis(tk, ptr, axis=1)
            return jnp.take_along_axis(par, ptr, axis=1), tok_t

        init = jnp.tile(jnp.arange(K)[None, :], (B, 1))
        ptr0, rest = jax.lax.scan(back, init, (toks, parents),
                                  reverse=True)
        first_tok = jnp.take_along_axis(tok0, ptr0, axis=1)
        gen = jnp.concatenate([first_tok[None], rest], axis=0)
        gen = gen.transpose(1, 2, 0)                 # (B, K, N)
    else:
        scores, lens, gen = scores0, lens0, tok0[..., None]

    # GNMT length penalty: rank by score / ((5+len)/6)^alpha
    if alpha > 0.0:
        norm = scores / (((5.0 + lens.astype(jnp.float32)) / 6.0) ** alpha)
    else:
        norm = scores
    order = jnp.argsort(-norm, axis=1)
    gen = jnp.take_along_axis(gen, order[..., None], axis=1)
    norm = jnp.take_along_axis(norm, order, axis=1)
    return gen, norm


def _build_beam_program(B, P, N, K, H, eos_id, alpha, acts):
    """Beam-search decode for one static signature — `_beam_loop` over
    the LM's cached decode step, everything one compiled program."""

    def run(params, prompt):
        h_last, kcs, vcs = _prefill(params, prompt, acts, H, P + N)

        def step_fn(state, tok, t):
            new_k, new_v, logits = _decode_token(params, acts, state[0],
                                                 state[1], tok, t, H)
            return (new_k, new_v), logits

        gen, norm = _beam_loop(_logits_of(params, h_last),
                               (tuple(kcs), tuple(vcs)), step_fn,
                               P, N, B, K, eos_id, alpha)
        seqs = jnp.concatenate(
            [jnp.broadcast_to(prompt[:, None], (B, K, P)), gen], axis=2)
        return seqs, norm

    return run


def lm_score(net, tokens, *, quantized=None):
    """Teacher-forced per-token log-probabilities of `tokens` under the
    DECODE stack's numerics (the quantized path iff
    `quantize_for_decode` was applied / ``quantized=True``): returns
    f32 (B, T-1) — logp of tokens[:, 1:] given the prefix.  The
    perplexity oracle the quantization tolerance tests pin against the
    float path (``exp(-mean(lm_score(...)))``)."""
    from ..ndarray.ndarray import NDArray

    if isinstance(tokens, NDArray):
        tokens = tokens._data
    tokens = jnp.asarray(tokens, jnp.int32)
    B, T = tokens.shape
    if T < 2:
        raise ValueError(f"need >= 2 tokens to score, got {T}")
    if T > net._max_len:
        raise ValueError(f"sequence {T} exceeds max_len {net._max_len}")
    H = net._layers[0].attn._num_heads
    qc = _quant_config(net, quantized)
    qkey = qc.cache_key() if qc is not None else None

    sig = ("score", B, T, qkey)
    cache = _program_cache(net)
    fn = _lru_touch(cache, sig)
    if fn is None:
        acts = tuple(lyr.ffn._act for lyr in net._layers)

        def run(params, toks):
            h, _, _ = _prefill(params, toks, acts, H, T, return_h=True)
            logits = _dense(_ln(h, *params["ln"]), *params["head"],
                            out_dtype=jnp.float32)
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            return jnp.take_along_axis(
                logp, toks[:, 1:, None], axis=2)[..., 0]

        fn = _cache_program(net, sig, jax.jit(run))
    path = _decode_path(qc)
    return _timed_decode(f"score_{path}", path, 0,
                         fn, _gather_params(net, T, qc), tokens, slo=False)


def lm_beam_search(net, prompt, max_new_tokens: int, *, beam_size: int = 4,
                   eos_id: int = -1, alpha: float = 0.0, quantized=None):
    """K-beam search decode for `models.TransformerLM` — the
    TPU-native counterpart of the reference era's BeamSearchSampler
    (GluonNLP `[UNVERIFIED — mount empty]`): prefill + the whole beam
    loop (expansion, cache reordering, backtracking) compile into ONE
    XLA program, cached per signature like `lm_generate`.

    prompt: int32 (B, P).  Returns (sequences, scores): int32
    (B, beam_size, P+N) sorted best-first, and f32 (B, beam_size)
    cumulative log-probabilities (GNMT length-penalty-normalized when
    ``alpha > 0``; eos_id >= 0 freezes finished beams' scores and
    lengths).  beam_size=1 reproduces greedy `lm_generate` exactly.
    `quantized` selects the int8 weight-quantized path as in
    `lm_generate`.
    """
    from ..ndarray.ndarray import NDArray

    if isinstance(prompt, NDArray):
        prompt = prompt._data
    prompt = jnp.asarray(prompt, jnp.int32)
    B, P = prompt.shape
    N = int(max_new_tokens)
    K = int(beam_size)
    if N < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {N}")
    if K < 1:
        raise ValueError(f"beam_size must be >= 1, got {K}")
    V = net.head._units
    if K > V:
        raise ValueError(f"beam_size {K} exceeds vocab {V}")
    if P + N > net._max_len:
        raise ValueError(
            f"prompt+new = {P + N} exceeds max_len {net._max_len}")
    H = net._layers[0].attn._num_heads
    qc = _quant_config(net, quantized)
    qkey = qc.cache_key() if qc is not None else None

    sig = ("beam", B, P, N, K, int(eos_id), float(alpha), qkey)
    cache = _program_cache(net)
    fn = _lru_touch(cache, sig)
    if fn is None:
        acts = tuple(lyr.ffn._act for lyr in net._layers)
        run = _build_beam_program(B, P, N, K, H, int(eos_id),
                                  float(alpha), acts)
        fn = _cache_program(net, sig, jax.jit(run))
    params = _gather_params(net, P + N, qc)
    _record_decode_weight_bytes(params, qc)
    path = _decode_path(qc)
    return _timed_decode(f"beam_decode_{path}", path, B * K * N,
                         fn, params, prompt)


# --------------------------------------------------------------------- #
# NMT (encoder-decoder Transformer) translation
# --------------------------------------------------------------------- #
def _gather_nmt_params(net, qc=None):
    """Decoder-side weight pytree for `models.Transformer` (the encoder
    runs through the PUBLIC block — training numerics — outside the
    decode program).  With a DecodeQuantConfig `qc`, target decoder
    matmul weights come out as int8+scale dicts (see `_dense`)."""
    def d(layer):
        if qc is not None:
            packed = qc.packed(layer)
            if packed is not None:
                return (packed, None if layer.bias is None
                        else layer.bias.data()._data)
        return (layer.weight.data()._data,
                None if layer.bias is None else layer.bias.data()._data)

    layers = []
    for lyr in net.decoder._layers:
        layers.append({
            "ln1": (lyr.ln1.gamma.data()._data, lyr.ln1.beta.data()._data),
            "qkv": d(lyr.self_attn.qkv),
            "proj": d(lyr.self_attn.proj),
            "ln2": (lyr.ln2.gamma.data()._data, lyr.ln2.beta.data()._data),
            "xq": d(lyr.cross_attn.q_proj),
            "xkv": d(lyr.cross_attn.kv_proj),
            "xproj": d(lyr.cross_attn.proj),
            "ln3": (lyr.ln3.gamma.data()._data, lyr.ln3.beta.data()._data),
            "ffn1": d(lyr.ffn.ffn_dense1),
            "ffn2": d(lyr.ffn.ffn_dense2),
        })
    return {
        "embed": net.tgt_embed.weight.data()._data,
        "ln": (net.decoder.ln.gamma.data()._data,
               net.decoder.ln.beta.data()._data),
        "head": d(net.out_proj),
        "layers": layers,
    }


def _nmt_decode_token(params, acts, pe, kcaches, vcaches, xks, xvs,
                      mem_mask, tok, t, H):
    """One decoder step at target position `t`: pre-LN self-attention
    against the cache, cross-attention over the precomputed encoder
    K/V (fp32 scores/softmax, the training path's numerics), FFN."""
    dt = params["embed"].dtype
    Bp = tok.shape[0]
    C = params["embed"].shape[1]
    D = C // H
    h = (params["embed"][tok].astype(dt) * math.sqrt(C)
         + jax.lax.dynamic_index_in_dim(pe, t, keepdims=False).astype(dt))
    new_k, new_v = [], []
    for li, (lp, act) in enumerate(zip(params["layers"], acts)):
        # self-attention with KV cache (the shared sub-step)
        h, kc, vc = _cached_self_attn(lp, h, kcaches[li], vcaches[li],
                                      t, H)
        # cross-attention over the fixed encoder memory
        x = _ln(h, *lp["ln2"])
        qx = _dense(x, *lp["xq"]).reshape(Bp, H, D)
        s = jnp.einsum("bhd,bhkd->bhk", qx.astype(jnp.float32),
                       xks[li].astype(jnp.float32)) / math.sqrt(D)
        if mem_mask is not None:
            s = jnp.where(mem_mask[:, None, :].astype(bool), s,
                          jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("bhk,bhkd->bhd", p,
                       xvs[li].astype(jnp.float32)).astype(dt)
        h = h + _dense(a.reshape(Bp, C), *lp["xproj"])
        h = h + _ffn_fwd(_ln(h, *lp["ln3"]), lp, act)
        new_k.append(kc)
        new_v.append(vc)
    logits = _dense(_ln(h, *params["ln"]), *params["head"],
                    out_dtype=jnp.float32)
    return tuple(new_k), tuple(new_v), logits


def _build_nmt_program(B, S, N, K, H, eos_id, bos_id, alpha, temperature,
                       top_k, acts, masked):
    """Translate program: BOS step → `_greedy_loop` (K=1) or
    `_beam_loop` over the decoder's cached step; the encoder memory and
    its per-layer cross K/V enter as traced arguments."""
    pick = _make_pick(temperature, top_k)

    def run(params, mem, mem_mask, pe, key):
        dt = params["embed"].dtype
        C = params["embed"].shape[1]
        D = C // H
        # per-layer cross-attention K/V from the encoder memory (once)
        xks, xvs = [], []
        for lp in params["layers"]:
            kv = _dense(mem.astype(dt), *lp["xkv"])
            kx, vx = jnp.split(kv, 2, axis=-1)
            xks.append(kx.reshape(B, S, H, D).transpose(0, 2, 1, 3))
            xvs.append(vx.reshape(B, S, H, D).transpose(0, 2, 1, 3))
        L = len(acts)
        kcs = tuple(jnp.zeros((B, H, N + 1, D), dt) for _ in range(L))
        vcs = tuple(jnp.zeros((B, H, N + 1, D), dt) for _ in range(L))
        bos = jnp.full((B,), bos_id, jnp.int32)

        if K == 1:
            def step_fn(state, tok, t):
                kc, vc = state
                kc, vc, logits = _nmt_decode_token(
                    params, acts, pe, kc, vc, tuple(xks), tuple(xvs),
                    mem_mask if masked else None, tok, t, H)
                return (kc, vc), logits

            (kcs, vcs), logits0 = step_fn((kcs, vcs), bos, jnp.int32(0))
            gen = _greedy_loop(logits0, (kcs, vcs), step_fn, pick, key,
                               1, N, B, eos_id)
            return gen, None

        # beam: cross K/V and the mask are per-BEAM constants — tile
        # them once to batch B*K (the state pytree only carries the
        # self-attention caches)
        xks_t = tuple(jnp.repeat(x, K, axis=0) for x in xks)
        xvs_t = tuple(jnp.repeat(x, K, axis=0) for x in xvs)
        mm_t = jnp.repeat(mem_mask, K, axis=0) if masked else None

        def step0(state, tok, t):
            kc, vc, logits = _nmt_decode_token(
                params, acts, pe, state[0], state[1], tuple(xks),
                tuple(xvs), mem_mask if masked else None, tok, t, H)
            return (kc, vc), logits

        def step_fn(state, tok, t):
            kc, vc, logits = _nmt_decode_token(
                params, acts, pe, state[0], state[1], xks_t, xvs_t,
                mm_t, tok, t, H)
            return (kc, vc), logits

        (kcs, vcs), logits0 = step0((kcs, vcs), bos, jnp.int32(0))
        gen, norm = _beam_loop(logits0, (kcs, vcs), step_fn, 1, N, B, K,
                               eos_id, alpha)
        return gen, norm

    return run


def nmt_translate(net, src, max_len: int, *, beam_size: int = 1,
                  eos_id: int = -1, bos_id: int = 0, alpha: float = 0.0,
                  temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                  src_valid_length=None, quantized=None):
    """Translate `src` with `models.Transformer` (encoder-decoder):
    the ENCODER runs through the public block (training numerics), the
    decoder runs the compiled KV-cache loop — greedy/sampling when
    ``beam_size == 1`` (returns int32 (B, max_len) target tokens, BOS
    excluded), K-beam otherwise (returns (sequences (B, K, max_len),
    scores (B, K)) best-first, GNMT length penalty via ``alpha``).

    ``bos_id`` seeds the decoder (the training convention prepends
    BOS=0); ``eos_id >= 0`` freezes finished rows/beams.  `quantized`
    selects the int8 weight-quantized decoder path as in `lm_generate`
    (the encoder stays float).
    ref: GluonNLP BeamSearchTranslator role `[UNVERIFIED — mount
    empty]`, one compiled program per signature.
    """
    from ..ndarray.ndarray import NDArray
    from .transformer import positional_encoding

    if isinstance(src, NDArray):
        src = src._data
    src = jnp.asarray(src, jnp.int32)
    B, S = src.shape
    N = int(max_len)
    K = int(beam_size)
    if N < 1:
        raise ValueError(f"max_len must be >= 1, got {N}")
    if K < 1:
        raise ValueError(f"beam_size must be >= 1, got {K}")
    # the same positional-limit contract lm_generate enforces via
    # net._max_len (ADVICE r5 #1: the attribute was dead and the two
    # entry points inconsistent)
    max_length = getattr(net, "_max_length", None)
    if max_length is not None:
        if N > max_length:
            raise ValueError(
                f"max_len {N} exceeds the model's max_length "
                f"{max_length}")
        if S > max_length:
            raise ValueError(
                f"src length {S} exceeds the model's max_length "
                f"{max_length}")
    V = net.out_proj._units
    if K > V:
        raise ValueError(f"beam_size {K} exceeds vocab {V}")
    if K > 1 and (temperature > 0.0 or top_k > 0):
        raise ValueError(
            "beam search is deterministic — temperature/top_k only "
            "apply at beam_size=1")
    H = net.decoder._layers[0].self_attn._num_heads
    qc = _quant_config(net, quantized)
    qkey = qc.cache_key() if qc is not None else None

    # encoder through the PUBLIC blocks — exact training numerics
    mask_nd = None
    mem_mask = jnp.ones((B, S), jnp.float32)
    masked = src_valid_length is not None
    if masked:
        vl = jnp.asarray(src_valid_length).reshape(-1)
        mem_mask = (jnp.arange(S)[None, :] < vl[:, None]).astype(jnp.float32)
        mask_nd = NDArray(mem_mask)
    mem = net.encoder(net._embed(net.src_embed, NDArray(src)),
                      mask_nd)._data

    # sampling params are inert at K>1 (validated above): keep them out
    # of the beam cache key so a sweep cannot trigger recompiles
    samp = (float(temperature), int(top_k)) if K == 1 else (0.0, 0)
    sig = ("nmt", B, S, N, K, int(eos_id), int(bos_id), float(alpha),
           samp, masked, qkey)
    cache = _program_cache(net)
    fn = _lru_touch(cache, sig)
    if fn is None:
        acts = tuple(lyr.ffn._act for lyr in net.decoder._layers)
        run = _build_nmt_program(B, S, N, K, H, int(eos_id), int(bos_id),
                                 float(alpha), samp[0], samp[1], acts,
                                 masked)
        fn = _cache_program(net, sig, jax.jit(run))
    # pe table built ONCE per width and cached on the net (an eager
    # rebuild per call would pay table construction + h2d every batch)
    pe = _pe_table(net, N + 1)
    params = _gather_nmt_params(net, qc)
    _record_decode_weight_bytes(params, qc)
    path = _decode_path(qc)
    gen, scores = _timed_decode(f"nmt_decode_{path}", path, B * K * N,
                                fn, params, mem, mem_mask, pe,
                                jax.random.PRNGKey(seed))
    return gen if K == 1 else (gen, scores)
