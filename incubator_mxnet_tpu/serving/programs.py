"""Compiled programs for paged continuous-batching decode.

Two program families, both STATIC-shaped so the serving engine never
recompiles after warmup (RetraceGuard-pinned in ci/serving_smoke.py):

* ``serving_step`` — ONE decode step for the whole fixed-width batch
  (``max_batch`` lanes).  Each lane carries its own block table row,
  position, token and PRNG key; inactive lanes write their K/V into
  the scratch block and their outputs are ignored host-side.  Compiled
  exactly once per engine: admission/eviction only change *argument
  values* (tables, masks), never shapes.
* ``serving_prefill_chunk`` — a FIXED-width window of ``chunk`` prompt
  positions computed against the paged pool (ISSUE 20).  The engine
  feeds a prompt through as ``ceil(P_tail / chunk)`` calls of this ONE
  program — start offset, valid length and the token window all ride
  in as traced values — so there is no per-bucket program ladder and
  no pow2 recompile for long prompts, and the scheduler can interleave
  decode steps between chunks (a 32k-token arrival no longer spikes
  every resident sequence's tpot).  Each chunk scatters its K/V into
  the sequence's pages and attends with the per-position
  ``kpos <= pos`` mask, which makes a position's K/V (and the
  first-token logits) INDEPENDENT of how the prompt was chunked — the
  prefix-cache bit-exactness argument in docs/serving.md.

Speculative decoding (ISSUE 19) adds three more static-shaped
families, built only when the engine configures ``speculate_k > 0``:

* ``serving_draft_step`` — k unrolled draft-model steps over the
  draft's own KV pool (same block tables/ids as the target's),
  emitting the proposals and their full proposal distributions.
* ``serving_spec_verify`` (+``_kv8``) — ONE batched (k+1)-token
  window forward of the TARGET against its paged pool, with on-device
  exact acceptance/rejection sampling (see `_build_spec_verify`).
* ``serving_draft_prefill_chunk`` — the chunk program on the draft
  weights, filling the draft pool alongside the target's.

Both donate the pool arrays, their scale pools and the recurrent state
(``donate_argnums=(0, 1, 2, 3, 4)``; the state is ``()`` for a decoder
of attention layers only, the carried counts for one with routed experts,
and for one whose layers select by an index the counts and a third pool a
layer, the index keys', beside K's and V's): the K/V pool
is a ring the engine threads through every call, and an un-donated
pool would copy the whole cache per token.  Donation coverage is
CI-pinned via `.hlolint_contracts.json` (serving_* entries).  A pool
array is ``(num_blocks, block_size, H*D)``: the one shape the donated
buffer, the K/V write (`ops.paged_attention.write_rows`) and the paged
kernel's page block all take as it lies, so the compiled program holds
no copy of it (`tests/test_chip_compile.py` pins that for a described
v5e; docs/serving.md, "The pool's layout").

Numerics: the step attention dispatches through
`ops.paged_attention` — on CPU (and whenever ``attn_impl="dense"``)
that is byte-for-byte the dense-gather recipe (scores and softmax in
fp32 with an iota position mask, exactly
`generation._cached_self_attn`'s math), so greedy tokens agree with
`lm_generate` and co-batched lanes are INDEPENDENT (batched matmuls
never mix lanes; masked key slots contribute exactly 0.0) — the two
facts the eviction bit-identity contract rests on (docs/serving.md
§"Why eviction is exact").  On TPU (or ``attn_impl="pallas"``) the
single-query Pallas kernel walks the block table directly — no dense
gather, nothing (B, H, max_seq_len)-shaped materialized — and the same
guarantees hold within the kernel path (deterministic, lane-local).

``kv_dtype="int8"`` keys a second program family
(``serving_step_kv8``/``serving_prefill_chunk_kv8``): K/V are quantized
per-head at page-write time (`contrib.quantization.quantize_kv`) with
fp32 scale pools riding alongside, and dequantized inside the
attention — s8 pages in HBM, CI-pinned via `.hlolint_contracts.json`.

Everything a program closes over is a plain int/float/str/tuple
(tpulint TPU008: no device arrays, no ``self`` captured); weights,
pools and per-lane state enter as arguments.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..contrib.quantization import quantize_kv
from ..models import generation as G
from ..ops.paged_attention import (default_impl, paged_attention,
                                   paged_attention_window, pages_per_step,
                                   pool_shapes, softmax_with_sink,
                                   window_kernel_fits, write_rows)
from ..ops.moe_experts import routed_experts
from ..ops.selective_scan import selective_scan
from ..ops.sparse_attention import (index_row, index_scores,
                                    paged_attention_sparse, select_positions)

__all__ = ["PagedPrograms"]

# LRU cap for the net-level serving program cache (override per net via
# `net._serving_program_cache_cap`): one step + one prefill-chunk
# program per engine config (plus the speculative pair when enabled)
_PROGRAM_CACHE_CAP = 16

# fold_in salts deriving the speculative acceptance / residual-resample
# streams from the per-request key: they must be DISTINCT from each
# other and from the plain position counters the draft/bonus picks use,
# so every uniform consumed by the rejection sampler is independent of
# the proposal that it judges (the exactness argument in
# docs/serving.md leans on this)
_ACCEPT_SALT = 0x5ACC
_RESID_SALT = 0x0E51


def _net_program_cache(net):
    """Net-level cache of JITTED serving programs keyed by the full
    static config, so a rebuilt engine with the same config (serving
    restarts, tests) reuses compiled programs instead of recompiling —
    the step/prefill analogue of generation's per-net program cache."""
    cache = getattr(net, "_serving_programs", None)
    if cache is None:
        cache = net._serving_programs = OrderedDict()
    return cache


def _note_build(kind: str) -> None:
    """Count a program-cache MISS (a fresh jit closure; the compile
    itself still happens lazily on first call)."""
    if telemetry.enabled():
        telemetry.counter("serving_program_builds_total",
                          labels={"kind": kind}).inc()


def _row_pick(temperature, top_k):
    """Single-lane token pick: logits (V,), position t, per-request key
    (2,) uint32 — greedy argmax at temperature<=0, else top-k-truncated
    sampling with a counter-based `fold_in(key, t)` so a request's
    sample stream depends only on (its seed, its positions), never on
    who it was co-batched with."""
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


def _top_k_logits(logits, temperature, top_k):
    """Temperature-scaled, top-k-masked logits — the distribution
    `_row_pick` samples from, shared with the speculative draft/verify
    programs so p (target) and q (draft) are BOTH this exact
    distribution (the acceptance ratio must compare like with like)."""
    lg = logits / jnp.float32(temperature)
    if top_k > 0:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, jnp.finfo(jnp.float32).min, lg)
    return lg


def _embed(spec, params, toks, pos):
    """Token embedding, scaled and given positions as the decoder's
    description says (`generation.DecoderSpec`)."""
    dt = params["embed"].dtype
    h = params["embed"][toks].astype(dt)
    if spec.embed_scale != 1.0:
        h = h * spec.embed_scale
    if spec.positions:
        h = h + params["pe"][pos].astype(dt)
    return h


def _dense32(x, w, b):
    """A small projection with a float32 result (`dt`, `B`, `C` stay
    float32 from here to the scan)."""
    y = jax.lax.dot_general(x, w.astype(x.dtype),
                            (((x.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y if b is None else y + b.astype(jnp.float32)


def _ssm_mixer(spec, lp, x, conv, state, row, fresh, ok, impl):
    """A Mamba mixer over the engine's recurrent state: ``x`` (N, T, C)
    normed inputs of N sequences, ``conv`` (d_conv - 1, S, d_inner) the
    lanes' conv windows (a tap a plane, so a lane is a row of each, as in
    the state), ``state`` (S, d_state, d_inner) float32.

    ``row=None``: sequence i is lane i (the decode step; N == S, T == 1)
    and ``ok`` (N, 1) says which lanes are live.  Else the one sequence
    continues lane ``row`` (a prefill chunk), from zero state and an
    empty window where ``fresh``, and ``ok`` (1, T) marks its valid
    positions.  A position that is not ok advances neither the state
    (its ``dt`` is 0: ``exp(0) * s + 0``) nor the window.  ``impl`` is the
    programs' "pallas" | "dense": the scan's kernel, or its XLA path.  Returns
    ``(out (N, T, C), conv, state)``, only the rows at hand rewritten."""
    Di, Ds, K, R = spec.ssm
    f32 = jnp.float32
    N, T = x.shape[:2]
    with jax.named_scope("ssm_conv"):
        uz = G._dense(x, *lp["in_proj"])
        u, z = uz[..., :Di], uz[..., Di:]
        taps, bias = lp["conv"]
        if row is None:                 # T == 1: a tap a plane of lanes
            held = jnp.concatenate([conv, jnp.swapaxes(u, 0, 1)])
            c = sum(held[k].astype(f32) * taps[k].astype(f32)
                    for k in range(K))[:, None]
        else:                           # N == 1: the lane's window, then u
            win = jax.lax.dynamic_index_in_dim(conv, row, 1, keepdims=False)
            win = jnp.where(fresh, jnp.zeros_like(win), win)
            held = jnp.concatenate([win, u[0]])         # (K-1+T, Di)
            c = sum(held[k:k + T].astype(f32) * taps[k].astype(f32)
                    for k in range(K))[None]
        if bias is not None:
            c = c + bias.astype(f32)
        u = jax.nn.silu(c).astype(x.dtype)
        dbc = _dense32(u, *lp["x_proj"])
        eps = spec.eps
        dt = G._rms(dbc[..., :R], *lp["dt_norm"], eps=eps).astype(x.dtype)
        Bm = G._rms(dbc[..., R:R + Ds], *lp["b_norm"], eps=eps)
        Cm = G._rms(dbc[..., R + Ds:], *lp["c_norm"], eps=eps)
        dt = jnp.where(ok[..., None],
                       jax.nn.softplus(_dense32(dt, *lp["dt_proj"])), 0.0)
    with jax.named_scope("ssm_scan"):
        y, state = selective_scan(
            u, dt, z, Bm, Cm, lp["A"], lp["D"], state,
            rows=None if row is None else row[None],
            reset=None if row is None else fresh.astype(jnp.int32)[None],
            impl="pallas" if impl == "pallas" else "xla")
    out = G._dense(y, *lp["out_proj"])
    with jax.named_scope("state_write"):
        if row is None:
            conv = jnp.where(ok[None], held[1:], conv)
        else:
            # the last K-1 inputs that are ok: rows n .. n+K-2 of `held`
            n = jnp.sum(ok.astype(jnp.int32))
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, jax.lax.dynamic_slice_in_dim(held, n, K - 1), row, 1)
    return out, conv, state


def ring_blocks(window, block_size):
    """Blocks a lane's ring holds for a window of ``window`` positions:
    those the visible positions ``t-window+1 .. t`` can lie in, whatever
    ``t``.  The block written at ``t`` then takes the place of one that
    lies wholly behind the window."""
    return (window - 2) // block_size + 2


def _ring_block(lane, blk, n):
    """The pool block that holds block ``blk`` of lane ``lane``'s
    sequence in a window layer: a ring of ``n`` a lane behind the scratch
    block, so a block behind the window gives its place to the one being
    written (docs/serving.md, "Window layers")."""
    return 1 + lane * n + blk % n


def _route(moe, x, router):
    """The router of a routed feed-forward over tokens ``x`` (N, C):
    scores over all experts in float32 (`generation.MoeSpec`: a sigmoid
    each, or a softmax over all), the ``top_k`` largest (of score +
    selection bias, where the scoring has one), the selected scores over
    their sum.  Returns ``(idx (N, K) int32, weights (N, K) float32)``."""
    w, bias = router
    g = jax.lax.dot_general(
        x, w.astype(x.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if moe.scoring == "softmax":
        g = jax.nn.softmax(g, axis=-1)
        _, idx = jax.lax.top_k(g, moe.top_k)
    else:
        g = jax.nn.sigmoid(g)
        _, idx = jax.lax.top_k(g + bias.astype(jnp.float32), moe.top_k)
    sel = jnp.take_along_axis(g, idx, axis=1)
    return idx.astype(jnp.int32), sel / jnp.sum(sel, axis=1, keepdims=True)


def _band_attention(q, k, v, start, window, sink, value_scale):
    """Attention of a chunk's queries ``q`` (T, Hq, D), at positions
    ``start .. start+T-1``, in a window layer: over ``k``, ``v`` (window-1
    + T, Hkv, .), the positions ``start-window+1 .. start+T-1`` (the
    window's reach before the chunk, then the chunk's own), each query
    seeing the ``window`` positions up to its own that exist (>= 0).  A
    block of queries at a time against the keys it can see; float32
    scores and softmax, the sink logit a head in the denominator."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G, W1 = Hq // Hkv, window - 1
    bq = 128 if T % 128 == 0 else T
    nb = T // bq
    at = jnp.arange(nb)[:, None] * bq + jnp.arange(bq + W1)[None, :]
    kb, vb = k[at], v[at]                       # (nb, bq+W1, Hkv, .)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(nb, bq, Hkv, G, D), kb,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    # query i of a block and key j of its keys lie i + W1 - j apart
    gap = jnp.arange(bq)[:, None] + W1 - jnp.arange(bq + W1)[None, :]
    seen = (gap >= 0) & (gap <= W1)                         # (bq, bq+W1)
    exists = start - W1 + at >= 0                           # (nb, bq+W1)
    s = jnp.where(seen[None, None, None] & exists[:, None, None, None], s,
                  jnp.finfo(jnp.float32).min)
    p = softmax_with_sink(
        s, None if sink is None else sink.reshape(Hkv, G, 1, 1))
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vb,
                   preferred_element_type=jnp.float32) * value_scale
    return o.astype(q.dtype).reshape(T, Hq, -1)


def _layers(spec, params, kv8, h, pool_k, pool_v, scale_k, scale_v, rec,
            wblk, off, attend, ssm=None, pos=None, ok=None, impl=None):
    """The decoder stack of every serving program, a layer at a time by
    the decoder's description: an "attn" layer is qkv, the K/V write
    into the lanes' pages at ``(wblk, off)`` (quantized first on an int8
    pool), attention over the pool — ``attend(q, pk, pv, sk, sv)`` is
    one thing the programs differ in — and the projection; an "ssm"
    layer is ``ssm(lp, x, conv, state)``, the other thing
    (`_ssm_mixer` over the step's lanes or over a chunk's one).  Then
    the FFN.  Pools are indexed by attention layer, ``rec = (states,
    conv windows)`` by ssm layer.  Each part runs under the
    `jax.named_scope` a device trace shows it by:
    ``layer<i>/kv_write``, ``layer<i>/paged_attn``, ``layer<i>/ssm_conv``,
    ``layer<i>/ssm_scan``, ``layer<i>/state_write``, ``layer<i>/ffn``.
    Returns ``(h, new_k, new_v, new_sk, new_sv, new_rec)``, the scale
    tuples empty on a float pool and ``new_rec`` ``()`` without ssm
    layers.

    Where the attention layers differ (``spec.attn``) a layer is three
    projections, rotary positions on queries and keys at ``pos``
    (``layer<i>/rope``), and ``attend(a, sink, q, k, v, pool_k, pool_v) ->
    (out, pool_k, pool_v)``, which writes and attends by the layer's
    `AttnSpec` ``a``: the full layers through the lanes' block tables,
    the window layers through their rings.  A "routed" feed-forward is the
    router (``layer<i>/router``) and the experts held here
    (``layer<i>/experts``: ``impl`` "pallas" is the ``moe_experts``
    kernel) over the tokens that are ``ok``; ``rec`` is then ``(counts,)``,
    three int32 the programs carry: pairs computed, tokens routed (a
    layer each), the most pairs one expert of one layer took, since the
    last decode step reported them.

    A layer with an index (`generation.IndexSpec`) also projects the index
    queries, the index key and the heads' weights from its normed input
    (``layer<i>/indexer``) and hands them to ``attend`` with the layer's
    index pool, which comes back written: ``rec`` is then ``(counts, index
    pools)``, a pool an indexed layer, and the counts gain four int32
    (`_add_wide`): positions scored and positions attended, summed over
    the ``ok`` queries and the indexed layers."""
    new_k, new_v, new_sk, new_sv, new_st, new_cv = [], [], [], [], [], []
    new_pi = []                         # index pools, an indexed layer
    routed = []                         # pairs by held expert, a layer
    for li, (lp, kind, act) in enumerate(zip(params["layers"], spec.kinds,
                                             spec.acts)):
        with jax.named_scope(f"layer{li}"):
            x = G._norm(spec, h, lp["ln1"])
            if kind == "ssm":
                j = len(new_st)
                a, cv, st = ssm(lp, x, rec[1][j], rec[0][j])
                h = h + a
                new_st.append(st)
                new_cv.append(cv)
            elif spec.attn:
                a = spec.attn[len(new_k)]
                lead = h.shape[:-1]
                q = G._dense(x, *lp["q"]).reshape(lead + (spec.heads, -1))
                k = G._dense(x, *lp["k"]).reshape(lead + (a.kv_heads, -1))
                v = G._dense(x, *lp["v"]).reshape(lead + (a.kv_heads, -1))
                if a.qk_norm:
                    q = G._rms(q, *lp["q_norm"], eps=spec.eps)
                    k = G._rms(k, *lp["k_norm"], eps=spec.eps)
                if spec.rope_dim and a.rope_base:
                    with jax.named_scope("rope"):
                        q = G._rope(q, pos, spec.rope_dim, a.rope_base)
                        k = G._rope(k, pos, spec.rope_dim, a.rope_base)
                if a.index:
                    ix = a.index
                    with jax.named_scope("indexer"):
                        qi = G._dense(x, *lp["index_q"]).reshape(
                            lead + (ix.heads, ix.dim))
                        ki = G._dense(x, *lp["index_k"]).reshape(
                            lead + (1, ix.dim))
                        qi = G._rope(qi, pos, ix.dim, a.rope_base)
                        ki = G._rope(ki, pos, ix.dim, a.rope_base)
                        wi = _dense32(x, *lp["index_w"])
                    o, pk, pv, pi = attend(
                        a, None, q, k, v, pool_k[len(new_k)],
                        pool_v[len(new_k)],
                        (qi, ki, wi, rec[1][len(new_pi)]))
                    new_pi.append(pi)
                else:
                    o, pk, pv = attend(a, lp.get("sink"), q, k, v,
                                       pool_k[len(new_k)],
                                       pool_v[len(new_k)])
                h = h + G._dense(o.reshape(lead + (-1,)), *lp["proj"])
                new_k.append(pk)
                new_v.append(pv)
            else:
                ai = len(new_k)
                q, k, v = G._qkv_heads(G._dense(x, *lp["qkv"]), spec.heads,
                                       spec.kv_heads)
                # write-then-read, the _cached_self_attn order: a position
                # is valid by the time the mask admits it
                with jax.named_scope("kv_write"):
                    if kv8:
                        k, ks = quantize_kv(k)  # s8 values / f32 scales
                        v, vs = quantize_kv(v)
                        sk = write_rows(scale_k[ai], wblk, off, ks)
                        sv = write_rows(scale_v[ai], wblk, off, vs)
                        new_sk.append(sk)
                        new_sv.append(sv)
                    else:
                        sk = sv = None
                    pk = write_rows(pool_k[ai], wblk, off, k)
                    pv = write_rows(pool_v[ai], wblk, off, v)
                with jax.named_scope("paged_attn"):
                    a = attend(q, pk, pv, sk, sv)
                h = h + G._dense(a.reshape(h.shape[:-1] + (-1,)),
                                 *lp["proj"])
                new_k.append(pk)
                new_v.append(pv)
            if act == "routed":
                x = G._norm(spec, h, lp["ln2"])
                with jax.named_scope("router"):
                    idx, wts = _route(spec.moe, x, lp["router"])
                with jax.named_scope("experts"):
                    y, counts = routed_experts(
                        x, idx, wts, ok, *lp["experts"],
                        first=spec.moe.first, experts=spec.moe.experts,
                        impl="pallas" if impl == "pallas" else "xla")
                h = h + y
                routed.append(counts)
                continue
            with jax.named_scope("ffn"):
                h = h + G._ffn_fwd(G._norm(spec, h, lp["ln2"]), lp, act)
    new_rec = (tuple(new_st), tuple(new_cv)) if new_st else ()
    carried = []
    if routed:
        counts = jnp.stack(routed)                      # (layers, held)
        carried = [
            rec[0][0] + jnp.sum(counts),
            rec[0][1] + len(routed) * jnp.sum(ok.astype(jnp.int32)),
            jnp.maximum(rec[0][2], jnp.max(counts))]
    if new_pi:
        at = len(carried)
        context = jnp.where(ok, pos + 1, 0)
        carried += _add_wide(rec[0][at], rec[0][at + 1], jnp.sum(context),
                             len(new_pi))
        carried += _add_wide(
            rec[0][at + 2], rec[0][at + 3],
            jnp.sum(jnp.minimum(context, spec.index.topk)), len(new_pi))
    if carried:
        new_rec = (jnp.stack(carried).astype(jnp.int32),) \
            + ((tuple(new_pi),) if new_pi else ())
    return (h, tuple(new_k), tuple(new_v), tuple(new_sk), tuple(new_sv),
            new_rec)


# a count that can pass int32 between two decode steps (a long prompt's
# chunks with no lane decoding) is carried as two: what lies above 2**20,
# and the rest
_WIDE = 20


def _add_wide(hi, lo, x, times):
    """``[hi, lo]`` of the count ``hi * 2**20 + lo`` after ``times`` times
    the int32 ``x`` is added to it."""
    lo = lo + times * (x & ((1 << _WIDE) - 1))
    return [hi + times * (x >> _WIDE) + (lo >> _WIDE),
            lo & ((1 << _WIDE) - 1)]


def wide_counts(words) -> tuple:
    """The counts that `_add_wide` carried as ``hi, lo, hi, lo, ...``."""
    return tuple((int(hi) << _WIDE) + int(lo)
                 for hi, lo in zip(words[0::2], words[1::2]))


def counts_carried(spec) -> int:
    """How many int32 the programs of this decoder carry and hand over
    behind a decode step's tokens: three for routed experts (pairs, tokens
    routed, the busiest expert's pairs), then four for an index (positions
    scored and positions attended, each as `_add_wide` has it)."""
    return 3 * (spec.moe is not None) + 4 * (spec.index is not None)


def _sparse_attend(ix, impl, tables, last, qpos, wblk, off):
    """`_layers`' ``attend`` of a layer with an index, for ``N`` sequences
    of ``T`` queries (a decode step's lanes of one, a prefill chunk's one
    of ``T``) whose arrays come ``lead``-shaped: write K/V and the index
    key at ``(wblk, off)``, score every position at or before each query's
    own (``qpos``) against the index pool, select the ``topk`` best, and
    attend those alone.  ``last`` (N,): each sequence's last query's
    position."""
    impl = "pallas" if impl == "pallas" else "xla"
    N, T = qpos.shape

    def seqs(x):                # (lanes or chunk, ...) -> (N, T, ...)
        return x.reshape((N, T) + x.shape[1:])

    def attend(a, sink, q, k, v, pk, pv, index):
        qi, ki, wi, pi = index
        with jax.named_scope("kv_write"):
            pk, pv = write_rows(pk, wblk, off, k), write_rows(pv, wblk, off, v)
        with jax.named_scope("indexer"):
            pi = write_rows(pi, wblk, off, jnp.pad(
                ki, ((0, 0),) * (ki.ndim - 1)
                + ((0, pi.shape[2] - ki.shape[-1]),)))
            scores = index_scores(seqs(qi), seqs(wi), pi, tables, last,
                                  impl=impl)
        with jax.named_scope("select"):
            seen = select_positions(scores, qpos, ix.topk, impl=impl)
        with jax.named_scope("sparse_attn"):
            o = paged_attention_sparse(seqs(q), pk, pv, tables, last, seen,
                                       impl=impl)
        return o.reshape(q.shape), pk, pv, pi

    return attend


def _step_attend(spec, bs, attn_impl, tables, pos, ok, wblk, off):
    """`_layers`' ``attend`` of a decode step where the attention layers
    differ: write the token's K/V, then the single-query kernel.  A full
    layer goes through the lanes' block tables as ever.  A window layer's
    pages are a ring a lane (`_ring_block`): its table is reckoned here
    from the lane and its position, from the first visible block on, with
    positions counted from that block's start, which is the form
    `paged_attention` takes a first visible position in.  A layer with an
    index scores, selects and attends through the lanes' block tables
    (`_sparse_attend`)."""
    W = spec.window
    if W:
        n = ring_blocks(W, bs)
        lane = jnp.arange(pos.shape[0], dtype=jnp.int32)
        lo = jnp.maximum(pos - (W - 1), 0)
        base = lo // bs
        tables_w = _ring_block(
            lane[:, None], base[:, None] + jnp.arange(n, dtype=jnp.int32), n)
        pos_w, first_w = pos - base * bs, lo - base * bs
        wblk_w = jnp.where(ok, _ring_block(lane, pos // bs, n), jnp.int32(0))
    if spec.index:
        sparse = _sparse_attend(spec.index, attn_impl, tables, pos,
                                pos[:, None], wblk, off)

    def attend(a, sink, q, k, v, pk, pv, index=None):
        if a.index:
            return sparse(a, sink, q, k, v, pk, pv, index)
        tb, wb, p, first = (tables_w, wblk_w, pos_w, first_w) if a.window \
            else (tables, wblk, pos, None)
        with jax.named_scope("kv_write"):
            pk, pv = write_rows(pk, wb, off, k), write_rows(pv, wb, off, v)
        with jax.named_scope("paged_attn"):
            o = paged_attention(q, pk, pv, tb, p, first=first, sink=sink,
                                value_scale=spec.value_scale, impl=attn_impl)
        return o, pk, pv

    return attend


def _token_forward(params, spec, bs, kv8, attn_impl,
                   pool_k, pool_v, scale_k, scale_v, rec,
                   tables, toks, pos, active, guard_msl=None):
    """One token's forward over the paged pool — the `serving_step`
    body minus the pick: embed `toks` at `pos`, write each attention
    layer's K/V into the lane's current block and attend, advance each
    ssm layer's state by the token (live lanes only), and return
    ``(new_k, new_v, new_sk, new_sv, new_rec, logits)``.

    ``guard_msl``: the speculative families step positions past the
    engine-committed ones (``pos .. pos+k``), so a full-length lane's
    window can run off the end of the sequence — with a guard length
    those positions clamp their gathers and write to the scratch block
    instead of wrapping into a neighbour's pages (their logits are
    never consumed host-side).  The non-speculative step passes None
    and keeps its original, unguarded ops byte-for-byte.
    """
    if guard_msl is None:
        pos_c = pos
        blk_idx = pos // bs
        ok = active
    else:
        pos_c = jnp.clip(pos, 0, guard_msl - 1)
        blk_idx = jnp.clip(pos_c // bs, 0, tables.shape[1] - 1)
        ok = active & (pos < guard_msl)
    off = pos_c % bs
    with jax.named_scope("embed"):
        h = _embed(spec, params, toks, pos_c)               # (B, C)
    # the block this step writes: the lane's table entry for its
    # current position — inactive (or guarded-out) lanes are pointed
    # at scratch
    wblk = jnp.take_along_axis(tables, blk_idx[:, None], axis=1)[:, 0]
    wblk = jnp.where(ok, wblk, jnp.int32(0))
    def ssm(lp, x, conv, state):
        a, conv, state = _ssm_mixer(spec, lp, x[:, None], conv, state,
                                    None, None, ok[:, None], attn_impl)
        return a[:, 0], conv, state

    if spec.attn:
        attend = _step_attend(spec, bs, attn_impl, tables, pos, ok, wblk,
                              off)
    else:
        def attend(q, pk, pv, sk, sv):
            return paged_attention(q, pk, pv, tables, pos, scale_k=sk,
                                   scale_v=sv, impl=attn_impl)  # (B, H, D)

    h, new_k, new_v, new_sk, new_sv, new_rec = _layers(
        spec, params, kv8, h, pool_k, pool_v, scale_k, scale_v, rec,
        wblk, off, attend, ssm, pos=pos_c, ok=ok, impl=attn_impl)
    with jax.named_scope("head"):
        logits = G._logits_of(params, h, spec)              # (B, V)
    return new_k, new_v, new_sk, new_sv, new_rec, logits


class _HostPacked:
    """A served program, jitted, whose small host arrays travel as ONE
    buffer.  ``fn(*device, *host, params)``: ``n_device`` leading
    arguments that live on the device, the first ``n_donated`` of them
    (all, unless told) donated (pools, scales, recurrent state; the step
    before's tokens are read and left), then the scheduler's numpy arrays
    and scalars of a call (block tables, tokens, positions, flags, keys),
    then the weight pytree.  Every numpy argument of a jitted call is a
    transfer of its own, 0.12 ms of the host's time each on a v5e (my
    chip runs, PR 30: six of them 0.6 ms a call, twice an iteration);
    here they are laid end to end in one int32 array on the host and cut
    apart again inside the program (static slices, a bitcast for uint32,
    ``!= 0`` for bool).
    Called and lowered like the jitted ``fn``; the compiled program keeps
    ``fn``'s name."""

    def __init__(self, fn, n_device, n_donated=None):
        self._fn, self._n = fn, int(n_device)
        self._donated = self._n if n_donated is None else int(n_donated)
        self._sig = self._jitted = None     # the host signature served

    def _program(self, host):
        sig = tuple((a.shape, a.dtype.char) for a in host)
        if sig != self._sig:        # an engine's shapes are fixed: once
            fn, n = self._fn, self._n
            cuts, at = [], 0
            for a in host:
                cuts.append((at, at + a.size, a.shape, a.dtype))
                at += a.size

            def program(*args):
                host = []
                for lo, hi, shape, dtype in cuts:
                    a = args[n][lo:hi].reshape(shape)
                    if dtype == bool:
                        a = a != 0
                    elif dtype != jnp.int32:
                        a = jax.lax.bitcast_convert_type(a, dtype)
                    host.append(a)
                return fn(*args[:n], *host, args[n + 1])

            program.__name__ = fn.__name__
            self._sig, self._jitted = sig, jax.jit(
                program, donate_argnums=tuple(range(self._donated)))
        return self._jitted

    def _split(self, args):
        n = self._n
        host = [np.asarray(a) for a in args[n:-1]]
        for a in host:
            if a.dtype.itemsize != 4 and a.dtype != np.bool_:
                raise TypeError(f"host argument of dtype {a.dtype}: only "
                                "bool and 4-byte types are packed")
        packed = np.concatenate(
            [(a.astype(np.int32) if a.dtype == np.bool_
              else a.view(np.int32)).ravel() for a in host])
        return self._program(host), args[:n] + (packed, args[-1])

    def __call__(self, *args):
        jitted, args = self._split(args)
        return jitted(*args)

    def lower(self, *args):
        jitted, args = self._split(args)
        return jitted.lower(*args)


def _build_step(spec, block_size, blocks_per_seq, temperature, top_k,
                kv_dtype, attn_impl, name):
    """The batched one-token decode program over the paged pool.

    Arguments (all traced):
      pool_k/pool_v    a tuple entry an attention layer, each
                       (num_blocks, bs, Hkv*D): a position a row, a KV
                       head a run of D lanes — s8 when
                       ``kv_dtype="int8"``, model dtype else
      scale_k/scale_v  per-layer fp32 scale pools (num_blocks, bs, Hkv)
                       for the int8 pool; EMPTY tuples on the float path
      rec              ``(states, conv windows)``, a tuple entry an ssm
                       layer: (B, d_state, d_inner) float32 and
                       (d_conv-1, B, d_inner), a row a lane; ``()`` for a
                       decoder without ssm layers
      prev             (B,) int32 — the step before's ``next_tokens``, the
                       device array they still are (not donated); with
                       routed layers or an index `counts_carried` counts
                       follow the tokens, in and out (`_layers`), and
                       ``rec`` is their carry, with an index ``(counts,
                       index pools)``: a third pool an indexed layer,
                       (num_blocks, bs, `index_row` (index dim)), rows
                       as K's and V's
      tables           (B, blocks_per_seq) int32 block ids per lane
      toks             (B,) int32 — the host's token of a lane
      fresh            (B,) bool — lanes whose input token is the host's
                       (a prompt's final chunk picked it); the others
                       take ``prev``, which the host may not have read yet
      pos              (B,) int32 — position this step writes/attends to
      active           (B,) bool  — lanes with a live sequence
      keys             (B, 2) uint32 — per-lane PRNG keys
      params           generation._gather_params pytree
    Returns (new_k, new_v, new_scale_k, new_scale_v, new_rec,
    next_tokens).  Every active lane's state row is rewritten in place;
    an inactive lane's keeps its value.

    ``attn_impl`` ("pallas"|"dense") picks the `ops.paged_attention`
    path; ``name`` becomes the jitted function's __name__ so
    RetraceGuard can budget the program family by name.
    """
    bs = int(block_size)
    pick = _row_pick(temperature, top_k)
    kv8 = kv_dtype == "int8"

    def serving_step(pool_k, pool_v, scale_k, scale_v, rec, prev, tables,
                     toks, fresh, pos, active, keys, params):
        if counts_carried(spec):
            prev = prev[:toks.shape[0]]         # its counts follow it
        toks = jnp.where(fresh, toks, prev)
        new_k, new_v, new_sk, new_sv, new_rec, logits = _token_forward(
            params, spec, bs, kv8, attn_impl,
            pool_k, pool_v, scale_k, scale_v, rec, tables, toks, pos, active)
        with jax.named_scope("pick"):
            nxt = jax.vmap(pick)(logits, pos, keys)
        if counts_carried(spec):
            # the counts since the step before (this step's and the
            # chunk's before it) leave with the tokens, and the carry
            # starts again from zero
            nxt = jnp.concatenate([nxt, new_rec[0]])
            new_rec = (jnp.zeros_like(new_rec[0]),) + new_rec[1:]
        return new_k, new_v, new_sk, new_sv, new_rec, nxt

    serving_step.__name__ = name
    return serving_step


def chunk_attention(spec, chunk, kv_dtype, attn_impl) -> str:
    """How a prefill chunk of ``chunk`` queries attends the sequence's
    pages in the table-named layers, from the shapes, the K/V dtype and
    the implementation alone: ``"window"`` (`paged_attention_window`: the
    chunk's queries walk the pages once together), ``"lanes"`` (every
    position a lane of the single-query kernel: int8 pages, which the
    window form has no scales for, and sizes it has no tiling for) or
    ``"dense"`` (no kernel); ``"sparse"`` where every such layer has an
    index (`ops.sparse_attention`: the pages once for a tile of queries,
    under each query's selection)."""
    if attn_impl != "pallas":
        return "dense"
    full = {a.kv_heads for a in spec.attn if not a.window and not a.index} \
        if spec.attn else {spec.kv_heads}
    if spec.index and not full:
        return "sparse"
    fits = kv_dtype != "int8" and all(
        window_kernel_fits(chunk, spec.heads, kv_heads, spec.head_dim,
                           spec.v_dim or None) for kv_heads in full)
    return "window" if fits else "lanes"


def _chunk_attend(spec, bs, attn_impl, window, tables, table_row, posc, ok,
                  wblk, off, start, valid_len, lane):
    """`_layers`' ``attend`` of a prefill chunk where the attention layers
    differ.  A full layer writes the chunk's K/V into the sequence's pages
    and attends them: with ``window`` through `paged_attention_window`,
    else as lanes of the single-query kernel.  A window layer
    attends densely (`_band_attention`): its own keys, and before them the
    window's reach behind the chunk, read from the lane's ring as the
    chunk before left it; then it writes into the ring only what a later
    query can still see, the ``window - 1`` positions before the chunk's
    end.  So a chunk needs no pages of its own in a window layer, however
    long it is (docs/serving.md, "Window layers").  A layer with an index
    writes the chunk's K/V and index keys into the sequence's pages, then
    scores, selects and attends a query at a time in one call each
    (`_sparse_attend`)."""
    W = spec.window
    if W:
        n, W1 = ring_blocks(W, bs), W - 1
        before = start - W1 + jnp.arange(W1, dtype=jnp.int32)
        b_blk = _ring_block(lane, jnp.maximum(before, 0) // bs, n)
        b_off = jnp.maximum(before, 0) % bs
        end = jnp.minimum(start + posc.shape[0], valid_len)
        tail = ok & (posc >= end - W1)
        wblk_w = jnp.where(tail, _ring_block(lane, posc // bs, n),
                           jnp.int32(0))
    if spec.index:
        # the chunk's queries stand at start .. start+T-1, clipped or not:
        # a query past the sequence's length is not ok, and its row unread
        T = posc.shape[0]
        sparse = _sparse_attend(
            spec.index, attn_impl, table_row[None], (start + T - 1)[None],
            (start + jnp.arange(T, dtype=jnp.int32))[None], wblk, off)

    def attend(a, sink, q, k, v, pk, pv, index=None):
        if a.index:
            return sparse(a, sink, q, k, v, pk, pv, index)
        if not a.window:
            with jax.named_scope("kv_write"):
                pk, pv = write_rows(pk, wblk, off, k), \
                    write_rows(pv, wblk, off, v)
            with jax.named_scope("paged_attn"):
                if window:
                    o = paged_attention_window(
                        q, pk, pv, table_row, start,
                        value_scale=spec.value_scale)
                else:
                    o = paged_attention(q, pk, pv, tables, posc,
                                        value_scale=spec.value_scale,
                                        impl=attn_impl)
            return o, pk, pv
        with jax.named_scope("paged_attn"):
            kb = pk[b_blk, b_off].reshape((W1,) + k.shape[1:])
            vb = pv[b_blk, b_off].reshape((W1,) + v.shape[1:])
            o = _band_attention(q, jnp.concatenate([kb, k]),
                                jnp.concatenate([vb, v]), start, W, sink,
                                spec.value_scale)
        with jax.named_scope("kv_write"):
            pk, pv = write_rows(pk, wblk_w, off, k), \
                write_rows(pv, wblk_w, off, v)
        return o, pk, pv

    return attend


def _build_prefill_chunk(spec, block_size, blocks_per_seq, chunk,
                         temperature, top_k, kv_dtype, attn_impl, name):
    """ONE fixed-width prefill chunk (ISSUE 20): positions
    ``start .. start+chunk-1`` of a single sequence's prompt, computed
    against the paged pool.  The engine walks a prompt's uncached tail
    through repeated calls — admission binds cache-hit prefix blocks
    read-only and ``start`` begins at the cached length.

    The body is the `_build_spec_verify` window recipe at batch 1:
    embed the window, scatter each layer's K/V into the sequence's
    pages (positions >= valid_len land in scratch), then ONE batched
    `paged_attention` (or, on the kernel path with float pages,
    `paged_attention_window`: the same mask, each page read once for all
    the chunk's queries, whatever the heads' width: `chunk_attention`)
    whose per-row ``kpos <= pos`` mask gives every
    window position exactly its causal prefix — including the
    positions this very chunk just wrote (write-then-read, the
    `serving_step` order).  Because each row's math is lane-local
    (batched matmuls never mix rows; masked slots contribute exactly
    0.0), a position's K/V and logits are byte-identical however the
    prompt is split into chunks — the fact that makes a prefix-cache
    hit bit-identical to a cold prefill.

    The first generated token is picked from the ``valid_len-1`` row
    on every call; the engine consumes it only from the final chunk.
    With ``kv_dtype="int8"`` K/V quantize per-head before the scatter
    and fp32 scales land in the scale pools.

    An ssm layer continues row ``lane`` of its state and conv window
    (``rec``, as `_build_step` has it) over the chunk's valid positions,
    from zero when ``start == 0`` whatever the lane held, and rewrites
    that one row; positions at or past ``valid_len`` advance nothing.
    A recurrence is sequential, so the state after a prompt does not
    depend on how the prompt was chunked either (to float32 roundoff:
    the scan's order of operations is the same, the matmuls' tiling is
    not).
    """
    bs = int(block_size)
    nbps = int(blocks_per_seq)
    CH = int(chunk)
    msl = nbps * bs
    pick = _row_pick(temperature, top_k)
    kv8 = kv_dtype == "int8"
    # where the kernel has the sizes for it, the chunk's queries walk the
    # sequence's pages once together and not once each, at any head width
    # (the kernel cuts a KV head's lanes out of a run of whole pages)
    window = chunk_attention(spec, CH, kv_dtype, attn_impl) == "window"

    def serving_prefill_chunk(pool_k, pool_v, scale_k, scale_v, rec,
                              table_row, toks, start, valid_len, key, lane,
                              params):
        posw = start + jnp.arange(CH, dtype=jnp.int32)         # (CH,)
        ok = posw < valid_len
        posc = jnp.clip(posw, 0, msl - 1)
        with jax.named_scope("embed"):
            h = _embed(spec, params, toks, posc)               # (CH, C)
        blk_idx = jnp.clip(posc // bs, 0, nbps - 1)
        off = posc % bs
        wblk = jnp.where(ok, table_row[blk_idx], jnp.int32(0))
        tables = jnp.broadcast_to(table_row[None, :], (CH, nbps))

        def ssm(lp, x, conv, state):
            a, conv, state = _ssm_mixer(spec, lp, x[None], conv, state,
                                        lane, start == 0, ok[None],
                                        attn_impl)
            return a[0], conv, state

        if spec.attn:
            attend = _chunk_attend(spec, bs, attn_impl, window, tables,
                                   table_row, posc, ok, wblk, off, start,
                                   valid_len, lane)
        elif window:
            def attend(q, pk, pv, sk, sv):
                return paged_attention_window(q, pk, pv, table_row, start)
        else:
            def attend(q, pk, pv, sk, sv):
                return paged_attention(q, pk, pv, tables, posc, scale_k=sk,
                                       scale_v=sv, impl=attn_impl)

        h, new_k, new_v, new_sk, new_sv, new_rec = _layers(
            spec, params, kv8, h, pool_k, pool_v, scale_k, scale_v, rec,
            wblk, off, attend, ssm, pos=posc, ok=ok,
            impl=attn_impl)                                    # (CH,H,D)
        with jax.named_scope("head"):
            logits = G._logits_of(params, h, spec)             # (CH, V)
        with jax.named_scope("pick"):
            li_idx = jnp.clip(valid_len - 1 - start, 0, CH - 1)
            first = pick(logits[li_idx], valid_len - 1, key)
        return new_k, new_v, new_sk, new_sv, new_rec, first

    serving_prefill_chunk.__name__ = name
    return serving_prefill_chunk


def _build_draft_step(spec, block_size, k, temperature, top_k,
                      greedy, attn_impl, msl, name):
    """k unrolled single-token draft steps over the DRAFT KV pool.

    The draft pool shares the target's block tables and `BlockPool`
    ids (one host-side allocation covers both pools), so this is
    exactly k `serving_step` bodies on the draft weights — same
    write-then-read page scatter, same paged attention — except the
    pick at step j both emits the proposal d_j AND records q_j, the
    full temp-scaled top-k-masked softmax the proposal was drawn from
    (the verifier's acceptance ratio needs q_j(d_j) and the residual
    needs the whole row).  Greedy mode (argmax drafts) returns a
    (B, k, 1) placeholder instead — the verifier never reads it.

    Positions ``pos .. pos+k-1`` can run past a full-length lane's
    last position; ``msl`` guards those steps into the scratch block.
    """
    bs = int(block_size)

    def serving_draft_step(pool_k, pool_v, tables, toks, pos, active,
                           keys, params):
        pk, pv = pool_k, pool_v
        cur = toks
        d_toks, d_probs = [], []
        for j in range(k):
            pk, pv, _, _, _, logits = _token_forward(
                params, spec, bs, False, attn_impl,
                pk, pv, (), (), (), tables, cur, pos + j, active,
                guard_msl=msl)
            with jax.named_scope("pick"):
                if greedy:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    d_probs.append(jnp.zeros_like(logits[..., :1]))
                else:
                    lg = _top_k_logits(logits, temperature, top_k)
                    nxt = jax.vmap(
                        lambda l, t, key: jax.random.categorical(
                            jax.random.fold_in(key, t), l, axis=-1)
                    )(lg, pos + j, keys).astype(jnp.int32)
                    d_probs.append(jax.nn.softmax(lg, axis=-1))
            d_toks.append(nxt)
            cur = nxt
        return (pk, pv, jnp.stack(d_toks, axis=1),
                jnp.stack(d_probs, axis=1))

    serving_draft_step.__name__ = name
    return serving_draft_step


def _build_draft_prefill_chunk(spec, block_size, blocks_per_seq,
                               chunk, attn_impl, name):
    """The chunk program on the DRAFT weights, filling the draft pool
    alongside the target's — `_build_prefill_chunk` minus the
    first-token pick (the target already picks it) and minus the
    int8-KV family (the draft pool always stays in the draft model's
    dtype: it is small and its quantization error would depress
    acceptance for nothing)."""
    bs = int(block_size)
    nbps = int(blocks_per_seq)
    CH = int(chunk)
    msl = nbps * bs

    def serving_draft_prefill_chunk(pool_k, pool_v, table_row, toks,
                                    start, valid_len, params):
        posw = start + jnp.arange(CH, dtype=jnp.int32)
        ok = posw < valid_len
        posc = jnp.clip(posw, 0, msl - 1)
        with jax.named_scope("embed"):
            h = _embed(spec, params, toks, posc)               # (CH, C)
        blk_idx = jnp.clip(posc // bs, 0, nbps - 1)
        off = posc % bs
        wblk = jnp.where(ok, table_row[blk_idx], jnp.int32(0))
        tables = jnp.broadcast_to(table_row[None, :], (CH, nbps))
        _, new_k, new_v, _, _, _ = _layers(
            spec, params, False, h, pool_k, pool_v, (), (), (), wblk, off,
            lambda q, pk, pv, sk, sv: paged_attention(
                q, pk, pv, tables, posc, impl=attn_impl))
        return new_k, new_v

    serving_draft_prefill_chunk.__name__ = name
    return serving_draft_prefill_chunk


def _build_spec_verify(spec, block_size, k, temperature, top_k,
                       greedy, kv_dtype, attn_impl, msl, name):
    """The speculative verifier: ONE batched forward of every lane's
    (k+1)-token window against the TARGET paged pool, then exact
    acceptance/rejection on device.

    The window is ``[toks, d_1 .. d_k]`` at positions
    ``pos .. pos+k`` — the big matmuls (qkv/proj/ffn/logits) batch
    over B·(k+1) rows, which is the whole point: one weight stream
    amortized over up to k+1 emitted tokens.  Per layer the FULL
    window's K/V scatter into the lane's pages
    (``pos//bs .. (pos+k)//bs``) first, then attention runs as k+1
    unrolled `paged_attention` calls at the exact single-query shape
    and per-position mask of `serving_step` — so window position j's
    math is byte-identical to the sequential step's (later positions'
    writes are already in the pool but the ``kpos <= pos+j`` mask
    contributes exactly 0 for them), which is what makes greedy
    speculation bit-identical to non-speculative decode.

    Acceptance (stochastic): accept d_j while
    ``u_j < p_j(d_j) / q_j(d_j)`` with u_j drawn from the
    `_ACCEPT_SALT`-derived stream at counter pos+j; the first rejected
    position resamples from ``normalize(max(p - q, 0))``
    (`_RESID_SALT` stream), and a fully-accepted window earns the
    bonus token sampled from p_{k+1} with the plain pick recipe.
    Every consumed draw has a unique (salt, counter) pair across the
    request's lifetime, and is independent of the proposal stream —
    the emitted distribution is provably the target's.  Greedy:
    ``out = argmax(logits)`` and the accept length is the leading run
    of draft/argmax matches.

    Returns ``(new_k, new_v, new_sk, new_sv, out (B, k+1) int32,
    accept_len (B,) int32)``; the engine delivers
    ``out[:, :accept_len+1]``.  No device-side rollback exists or is
    needed: rejected positions' pages are overwritten before any mask
    admits them (write-before-read, the same argument as bucket-pad
    garbage), so rollback is host-side position truncation only.
    """
    bs = int(block_size)
    T = k + 1
    kv8 = kv_dtype == "int8"

    def serving_spec_verify(pool_k, pool_v, scale_k, scale_v, tables,
                            toks, pos, active, keys, draft_toks,
                            draft_probs, params):
        win = jnp.concatenate([toks[:, None], draft_toks], axis=1)
        posw = (pos[:, None]
                + jnp.arange(T, dtype=jnp.int32)[None, :])     # (B, T)
        posc = jnp.clip(posw, 0, msl - 1)
        with jax.named_scope("embed"):
            h = _embed(spec, params, win, posc)                # (B, T, C)
        blk_idx = jnp.clip(posc // bs, 0, tables.shape[1] - 1)
        off = posc % bs
        wblk = jnp.take_along_axis(tables, blk_idx, axis=1)    # (B, T)
        wblk = jnp.where(active[:, None] & (posw < msl), wblk,
                         jnp.int32(0))
        h, new_k, new_v, new_sk, new_sv, _ = _layers(
            spec, params, kv8, h, pool_k, pool_v, scale_k, scale_v, (),
            wblk, off,
            lambda q, pk, pv, sk, sv: jnp.stack(
                [paged_attention(q[:, j], pk, pv, tables, pos + j,
                                 scale_k=sk, scale_v=sv, impl=attn_impl)
                 for j in range(T)], axis=1))                  # (B,T,H,D)
        with jax.named_scope("head"):
            logits = G._logits_of(params, h, spec)             # (B,T,V)

        with jax.named_scope("pick"):
            if greedy:
                out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                match = (draft_toks == out[:, :k]).astype(jnp.int32)
                alen = jnp.cumprod(match, axis=1).sum(axis=1)
            else:
                lg = _top_k_logits(logits, temperature, top_k)
                p = jax.nn.softmax(lg, axis=-1)                    # (B,T,V)

                def lane(lg_l, p_l, q_l, d_l, t0, key):
                    ts = t0 + jnp.arange(k, dtype=jnp.int32)
                    us = jax.vmap(lambda t: jax.random.uniform(
                        jax.random.fold_in(
                            jax.random.fold_in(key, _ACCEPT_SALT), t)))(ts)
                    pd = jnp.take_along_axis(p_l[:k], d_l[:, None], 1)[:, 0]
                    qd = jnp.take_along_axis(q_l, d_l[:, None], 1)[:, 0]
                    acc = (us * jnp.maximum(qd, 1e-38) < pd).astype(jnp.int32)
                    alen_l = jnp.cumprod(acc).sum()
                    # first rejected position (clamped when all accepted —
                    # then `last` selects the bonus instead)
                    ri = jnp.minimum(alen_l, k - 1)
                    resid = jnp.maximum(p_l[ri] - q_l[ri], 0.0)
                    corr = jax.random.categorical(
                        jax.random.fold_in(
                            jax.random.fold_in(key, _RESID_SALT), t0 + ri),
                        jnp.log(resid + 1e-38)).astype(jnp.int32)
                    bonus = jax.random.categorical(
                        jax.random.fold_in(key, t0 + k),
                        lg_l[k]).astype(jnp.int32)
                    last = jnp.where(alen_l == k, bonus, corr)
                    d_pad = jnp.concatenate(
                        [d_l, jnp.zeros((1,), jnp.int32)])
                    out_l = jnp.where(jnp.arange(T) < alen_l, d_pad, last)
                    return out_l, alen_l

                out, alen = jax.vmap(lane)(lg, p, draft_probs, draft_toks,
                                           pos, keys)
        return new_k, new_v, new_sk, new_sv, out, alen.astype(jnp.int32)

    serving_spec_verify.__name__ = name
    return serving_spec_verify


def _nbytes(arrays) -> int:
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(arrays))


class PagedPrograms:
    """The engine's only handle on the device: the compiled programs, the
    arrays they thread through every call, and the calls themselves.

    Programs: one jitted step program plus ONE fixed-width prefill-chunk
    program (and the speculative three), all resolved through a net-level
    LRU keyed by the full static config — rebuilding an engine with the
    same config reuses the compiled programs.

    Arrays: the K/V pools, a tuple entry an attention layer,
    ``(num_blocks, bs, Hkv*D)`` — a position a row and a KV head a run of
    D lanes, the one shape the K/V write, the paged kernel and the
    donated buffer all take as it lies (docs/serving.md) — with fp32
    scale pools ``(num_blocks, bs, Hkv)`` beside int8 pages; the second
    kind of per-sequence state (docs/serving.md, "Two kinds of state"),
    per ssm layer one float32 recurrent state ``(B, d_state, d_inner)``
    and one conv window ``(d_conv-1, B, d_inner)``, a row a lane, which a
    prompt's first chunk starts from zero; for a decoder whose attention
    layers select by an index, in that same slot the carried counts and an
    index pool a layer, ``(num_blocks, bs, index_row(dim))``, named by the
    block tables as K's and V's are (docs/serving.md, "An index over the
    pages"); when speculating, the draft's
    K/V pools in the draft model's dtype, addressed by the SAME block
    tables and `BlockPool` ids as the target's, so one lane allocation
    covers both and eviction frees both.  This object holds the ONLY
    reference to each and rebinds it after every donated call (the
    buffers really are deleted on XLA:CPU too).  Beside them the last
    step's next tokens, which the next step takes as its input where they
    lie (not donated: the scheduler reads them a step late).  And the
    gathered weight pytrees, cached on the nets' weight-buffer
    fingerprints.

    Calls: a method a program family (`prefill_chunk`, `step`,
    `draft_step`, `spec_verify`), each taking what a scheduler knows
    (tables, tokens, positions) and returning what it consumes, still on
    the device; `gather_params()` refreshes the weights they read.

    The arrays are touched by ONE thread only, the engine's scheduler:
    the call methods, `gather_params` and `release` are its alone.  What
    other threads read (`kv_pool_bytes`, `state_bytes`,
    `pages_per_step`, `chunk_attn`, the labels) is frozen at
    construction."""

    @telemetry.profiler.setup_phased("programs")
    def __init__(self, net, *, max_batch, block_size, temperature, top_k,
                 quantized, max_seq_len=None, num_blocks=None,
                 kv_dtype=None, attn_impl=None, prefill_chunk=32,
                 speculate_k=0, draft_net=None, spec_greedy=False):
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (model dtype) or 'int8', "
                f"got {kv_dtype!r}")
        if attn_impl not in (None, "pallas", "dense"):
            raise ValueError(
                f"attn_impl must be None (auto), 'pallas' or 'dense', "
                f"got {attn_impl!r}")
        self._net = net
        self._spec = G.decoder_spec(net)
        if self._spec.carried:
            # a recurrence cannot be rolled back to a rejected position,
            # nor can a ring that has given a block's place away, and
            # neither state is a page a scale could sit beside; the
            # speculative programs carry no experts' counts, and neither
            # they nor a prefix hit nor a scale pool know of an index pool
            what = "recurrent (ssm) layers" if self._spec.recurrent \
                else "an index over its pages" if self._spec.index \
                else "window layers or routed experts"
            if int(speculate_k) > 0 or draft_net is not None:
                raise ValueError(
                    "speculative decoding (speculate_k / draft_net) is not "
                    f"built for a decoder with {what}")
            if kv_dtype == "int8":
                raise ValueError(
                    "kv_dtype='int8' is not built for a decoder with "
                    f"{what}")
            if self._spec.recurrent and self._spec.moe is not None:
                raise ValueError("a decoder with both recurrent (ssm) "
                                 "layers and routed experts is not built")
        self._B = int(max_batch)
        self._bs = int(block_size)
        msl = int(max_seq_len if max_seq_len is not None
                  else self._spec.max_len)
        msl = (msl // self._bs) * self._bs
        if msl < self._bs:
            raise ValueError(
                f"max_seq_len {max_seq_len} < one block ({block_size})")
        if msl > self._spec.max_len:
            raise ValueError(
                f"max_seq_len {msl} exceeds net.max_len "
                f"{self._spec.max_len}")
        self._msl = msl
        self._nbps = msl // self._bs
        self._num_blocks = int(num_blocks if num_blocks is not None
                               else self._B * self._nbps + 1)
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        self._qc = G._quant_config(net, quantized)
        self._kv_dtype = kv_dtype
        self._impl_forced = attn_impl is not None
        self._impl = attn_impl or default_impl()
        if int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self._chunk = min(int(prefill_chunk), msl)
        # distinct def names per KV family: RetraceGuard budgets
        # compiles BY NAME, so the int8-KV programs must not count
        # against (or hide behind) the float-KV budget
        sfx = "_kv8" if kv_dtype == "int8" else ""
        self._key = (self._spec, self._bs, self._nbps,
                     self._temperature, self._top_k, self.path,
                     self._kv_dtype, self._impl)
        self._label = self.path + ("_ssm" if self._spec.recurrent else "") \
            + ("_win" if self._spec.window else "") \
            + ("_moe" if self._spec.moe is not None else "") \
            + ("_idx" if self._spec.index else "") \
            + sfx + ("_pallas" if self._impl_forced
                     and self._impl == "pallas" else "")
        self._params = None
        self._params_key = None
        self._called = set()                # program kinds called once
        self._step = self._program(
            ("step",) + self._key, lambda: _HostPacked(
                _build_step(self._spec, self._bs, self._nbps,
                            self._temperature, self._top_k,
                            self._kv_dtype, self._impl,
                            "serving_step" + sfx), 6, 5))
        self._prefill_chunk = self._program(
            ("prefill_chunk", self._chunk) + self._key, lambda: _HostPacked(
                _build_prefill_chunk(self._spec, self._bs, self._nbps,
                                     self._chunk, self._temperature,
                                     self._top_k, self._kv_dtype,
                                     self._impl,
                                     "serving_prefill_chunk" + sfx), 5))
        self._init_speculative(net, speculate_k, draft_net, spec_greedy)
        self._allocate()

    def _program(self, key, build):
        """The jitted program under ``key`` (its first entry the family)
        in the net's cache, built on a miss."""
        cache = _net_program_cache(self._net)
        prog = G._lru_touch(cache, key)
        if prog is None:
            _note_build(key[0])
            prog = build()
            G._lru_put(self._net, cache, key, prog,
                       "_serving_program_cache_cap", _PROGRAM_CACHE_CAP,
                       gauge="serving_program_cache_size")
        return prog

    def _init_speculative(self, net, speculate_k, draft_net, spec_greedy):
        """Resolve the draft model and build the speculative program
        pair.  ``draft_net=None`` with ``speculate_k>0`` self-drafts
        through PR 7's int8 weight path (requires
        `net.quantize_for_decode` and a float target — an int8 target
        drafting for itself would verify its own proposals)."""
        self._spec_k = k = int(speculate_k)
        self._spec_greedy = greedy = \
            bool(spec_greedy) or self._temperature <= 0.0
        self._draft_params = None
        self._draft_params_key = None
        self._draft_net = self._draft_spec = None
        if k == 0:
            return
        msl = self._msl
        if k < 0:
            raise ValueError(
                f"speculate_k must be >= 0, got {speculate_k}")
        if k >= msl:
            raise ValueError(f"speculate_k {k} >= max_seq_len {msl}")
        if draft_net is None:
            if self.path != "float":
                raise ValueError(
                    "speculate_k with draft_net=None self-drafts via the "
                    "int8 weight path, but the target is already int8 — "
                    "pass a distinct draft_net")
            self._draft_qc = G._quant_config(net, True)
            self._draft_net = net
            self._draft_spec = dspec = self._spec
            self._draft_label = "self-int8"
        else:
            self._draft_qc = G._quant_config(draft_net, None)
            self._draft_net = draft_net
            self._draft_spec = dspec = G.decoder_spec(draft_net)
            if dspec.carried:
                raise ValueError("a draft_net with recurrent (ssm) layers, "
                                 "window layers or routed experts cannot "
                                 "be rolled back")
            if dspec.vocab != self._spec.vocab:
                raise ValueError(
                    f"draft_net vocab {dspec.vocab} != target vocab "
                    f"{self._spec.vocab}")
            if dspec.max_len < msl:
                raise ValueError(
                    f"draft_net.max_len {dspec.max_len} < "
                    f"max_seq_len {msl}")
            self._draft_label = f"net[{len(dspec.kinds)}x{dspec.units}]"
        sfx = "_kv8" if self._kv_dtype == "int8" else ""
        self._draft_step = self._program(
            ("draft_step",) + self._key
            + (dspec, G._decode_path(self._draft_qc), k, greedy),
            lambda: jax.jit(
                _build_draft_step(dspec, self._bs, k, self._temperature,
                                  self._top_k, greedy, self._impl, msl,
                                  "serving_draft_step"),
                donate_argnums=(0, 1)))
        self._spec_verify = self._program(
            ("spec_verify",) + self._key + (k, greedy),
            lambda: jax.jit(
                _build_spec_verify(self._spec, self._bs, k,
                                   self._temperature, self._top_k,
                                   greedy, self._kv_dtype, self._impl,
                                   msl, "serving_spec_verify" + sfx),
                donate_argnums=(0, 1, 2, 3)))
        self._draft_prefill_chunk = self._program(
            ("draft_prefill_chunk", self._chunk) + self._key + (dspec,),
            lambda: jax.jit(
                _build_draft_prefill_chunk(
                    dspec, self._bs, self._nbps, self._chunk, self._impl,
                    "serving_draft_prefill_chunk"),
                donate_argnums=(0, 1)))

    def _allocate(self):
        """The device arrays (class docstring), zeroed, and their byte
        counts.  ``self._kv`` is ``[pool_k, pool_v, scale_k, scale_v,
        rec]`` in the order the target's programs take and return them,
        ``self._draft_kv`` the draft's ``[pool_k, pool_v]``; empty tuples
        stand for what this configuration has none of."""
        spec, B, bs = self._spec, self._B, self._bs
        params = self.gather_params()
        G._record_decode_weight_bytes(params, self._qc)
        emb = params["embed"]
        kv8 = self._kv_dtype == "int8"
        dt = jnp.int8 if kv8 else emb.dtype
        L = spec.kinds.count("attn")
        page, scales = pool_shapes(self._num_blocks, bs, spec.kv_heads,
                                   spec.head_dim)

        def each(n, shape, dtype, make=jnp.zeros):
            return tuple(make(shape, dtype) for _ in range(n))

        rec = ()
        if spec.recurrent:
            Di, Ds, K, _ = spec.ssm
            n_ssm = spec.kinds.count("ssm")
            rec = (each(n_ssm, (B, Ds, Di), jnp.float32),
                   each(n_ssm, (K - 1, B, Di), emb.dtype))
        elif counts_carried(spec):
            # the counts, then an index pool a layer that has an index:
            # rows as K's and V's, named by the same block tables
            pools_i = tuple(
                jnp.zeros((self._num_blocks, bs, index_row(a.index.dim)), dt)
                for a in spec.attn if a.index)
            rec = (jnp.zeros((counts_carried(spec),), jnp.int32),) \
                + ((pools_i,) if pools_i else ())
        n_sc = L if kv8 else 0
        # a window layer's pages: a ring of `window_blocks` a lane behind
        # the scratch block, whatever `max_seq_len` (0: no window layer)
        self.window_blocks = ring_blocks(spec.window, bs) \
            if spec.window else 0
        if spec.attn:
            dv = spec.v_dim or spec.head_dim
            nb_w = B * self.window_blocks + 1
            pools_k = tuple(jnp.zeros(
                (nb_w if a.window else self._num_blocks, bs,
                 a.kv_heads * spec.head_dim), dt) for a in spec.attn)
            pools_v = tuple(jnp.zeros(
                (nb_w if a.window else self._num_blocks, bs,
                 a.kv_heads * dv), dt) for a in spec.attn)
            full = [i for i, a in enumerate(spec.attn) if not a.window]
            if full:    # the kernel's rule takes the mean of the two rows
                page = page[:2] + ((pools_k[full[0]].shape[2]
                                    + pools_v[full[0]].shape[2]) // 2,)
        else:
            pools_k, pools_v = each(L, page, dt), each(L, page, dt)
        self._kv = [pools_k, pools_v,
                    each(n_sc, scales, jnp.float32, jnp.ones),
                    each(n_sc, scales, jnp.float32, jnp.ones), rec]
        # the last step's next tokens, which the next step reads where
        # they lie (before any step: no lane takes its token from them);
        # behind them the counts of a decoder with routed layers or an index
        self._last = jnp.zeros((B + counts_carried(spec),), jnp.int32)
        self._draft_kv = [(), ()]
        if self._spec_k:
            dspec = self._draft_spec
            ddt = self._draft_params["embed"].dtype
            dpage, _ = pool_shapes(self._num_blocks, bs, dspec.kv_heads,
                                   dspec.head_dim)
            n = len(dspec.kinds)
            self._draft_kv = [each(n, dpage, ddt), each(n, dpage, ddt)]
        # how a prefill chunk attends the table-named pages: "window",
        # "lanes" or "dense" (`chunk_attention`), static for an engine
        self.chunk_attn = chunk_attention(spec, self._chunk,
                                          self._kv_dtype, self._impl)
        # how a layer with an index selects the positions it attends:
        # "kernel" (`select_positions`' Pallas kernel), "xla", or "none"
        # (no index), static for an engine
        self.index_select = "none" if spec.index is None else \
            "kernel" if self._impl == "pallas" else "xla"
        # block-table entries a grid step of the single-query kernel
        # covers (the kernel's own rule, from these shapes); 0 on the
        # dense path, which runs no kernel
        self.pages_per_step = pages_per_step(
            bs, self._nbps, page[2] * jnp.dtype(dt).itemsize) \
            if self._impl == "pallas" else 0
        # the footprints are STATIC (donation replaces arrays, never
        # shapes): frozen here so readers on other threads never touch
        # the live tuples the scheduler thread is rewriting.  Draft pages
        # count: they are resident HBM spent per token position.  The
        # window layers' rings are counted apart: their bytes do not grow
        # with a sequence's length.
        windowed = [i for i, a in enumerate(spec.attn) if a.window]
        self.window_pool_bytes = _nbytes(
            [(pools_k[i], pools_v[i]) for i in windowed])
        # the index pools grow with a sequence's length as K's and V's do,
        # block for block: a block's bytes count all three arrays
        self.index_pool_bytes = _nbytes(rec[1:]) if spec.index else 0
        self.kv_pool_bytes = _nbytes(self._kv[:4] + self._draft_kv) \
            - self.window_pool_bytes + self.index_pool_bytes
        self.state_bytes = _nbytes(rec) if spec.recurrent else 0

    # -- static description (any thread) ------------------------------- #
    @property
    def spec(self):
        """The target decoder's `generation.DecoderSpec`."""
        return self._spec

    @property
    def max_seq_len(self) -> int:
        """Cap on prompt+generated per sequence: the asked-for length (or
        the net's) rounded down to whole blocks."""
        return self._msl

    @property
    def num_blocks(self) -> int:
        return self._num_blocks

    @property
    def path(self) -> str:
        """Telemetry label of the weight path ("float" / "int8")."""
        return G._decode_path(self._qc)

    @property
    def kv_dtype(self):
        return self._kv_dtype

    @property
    def attn_impl(self) -> str:
        """Resolved paged-attention impl ("pallas" / "dense")."""
        return self._impl

    @property
    def prog_label(self) -> str:
        """Telemetry/program label: weight path, plus ``_ssm`` for a
        decoder with recurrent layers, ``_kv8`` for the int8 KV pool and
        ``_pallas`` when the kernel was forced off its
        home platform (the hlolint gate compiles that variant on CPU to
        pin the no-dense-probs census)."""
        return self._label

    @property
    def prefill_chunk_len(self) -> int:
        """Static chunk width in tokens (never over `max_seq_len`)."""
        return self._chunk

    @property
    def spec_greedy(self) -> bool:
        """Effective acceptance mode: True = argmax prefix-match
        (temperature<=0 always implies it)."""
        return self._spec_greedy

    @property
    def draft_label(self) -> str:
        """Draft identity for telemetry/varz ("self-int8" or the
        draft net's shape)."""
        return self._draft_label

    # -- the device arrays, for a reader on the scheduler's thread or of
    # an engine at rest (tests, chip_smoke.py) ------------------------- #
    @property
    def kv_pools(self) -> tuple:
        """``(pool_k, pool_v, scale_k, scale_v)``, each a tuple entry an
        attention layer (the scales empty on a float pool)."""
        return tuple(self._kv[:4])

    @property
    def recurrent_state(self) -> tuple:
        """``(states, conv windows)``, a tuple entry an ssm layer; ``()``
        for a decoder without any."""
        return self._kv[4]

    @property
    def index_pools(self) -> tuple:
        """The index keys' pools, an entry a layer with an index; ``()``
        for a decoder without one."""
        return self._kv[4][1] if self._spec.index else ()

    @property
    def draft_pools(self) -> tuple:
        """The draft's ``(pool_k, pool_v)``; empty without speculation."""
        return tuple(self._draft_kv)

    # -- scheduler thread only ----------------------------------------- #
    def gather_params(self):
        """Refresh the weight pytrees the calls below read — the
        target's, which is returned, and the draft's when speculating —
        each cached on its net's weight-buffer identity fingerprint (PR 7
        idiom): the engine calls this every iteration — training/
        `set_data` swaps are picked up, but an unchanged net costs ~a
        dozen id() calls and the int8 requantize never runs per-token."""
        key = G._params_fingerprint(self._net)
        if self._params_key != key:
            self._params = G._gather_params(self._net, self._msl, self._qc)
            self._params_key = key
        if self._spec_k:
            key = G._params_fingerprint(self._draft_net)
            if self._draft_params_key != key:
                self._draft_params = G._gather_params(
                    self._draft_net, self._msl, self._draft_qc)
                self._draft_params_key = key
        return self._params

    def release(self):
        """Let go of the device arrays, the gathered weight pytrees and
        the nets (the engine's scheduler calls this as it ends, on the
        one thread that writes them): the jitted programs stay in the
        nets' own caches."""
        self._kv = [(), (), (), (), ()]
        self._draft_kv = [(), ()]
        self._last = None
        self._net = self._draft_net = None
        self._params = self._params_key = None
        self._draft_params = self._draft_params_key = None

    def _call(self, kind, n_tokens, fn, held, *args):
        """One program call: ``fn(*held, *args)`` under the family's
        telemetry label, its leading outputs — the donated arrays,
        replaced — bound back into ``held`` in place.  Returns the other
        outputs (still on the device; nothing is waited for)."""
        if kind not in self._called:
            return self._first_call(kind, n_tokens, fn, held, *args)
        out = G._timed_decode(f"serving_{kind}_{self._label}",
                              f"serving_{self._label}", n_tokens,
                              fn, *held, *args)
        held[:] = out[:len(held)]
        return out[len(held):]

    def _first_call(self, kind, *call):
        """`_call`'s first of a kind, where its program is traced, lowered
        and compiled (or loaded from the cache): a phase of the start,
        ``setup.first_call.<kind>``."""
        self._called.add(kind)
        with telemetry.profiler.setup_phase("first_call." + kind,
                                            label=self._label):
            return self._call(kind, *call)

    def prefill_chunk(self, row, toks, start, P, key, lane, n):
        """Positions ``start .. start+n-1`` of lane ``lane``'s prompt of
        ``P`` tokens (``toks`` the chunk-wide window, ``row`` the lane's
        block-table row) into the pool — and into the draft's pool when
        speculating: the draft's first proposal attends to the full
        prompt.  Returns the first-token pick, meaningful on a prompt's
        final chunk."""
        start, P = np.int32(start), np.int32(P)
        first, = self._call("prefill_chunk", n, self._prefill_chunk,
                            self._kv, row, toks, start, P, key,
                            np.int32(lane), self._params)
        if self._spec_k:
            self._call("draft_prefill_chunk", n, self._draft_prefill_chunk,
                       self._draft_kv, row, toks, start, P,
                       self._draft_params)
        return first

    def step(self, tables, toks, pos, active, keys, fresh, n_live):
        """One decode step of every lane; returns the next tokens (B,).
        A lane's input token is ``toks``' where ``fresh``, else what the
        step before returned for it, taken on the device: the caller need
        not have read that yet."""
        nxt, = self._call("step", n_live, self._step, self._kv, self._last,
                          tables, toks, fresh, pos, active, keys,
                          self._params)
        self._last = nxt
        return nxt

    def draft_step(self, tables, toks, pos, active, keys, n_live):
        """k draft steps of every lane on the draft's pool; returns
        ``(d_toks (B, k), d_probs)`` for `spec_verify`."""
        return self._call("draft_step", n_live * self._spec_k,
                          self._draft_step, self._draft_kv, tables, toks,
                          pos, active, keys, self._draft_params)

    def spec_verify(self, tables, toks, pos, active, keys, d_toks, d_probs,
                    n_live):
        """The target's verdict on the lanes' windows; returns ``(out
        (B, k+1), accept_len (B,))``.  The recurrent state is no part of
        it (no recurrent decoder speculates)."""
        pools = self._kv[:4]
        out = self._call("spec_verify", n_live, self._spec_verify, pools,
                         tables, toks, pos, active, keys, d_toks, d_probs,
                         self._params)
        self._kv[:4] = pools
        return out
