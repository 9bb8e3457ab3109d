"""A decoder whose attention layers attend a learned selection of their
context, with routed feed-forwards (`models/routed_sparse.py`), against
its own eager forward and against the plain reference
(`perf/reference/keye-vl-2-30b-a3b.py`, which imports nothing of the
program), on seeded float32 weights at a tiny size: 3 layers, 8 query
heads on 2 KV heads of 16, an index of 4 heads of 8 that keeps 12
positions (fewer than the contexts), 4 experts held of 16, top-4.

Whole-sequence forward; prefill in chunks and decoding through
`ServingEngine`, through all three pools (docs/serving.md, "An index over
the pages"), on logits AND on the selected sets; a context within `topk`
is the plain grouped-query model; the chip's share of the experts tied to
the whole layer; what the engine counts, reckons and refuses.
"""
import importlib
import importlib.util
import itertools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu.models import generation as G
from incubator_mxnet_tpu.models.routed_sparse import RoutedSparseDecoder
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.serving import ServingEngine
from incubator_mxnet_tpu.serving import programs as SP

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_moe = importlib.import_module("incubator_mxnet_tpu.ops.moe_experts")

TOPK = 12
CFG = dict(vocab_size=97, hidden_size=32, num_hidden_layers=3,
           num_attention_heads=8, num_key_value_heads=2, head_dim=16,
           moe_intermediate_size=16, num_experts=4, num_experts_published=16,
           num_experts_per_tok=4, rope_theta=1e7, rms_norm_eps=1e-6,
           max_position_embeddings=128,
           sa_config=dict(indexer_head_dim=8, indexer_num_heads=4,
                          indexer_num_kv_heads=1, topk=TOPK))
ENGINE = dict(max_batch=3, block_size=4, max_seq_len=128, prefill_chunk=16)


@pytest.fixture(scope="module")
def ref():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "perf_reference_keye_vl_2_30b_a3b",
        os.path.join(ROOT, "perf", "reference", "keye-vl-2-30b-a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _param_map(shapes):
    """Reference leaf -> program parameter, as the configuration's
    `param_map` has it (`l3.q_w` -> `q_w3`)."""
    out = {"embed": "embed.weight", "head": "head_w", "ln_g": "ln.gamma"}
    for leaf in shapes:
        if "." in leaf:
            layer, name = leaf[1:].split(".")
            out[leaf] = {"router_w": "router"}.get(name, name) + layer
    return out


def _seeded(ref, cfg=CFG, held="0-3", dtype="float32", seed=5, std=0.2):
    """(net, the reference's float32 leaves, the reference's cfg): the
    benchmark's own weights from the seed (wider than its 0.02, so that
    every path moves the logits at this size)."""
    from perf import weights

    rcfg = dict(cfg, experts_held=held)
    net = RoutedSparseDecoder(
        dtype=dtype, **dict(cfg, first_expert=int(held.split("-")[0])))
    net.initialize()
    shapes = ref.param_shapes(rcfg)
    w = weights.make(seed, shapes, std=std)
    weights.assign(weights.leaves(net, _param_map(shapes), w), w)
    return net, {k: v.astype(jnp.float32) for k, v in w.items()}, rcfg


@pytest.fixture(scope="module")
def seeded(ref):
    return _seeded(ref)


def _ref_logits(ref, w32, rcfg, seq, select="index"):
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(w32, jnp.asarray(seq), rcfg, "fp32", select)
        return onp.asarray(ref.head(ref.head_matrix(w32), h))


def _gap(lg, prompt, served):
    """How far the served tokens' logits lie below the best, in the
    whole-sequence logits ``lg`` of prompt + served."""
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(served)]
    return float(max(r.max() - r[t] for r, t in zip(rows, served)))


def _prompts(ns, seed=1):
    rng = onp.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).astype(onp.int32)
            for n in ns]


# --- the description ------------------------------------------------------ #
def test_the_description_follows_the_published_keys(seeded):
    net, _, _ = seeded
    spec = G.decoder_spec(net)
    assert spec.kinds == ("attn",) * 3 and spec.acts == ("routed",) * 3
    assert spec.attn == (G.AttnSpec(2, 0, False, 1e7, G.IndexSpec(4, 8, TOPK),
                                    True),) * 3
    assert spec.index == G.IndexSpec(4, 8, TOPK)
    assert (spec.heads, spec.head_dim, spec.rope_dim, spec.v_dim) == (
        8, 16, 16, 0)
    assert spec.moe == G.MoeSpec(16, 0, 4, 4, 16, "softmax")
    assert spec.carried and not spec.window and not spec.recurrent
    assert SP.counts_carried(spec) == 7
    layers = G._gather_params(net, 128)["layers"]
    assert set(layers[0]) == {"ln1", "ln2", "q", "k", "v", "proj", "q_norm",
                              "k_norm", "index_q", "index_k", "index_w",
                              "router", "experts"}
    # nothing is copied: a layer's leaves are the net's own buffers
    assert layers[2]["experts"][0] is net.gate_e2.data()._data
    assert layers[1]["index_q"][0].shape == (4 * 8, 32)
    assert layers[1]["router"] == (net.router1.data()._data, None)


def test_the_accepted_decoders_describe_no_index():
    """The new fields default to what the three accepted configurations
    are: no index, no head norm, a sigmoid router with a bias."""
    assert G.AttnSpec(4, 0, False, 1e4) == G.AttnSpec(4, 0, False, 1e4, None,
                                                      False)
    assert G.MoeSpec(32, 0, 8, 4, 16).scoring == "sigmoid"
    from incubator_mxnet_tpu.models.transformer import TransformerLM

    lm = TransformerLM(vocab=50, units=16, hidden_size=32, num_layers=1,
                       num_heads=2, max_len=64, dropout=0.0)
    spec = G.decoder_spec(lm)
    assert spec.index is None and SP.counts_carried(spec) == 0


def test_grad_req_null_holds_no_gradient_buffers():
    net = RoutedSparseDecoder(dtype="bfloat16", grad_req="null", **CFG)
    net.initialize()
    for name, p in net.collect_params().items():
        assert p.grad_req == "null", name
        assert str(p.data()._data.dtype) == "bfloat16", name


def test_what_the_class_does_not_build_is_refused():
    with pytest.raises(ValueError, match="own matrix"):
        RoutedSparseDecoder(**dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="one key a position"):
        RoutedSparseDecoder(**dict(CFG, sa_config=dict(
            CFG["sa_config"], indexer_num_kv_heads=2)))
    with pytest.raises(ValueError, match="not among"):
        RoutedSparseDecoder(**dict(CFG, first_expert=14))


# --- against the reference -------------------------------------------------- #
def test_forward_matches_the_reference(seeded, ref):
    net, w32, rcfg = seeded
    seq = _prompts((60,), seed=3)[0]
    got = onp.asarray(net(NDArray(jnp.asarray(seq)[None]))._data[0])
    want = _ref_logits(ref, w32, rcfg, seq)
    assert onp.abs(want).max() > 1.0
    onp.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("drop", ["q_norm_g", "k_norm_g", "index_q_w",
                                  "index_k_w", "index_w_w", "router_w"])
def test_every_assumed_piece_moves_the_reference(seeded, ref, drop):
    """A reference that lost one of the pieces the configuration lists as
    assumed (a head norm's gain, an index matrix, the router) answers
    otherwise: none is inert at this size."""
    _, w32, rcfg = seeded
    seq = _prompts((60,), seed=3)[0]
    want = _ref_logits(ref, w32, rcfg, seq)
    changed = dict(w32)
    for leaf in w32:
        if leaf.endswith("." + drop):
            changed[leaf] = jnp.flip(w32[leaf], 0) if drop.endswith("_w") \
                else jnp.ones_like(w32[leaf])
    assert onp.abs(_ref_logits(ref, changed, rcfg, seq) - want).max() > 1e-2


def _spy_on_the_selection(monkeypatch):
    """Every selection the served programs compute, as it is computed:
    [(call's number in its program, positions, mask)]."""
    got, real, n = [], SP.select_positions, itertools.count()

    def spy(scores, pos, k, **kw):
        seen = real(scores, pos, k, **kw)
        call = next(n)
        jax.debug.callback(
            lambda p, s: got.append((call, onp.asarray(p), onp.asarray(s))),
            pos, seen, ordered=True)
        return seen

    monkeypatch.setattr(SP, "select_positions", spy)
    return got


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_engine_matches_the_reference_on_logits_and_selections(
        ref, monkeypatch, impl):
    """Prefill in chunks, then decoding, through K's, V's and the index
    keys' pages: the served tokens are the reference's own (gap 0), and
    every query's selected set, in every layer, is the reference's `S_t`
    position for position: 12 of up to 62, so the index decides."""
    net, w32, rcfg = _seeded(ref)
    got = _spy_on_the_selection(monkeypatch)
    prompt = _prompts((45,), seed=4)[0]
    with ServingEngine(net, attn_impl=impl,
                       **dict(ENGINE, max_batch=1)) as eng:
        # the selection's form: the kernel under pallas (interpret mode)
        assert eng.varz_config()["index_select"] == {
            "dense": "xla", "pallas": "kernel"}[impl]
        toks = onp.asarray(eng.submit(prompt, 18).result(timeout=900))
    seq = onp.concatenate([prompt, toks])
    assert _gap(_ref_logits(ref, w32, rcfg, seq), prompt, toks) < 1e-4
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(ref.selections(w32, jnp.asarray(seq), rcfg))
    assert want.shape == (3, len(seq), len(seq))
    assert (want.sum(-1)[:, TOPK:] == TOPK).all()
    L, seen_at = 3, {}
    for call, pos, seen in got:
        # a chunk's rows past the prompt are no queries, and the last
        # served token is never one
        lo, hi = (0, len(prompt)) if pos.shape[1] > 1 \
            else (len(prompt), len(seq) - 1)
        for p, row in zip(pos.reshape(-1), seen.reshape(-1, seen.shape[-1])):
            if lo <= p < hi:
                assert (call % L, int(p)) not in seen_at
                seen_at[call % L, int(p)] = row
    # every layer, every position whose token was a query: the prompt's by
    # a chunk, each served token's but the last by a step
    assert set(seen_at) == {(l, p) for l in range(L)
                            for p in range(len(seq) - 1)}
    for (l, p), row in seen_at.items():
        assert not row[len(seq):].any()
        onp.testing.assert_array_equal(row[:len(seq)], want[l, p],
                                       err_msg=f"layer {l} position {p}")


def test_a_context_within_topk_is_the_plain_grouped_query_model(ref):
    """While no context passes `topk` every position is selected: the
    served tokens are those of the same weights under plain causal
    grouped-query attention (the reference with the latest `topk`
    positions in the selection's place, which is then all of them)."""
    cfg = dict(CFG, sa_config=dict(CFG["sa_config"], topk=64))
    net, w32, rcfg = _seeded(ref, cfg)
    prompts = _prompts((37, 5), seed=9)
    with ServingEngine(net, **ENGINE) as eng:
        hs = [eng.submit(p, 12) for p in prompts]
        served = [onp.asarray(h.result(timeout=600)) for h in hs]
    for p, t in zip(prompts, served):
        seq = onp.concatenate([p, t])
        plain = _ref_logits(ref, w32, rcfg, seq, select="recent")
        onp.testing.assert_allclose(_ref_logits(ref, w32, rcfg, seq), plain,
                                    atol=1e-5)
        assert _gap(plain, p, t) < 1e-4
    # and with 12 kept of these contexts it is another model
    _, w32, rcfg = _seeded(ref)
    seq = onp.concatenate([prompts[0], served[0]])
    assert onp.abs(_ref_logits(ref, w32, rcfg, seq)
                   - _ref_logits(ref, w32, rcfg, seq, "recent")).max() > 1e-2


def test_the_result_does_not_depend_on_the_chunking(seeded):
    net, _, _ = seeded
    prompt = _prompts((53,), seed=2)[0]
    out = []
    for chunk in (8, 16, 64):
        with ServingEngine(net, **dict(ENGINE, prefill_chunk=chunk)) as eng:
            out.append(eng.submit(prompt, 8).result(timeout=600))
    assert out[0] == out[1] == out[2]


def test_lanes_do_not_mix(seeded, ref):
    """Three requests of different lengths together, each answered as the
    reference answers it alone."""
    net, w32, rcfg = seeded
    prompts = _prompts((50, 5, 33), seed=7)
    with ServingEngine(net, **ENGINE) as eng:
        hs = [eng.submit(p, 10) for p in prompts]
        served = [onp.asarray(h.result(timeout=600)) for h in hs]
    for p, t in zip(prompts, served):
        assert _gap(_ref_logits(ref, w32, rcfg, onp.concatenate([p, t])),
                    p, t) < 1e-4


def test_engine_in_bfloat16_stays_within_its_rounding(ref):
    net, w32, rcfg = _seeded(ref, dtype="bfloat16", std=0.05)
    prompt = _prompts((40,), seed=5)[0]
    with ServingEngine(net, **ENGINE) as eng:
        toks = onp.asarray(eng.submit(prompt, 10).result(timeout=600))
    seq = onp.concatenate([prompt, toks])
    # 12 positions kept: a selection that flips on a rounding moves more
    # than a rounded product does
    assert _gap(_ref_logits(ref, w32, rcfg, seq), prompt, toks) < 0.5


# --- a chip's share ---------------------------------------------------------- #
def test_the_shares_add_up_to_the_whole_layer(ref):
    """The guide's share test: four chips of 4 experts each route over all
    16 (softmax, top-4 renormalised, no bias) and compute the pairs of
    their own 4; what the four compute, added up, is the uncut reference's
    routed layer with all 16 experts: through the served programs' router
    and experts (both impls) and through the model's own layer.  The
    attention, which every chip computes alike, is outside the sum: it
    counts once."""
    from incubator_mxnet_tpu.models import routed_sparse as RS

    rng = onp.random.default_rng(11)
    D, Fe, E_all, held, K, T = 32, 16, 16, 4, 4, 24
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w = {"router_w": jnp.asarray(rng.normal(size=(E_all, D)), jnp.float32)}
    for name, shape in (("gate_e", (E_all, Fe, D)), ("up_e", (E_all, Fe, D)),
                        ("down_e", (E_all, D, Fe))):
        w[name] = jnp.asarray(0.3 * rng.normal(size=shape), jnp.float32)
    whole = dict(CFG, num_experts=E_all, experts_held="0-15")
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(ref.routed_ffn(x, w, whole, "fp32"))
        moe = G.MoeSpec(E_all, 0, held, K, Fe, "softmax")
        idx, wts = SP._route(moe, x, (w["router_w"], None))
        onp.testing.assert_allclose(onp.asarray(wts).sum(1), 1.0, atol=1e-6)
        ok = jnp.ones((T,), bool)
        for impl in ("xla", "pallas"):
            total = sum(onp.asarray(_moe.routed_experts(
                x, idx, wts, ok, *(w[n][f:f + held]
                                   for n in ("gate_e", "up_e", "down_e")),
                first=f, experts=E_all, impl=impl)[0])
                for f in range(0, E_all, held))
            onp.testing.assert_allclose(total, want, atol=2e-5)
        total = sum(onp.asarray(RS._routed(
            x, {"router": w["router_w"],
                **{n: w[n][f:f + held] for n in ("gate_e", "up_e",
                                                 "down_e")}}, K, f))
            for f in range(0, E_all, held))
        onp.testing.assert_allclose(total, want, atol=2e-5)
    assert onp.abs(want).max() > 0.1


def test_a_later_share_serves_its_own_experts(ref):
    net, w32, rcfg = _seeded(ref, held="8-11")
    assert G.decoder_spec(net).moe.first == 8
    prompt = _prompts((30,), seed=12)[0]
    with ServingEngine(net, **ENGINE) as eng:
        toks = onp.asarray(eng.submit(prompt, 6).result(timeout=600))
    seq = onp.concatenate([prompt, toks])
    assert _gap(_ref_logits(ref, w32, rcfg, seq), prompt, toks) < 1e-4


# --- what the engine reckons, counts and refuses ------------------------------ #
def test_a_block_counts_three_arrays_a_layer(seeded):
    """Every place that reckons a block's or a token's bytes counts the
    index pool: K and V rows of 2 heads of 16 and an index row of 128
    lanes (8 of key, padded to the device's tile), float32, 3 layers."""
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops.sparse_attention import index_row

    net, _, _ = seeded
    telemetry.enable()
    assert index_row(8) == index_row(64) == 128 and index_row(192) == 256
    with ServingEngine(net, **ENGINE) as eng:
        nb = 3 * 128 // 4 + 1
        pk, pv, _, _ = eng._programs.kv_pools
        assert len(eng._programs.index_pools) == 3
        assert eng._programs.index_pools[0].shape == (nb, 4, 128)
        assert pk[0].shape == pv[2].shape == (nb, 4, 32)
        assert eng.index_pool_bytes == 3 * nb * 4 * 128 * 4
        assert eng.kv_pool_bytes == 3 * nb * 4 * (32 + 32 + 128) * 4
        assert eng.kv_block_bytes == 3 * 4 * (32 + 32 + 128) * 4
        assert eng.kv_bytes_per_token == 3 * (32 + 32 + 128) * 4
        v = eng.varz_config()
        assert v["index_pool_bytes"] == eng.index_pool_bytes
        assert v["kv_pool_bytes"] == eng.kv_pool_bytes
        assert v["index_topk"] == TOPK and v["prefix_cache"] is False
        assert v["prog_label"] == "float_moe_idx"
        assert v["experts_held"] == 4 and v["experts_published"] == 16
        st = eng.stats()
        assert st["index_pool_bytes"] == eng.index_pool_bytes
        labels = {"engine": eng._name}
        assert telemetry.gauge("serving_index_pool_bytes",
                               labels=labels).value == eng.index_pool_bytes
        assert telemetry.gauge("serving_kv_bytes_per_token",
                               labels=labels).value == eng.kv_bytes_per_token


def test_the_index_counts_ride_with_the_tokens(seeded):
    """`index_positions_scored` and `sparse_positions_attended` of the
    ring, counted on the device and read with the step's tokens: every
    query (a prompt token, a decode step's token) scores its whole context
    and attends min(context, topk) of it, in each of the 3 layers."""
    from incubator_mxnet_tpu import telemetry

    net, _, _ = seeded
    prompts = _prompts((23, 40), seed=8)
    t0 = time.monotonic()
    with ServingEngine(net, **ENGINE) as eng:
        for h in [eng.submit(p, 9) for p in prompts]:
            h.result(timeout=600)
        assert eng.drain(timeout=600)
        name = eng._name
    records, _ = telemetry.profiler.iterations(t0, None)
    mine = [r for r in records if r.engine == name]
    contexts = [c for n in (23, 40) for c in range(1, n + 8 + 1)]
    assert sum(r.index_positions_scored for r in mine) == 3 * sum(contexts)
    assert sum(r.sparse_positions_attended for r in mine) == 3 * sum(
        min(c, TOPK) for c in contexts)
    assert sum(r.expert_tokens for r in mine) == 3 * len(contexts)
    assert "index_positions_scored" in mine[0].as_dict()


def test_the_runs_the_sparse_kernels_walk_are_counted(seeded):
    """`sparse_runs_walked` and `sparse_runs_table` in `stats()` and
    `/varz`: over a lane's chunks and steps, both kernels of each of the 3
    layers visit a sequence's live runs of 16 pages (64 positions; a
    table of 32 entries is two runs) once, where a grid over every run
    took two steps a call."""
    net, _, _ = seeded
    P, out = 70, 5
    with ServingEngine(net, attn_impl="pallas",
                       **dict(ENGINE, max_batch=1)) as eng:
        eng.submit(_prompts((P,), seed=9)[0], out).result(timeout=900)
        assert eng.drain(timeout=600)
        st, v = eng.stats(), eng.varz_config()
    # the chunks' last positions (the program's width, 16), then the steps'
    lasts = [s + 15 for s in range(0, P, 16)] + \
        [P + k for k in range(st["steps"])]
    assert st["steps"] == out - 1
    live = sum(min(p // 64 + 1, 2) for p in lasts)
    assert (st["sparse_runs_walked"], st["sparse_runs_table"]) == (
        3 * 2 * live, 3 * 2 * 2 * len(lasts))
    assert (v["sparse_runs_walked"], v["sparse_runs_table"]) == (
        st["sparse_runs_walked"], st["sparse_runs_table"])


def test_a_count_past_int32_is_carried_in_two_words():
    hi, lo = jnp.int32(0), jnp.int32(0)
    total = 0
    for x, times in ((2 ** 31 - 1, 48), (17_301_504, 8), (5, 1)):
        hi, lo = SP._add_wide(hi, lo, jnp.int32(x), times)
        total += x * times
    assert total > 2 ** 36 and int(lo) < 2 ** SP._WIDE
    assert SP.wide_counts([int(hi), int(lo), 0, 7]) == (total, 7)


def test_a_prefix_lookup_is_a_miss(seeded):
    """The same prompt twice: a prefix hit would hand over K's and V's
    blocks and knows of no index pool; the second is a miss too, and
    answers the same."""
    net, _, _ = seeded
    prompt = _prompts((33,))[0]
    with ServingEngine(net, **ENGINE) as eng:
        a = eng.submit(prompt, 5).result(timeout=600)
        b = eng.submit(prompt, 5).result(timeout=600)
        st = eng.stats()
        assert a == b
        assert st["prefix_cache"]["hits"] == 0
        assert st["prefix_cache"]["misses"] == 2


def test_speculation_and_int8_kv_are_refused(seeded):
    net, _, _ = seeded
    kw = dict(max_batch=2, block_size=4, max_seq_len=64)
    with pytest.raises(ValueError, match="an index over its pages"):
        ServingEngine(net, speculate_k=2, **kw)
    with pytest.raises(ValueError, match="an index over its pages"):
        ServingEngine(net, draft_net=net, **kw)
    with pytest.raises(ValueError, match="an index over its pages"):
        ServingEngine(net, kv_dtype="int8", **kw)


def test_an_engine_without_an_index_reports_none():
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.models.transformer import TransformerLM

    lm = TransformerLM(vocab=50, units=16, hidden_size=32, num_layers=1,
                       num_heads=2, max_len=64, dropout=0.0)
    lm.initialize()
    lm(NDArray(jnp.ones((1, 4), jnp.int32)))
    t0 = time.monotonic()
    with ServingEngine(lm, max_batch=2, block_size=8, max_seq_len=64) as eng:
        eng.submit(onp.arange(5, dtype=onp.int32), 4).result(timeout=300)
        v = eng.varz_config()
        assert (v["index_pool_bytes"], v["index_topk"]) == (0, 0)
        assert v["index_select"] == "none"
        assert (v["sparse_runs_walked"], v["sparse_runs_table"]) == (0, 0)
        assert eng.stats()["sparse_runs_walked"] == 0
        assert eng.stats()["sparse_runs_table"] == 0
        assert eng.index_pool_bytes == 0 and eng._programs.index_pools == ()
        name = eng._name
    records, _ = telemetry.profiler.iterations(t0, None)
    assert all(r.index_positions_scored == r.sparse_positions_attended == 0
               for r in records if r.engine == name)
