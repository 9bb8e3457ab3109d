"""A decoder of full and sliding-window attention layers with routed
feed-forwards (`models/routed_window.py`) against its own eager forward and
against the plain reference (`perf/reference/mimo-v2-flash.py`, which
imports nothing of the program), on seeded weights at a tiny size: the
published pattern of one period (full, window x4, full, window; dense, then
routed), 8 query heads on 2 and 4 KV heads, keys 12 and values 8 wide, a
window of 10, 8 experts held of 32, top-4.

Whole-sequence forward; prefill in chunks and decoding through
`ServingEngine`, through both kinds of pool (block tables for the full
layers, a ring a lane for the window layers: docs/serving.md, "Window
layers and routed experts"); the chip's share of the experts tied to the
whole layer; what the engine counts and what it refuses.
"""
import importlib
import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu.models import generation as G
from incubator_mxnet_tpu.models.routed_window import RoutedWindowDecoder
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.serving import ServingEngine
from incubator_mxnet_tpu.serving import programs as SP

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_moe = importlib.import_module("incubator_mxnet_tpu.ops.moe_experts")

CFG = dict(vocab_size=97, hidden_size=32, intermediate_size=64,
           num_hidden_layers=7, num_attention_heads=8, num_key_value_heads=2,
           swa_num_key_value_heads=4, head_dim=12, v_head_dim=8,
           hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
           moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=10,
           moe_intermediate_size=16, n_routed_experts=8,
           n_routed_experts_published=32, num_experts_per_tok=4,
           rope_theta=5000000.0, swa_rope_theta=10000.0,
           partial_rotary_factor=0.334, attention_value_scale=0.707,
           add_swa_attention_sink_bias=True, layernorm_epsilon=1e-5,
           max_position_embeddings=128)
ENGINE = dict(max_batch=3, block_size=4, max_seq_len=128, prefill_chunk=16)


@pytest.fixture(scope="module")
def ref():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "perf_reference_mimo_v2_flash",
        os.path.join(ROOT, "perf", "reference", "mimo-v2-flash.py"))
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


def _seeded(ref, cfg=CFG, held="0-7", dtype="float32", seed=5, std=0.2):
    """(net, the reference's float32 leaves, the reference's cfg): the
    benchmark's own weights from the seed (wider than its 0.02, so that
    every path moves the logits at this size)."""
    from perf import weights

    rcfg = dict(cfg, experts_held=held)
    kw = dict(cfg, first_expert=int(held.split("-")[0]))
    net = RoutedWindowDecoder(dtype=dtype, **kw)
    net.initialize()
    shapes = ref.param_shapes(rcfg)
    w = weights.make(seed, shapes, std=std)
    weights.assign(weights.leaves(net, _param_map(shapes), w), w)
    return net, {k: v.astype(jnp.float32) for k, v in w.items()}, rcfg


@pytest.fixture(scope="module")
def seeded(ref):
    return _seeded(ref)


def _ref_logits(ref, w32, rcfg, seq):
    with jax.default_matmul_precision("highest"):
        return onp.asarray(ref.logits(w32, jnp.asarray(seq)[None], rcfg)[0])


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
    assert spec.kinds == ("attn",) * 7 and not spec.recurrent
    assert spec.acts == ("silu_gated",) + ("routed",) * 6
    assert [a.kv_heads for a in spec.attn] == [2, 4, 4, 4, 4, 2, 4]
    assert [a.window for a in spec.attn] == [0, 10, 10, 10, 10, 0, 10]
    assert [a.sink for a in spec.attn] == [False] + [True] * 4 + [False, True]
    assert spec.attn[0].rope_base == 5e6 and spec.attn[1].rope_base == 1e4
    assert (spec.heads, spec.head_dim, spec.v_dim) == (8, 12, 8)
    assert spec.rope_dim == 4           # 0.334 x 12, to the even number
    assert spec.value_scale == 0.707 and spec.window == 10
    assert spec.moe == G.MoeSpec(32, 0, 8, 4, 16)
    assert spec.carried
    layers = G._gather_params(net, 128)["layers"]
    assert set(layers[0]) == {"ln1", "ln2", "q", "k", "v", "proj",
                              "ffn_gate", "ffn1", "ffn2"}
    assert set(layers[1]) == {"ln1", "ln2", "q", "k", "v", "proj", "sink",
                              "router", "experts"}
    # nothing is copied: a layer's leaves are the net's own buffers
    assert layers[3]["experts"][0] is net.gate_e3.data()._data
    assert layers[5]["k"][0].shape == (2 * 12, 32)
    assert layers[6]["k"][0].shape == (4 * 12, 32)


def test_grad_req_null_holds_no_gradient_buffers():
    net = RoutedWindowDecoder(dtype="bfloat16", grad_req="null", **CFG)
    net.initialize()
    for name, p in net.collect_params().items():
        assert p.data()._grad is None, name
        assert p.data()._data.dtype == jnp.bfloat16, name


def test_the_patterns_must_cover_every_layer():
    with pytest.raises(ValueError, match="one entry"):
        RoutedWindowDecoder(**dict(CFG, moe_layer_freq=[0, 1]))
    with pytest.raises(ValueError, match="not among"):
        RoutedWindowDecoder(**dict(CFG, first_expert=30))


# --- whole-sequence forward ------------------------------------------------ #
def test_forward_matches_the_reference(seeded, ref):
    net, w32, rcfg = seeded
    toks = onp.stack(_prompts((40, 40)))
    got = onp.asarray(net(NDArray(jnp.asarray(toks)))._data)
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(ref.logits(w32, jnp.asarray(toks), rcfg))
    onp.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


@pytest.mark.parametrize("drop", ["sink", "router_bias", "value_scale",
                                  "window", "rope"])
def test_every_assumed_piece_moves_the_reference(seeded, ref, drop):
    """A path that drops the sink logit, the selection bias, the value
    scale, the window or the rotary turn does not pass for the model: the
    reference's logits move by more than any tolerance here."""
    _, w32, rcfg = seeded
    seq = _prompts((40,))[0]
    want = _ref_logits(ref, w32, rcfg, seq)
    w, cfg = dict(w32), dict(rcfg)
    if drop in ("sink", "router_bias"):
        for k in w:
            if k.endswith(drop):
                w[k] = w[k] + 2.0 * (-1.0) ** jnp.arange(w[k].shape[0])
    elif drop == "value_scale":
        cfg["attention_value_scale"] = 1.0
    elif drop == "window":
        cfg["sliding_window"] = 128
    else:
        cfg["rope_theta"] = cfg["swa_rope_theta"] = 1.0 + 1e-9
    assert onp.abs(_ref_logits(ref, w, cfg, seq) - want).max() > 1e-2


# --- through the engine ---------------------------------------------------- #
@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_engine_matches_the_forward_and_the_reference(seeded, ref, impl):
    """Prefill in chunks of 16 (longer than the window of 10), then 30
    decode steps, four requests over three lanes, prompts of up to 50
    tokens: far past window + 2 blocks of 4, so every window layer's ring
    has gone round many times and a reused lane starts behind another's
    pages.  Every served token is the best of the model's own eager
    forward and of the plain reference at its position (float32 on both
    sides: a gap within the logits' roundoff)."""
    net, w32, rcfg = seeded
    prompts = _prompts((37, 5, 19, 50))
    with ServingEngine(net, attn_impl=impl, **ENGINE) as eng:
        handles = [eng.submit(p, 30) for p in prompts]
        served = [onp.asarray(h.result(timeout=600), onp.int32)
                  for h in handles]
    for p, s in zip(prompts, served):
        assert len(s) == 30
        seq = onp.concatenate([p, s])
        own = onp.asarray(net(NDArray(jnp.asarray(seq)[None]))._data[0])
        assert _gap(own, p, s) < 1e-4
        assert _gap(_ref_logits(ref, w32, rcfg, seq), p, s) < 1e-4


def test_the_result_does_not_depend_on_the_chunking(seeded):
    """Chunks of 4 (shorter than the window: the ring keeps what earlier
    chunks wrote), of 16 and of 64 (the whole prompt at once) serve the
    same tokens."""
    net, _, _ = seeded
    prompt = _prompts((45,), seed=3)[0]
    served = []
    for chunk in (4, 16, 64):
        with ServingEngine(net, **dict(ENGINE, prefill_chunk=chunk)) as eng:
            served.append(eng.submit(prompt, 12).result(timeout=600))
    assert served[0] == served[1] == served[2]


def test_engine_in_bfloat16_stays_within_its_rounding(ref):
    net, w32, rcfg = _seeded(ref, dtype="bfloat16", seed=7, std=0.1)
    prompts = _prompts((33, 21), seed=2)
    with ServingEngine(net, **ENGINE) as eng:
        served = [onp.asarray(eng.submit(p, 12).result(timeout=600),
                              onp.int32) for p in prompts]
    for p, s in zip(prompts, served):
        lg = _ref_logits(ref, w32, rcfg, onp.concatenate([p, s]))
        assert _gap(lg, p, s) < 0.5


# --- the share ties to the model ------------------------------------------- #
def test_the_shares_add_up_to_the_whole_layer(ref):
    """Four chips hold 8 of 32 experts each.  Every one routes over all 32
    and computes the pairs of its own 8; what the four compute, added up,
    is the uncut reference's routed layer with all 32 experts: through the
    served programs' router and experts (both impls) and through the
    model's own layer."""
    from incubator_mxnet_tpu.models import routed_window as RW

    rng = onp.random.default_rng(11)
    D, Fe, E_all, held, K, T = 32, 16, 32, 8, 4, 24
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w = {"router_w": jnp.asarray(rng.normal(size=(E_all, D)), jnp.float32),
         "router_bias": jnp.asarray(0.3 * rng.normal(size=(E_all,)),
                                    jnp.float32),
         "gate_e": jnp.asarray(0.3 * rng.normal(size=(E_all, Fe, D)),
                               jnp.float32),
         "up_e": jnp.asarray(0.3 * rng.normal(size=(E_all, Fe, D)),
                             jnp.float32),
         "down_e": jnp.asarray(0.3 * rng.normal(size=(E_all, D, Fe)),
                               jnp.float32)}
    whole = dict(CFG, n_routed_experts=E_all, experts_held="0-31")
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(ref.routed_ffn(x, w, whole, "fp32"))
        moe = G.MoeSpec(E_all, 0, held, K, Fe)
        idx, wts = SP._route(moe, x, (w["router_w"], w["router_bias"]))
        ok = jnp.ones((T,), bool)
        for impl in ("xla", "pallas"):
            total = sum(onp.asarray(_moe.routed_experts(
                x, idx, wts, ok, *(w[n][f:f + held]
                                   for n in ("gate_e", "up_e", "down_e")),
                first=f, experts=E_all, impl=impl)[0], onp.float64)
                for f in range(0, E_all, held))
            onp.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
        own = sum(onp.asarray(RW._routed(
            x, {"router": w["router_w"], "router_bias": w["router_bias"],
                **{n: w[n][f:f + held]
                   for n in ("gate_e", "up_e", "down_e")}}, K, f),
            onp.float64) for f in range(0, E_all, held))
        onp.testing.assert_allclose(own, want, atol=2e-5, rtol=0)
        # and one share alone is not the layer
        assert onp.abs(onp.asarray(ref.routed_ffn(
            x, {**w, **{n: w[n][:held] for n in ("gate_e", "up_e",
                                                  "down_e")}},
            dict(CFG, experts_held="0-7"), "fp32")) - want).max() > 1e-2


def test_a_later_share_serves_its_own_experts(ref):
    """A net that holds experts 8-15 of 32, through the engine, against
    the reference given the same share."""
    net, w32, rcfg = _seeded(ref, held="8-15", seed=9)
    assert G.decoder_spec(net).moe.first == 8
    p = _prompts((29,), seed=4)[0]
    with ServingEngine(net, **ENGINE) as eng:
        s = onp.asarray(eng.submit(p, 10).result(timeout=600), onp.int32)
    assert _gap(_ref_logits(ref, w32, rcfg, onp.concatenate([p, s])),
                p, s) < 1e-4


# --- the window layers' rings ----------------------------------------------- #
def test_pages_a_grid_step_follow_both_rows_widths():
    """What `/varz` reports for the served cell is the kernel's own rule:
    the mean of the key row (4 x 192) and the value row (4 x 128) in
    bfloat16, 12 pages of 64 a grid step; a ring's 3 entries are one run."""
    from incubator_mxnet_tpu.ops.paged_attention import pages_per_step

    assert pages_per_step(64, 144, (768 + 512) * 2 // 2) == 12
    assert pages_per_step(64, 3, (1536 + 1024) * 2 // 2) == 3


def test_a_ring_holds_what_the_window_can_reach():
    assert SP.ring_blocks(128, 64) == 3     # the served cell's
    assert SP.ring_blocks(10, 4) == 4
    for W, bs in ((128, 64), (10, 4), (129, 64), (130, 64), (2, 16),
                  (64, 64), (65, 64)):
        n = SP.ring_blocks(W, bs)
        for t in range(0, 5 * bs + W):
            lo = max(t - W + 1, 0)
            seen = set(range(lo // bs, t // bs + 1))
            assert len(seen) <= n
            # the block written at t takes no visible block's place
            assert all(b % n != (t // bs) % n or b == t // bs for b in seen)


def test_window_pool_bytes_do_not_depend_on_max_seq_len(seeded):
    net, _, _ = seeded
    sizes = []
    for msl in (64, 128):
        with ServingEngine(net, **dict(ENGINE, max_seq_len=msl)) as eng:
            sizes.append((eng.window_pool_bytes, eng.kv_pool_bytes))
            n = eng._programs.window_blocks
            assert n == 4
            # 5 window layers, 3 lanes x 4 blocks + scratch, 4 positions,
            # 4 KV heads of 12 + 8, float32
            assert eng.window_pool_bytes == 5 * (3 * n + 1) * 4 * 4 * 20 * 4
            pk, pv, _, _ = eng._programs.kv_pools
            assert pk[1].shape == (3 * n + 1, 4, 48)
            assert pv[1].shape == (3 * n + 1, 4, 32)
            assert pk[0].shape == (3 * msl // 4 + 1, 4, 24)
            assert pv[5].shape == (3 * msl // 4 + 1, 4, 16)
    assert sizes[0][0] == sizes[1][0]
    assert sizes[0][1] < sizes[1][1]


def test_window_blocks_held_stay_under_the_constant(seeded):
    """The ring's fields of the ledger: the window layers never hold more
    than `window_blocks` a lane whatever the lengths, the block tables'
    pool is reserved by length as ever, and a lane that ends gives both
    back."""
    from incubator_mxnet_tpu import telemetry

    net, _, _ = seeded
    t0 = time.monotonic()
    with ServingEngine(net, **ENGINE) as eng:
        total = eng.stats()["blocks_total"]
        for p in _prompts((50, 44, 31, 9), seed=6):
            eng.submit(p, 25)
        assert eng.drain(timeout=600)
        st = eng.stats()
        assert st["blocks_free"] == st["blocks_total"] == total
        assert eng._pool_use_locked()["window_blocks_held"] == 0
        name = eng._name
    records, _ = telemetry.profiler.iterations(t0, None)
    mine = [r for r in records if r.engine == name]
    assert mine and all(r.window_blocks_total == 3 * 4 for r in mine)
    assert all(r.window_blocks_held <= 4 * r.occupancy + 4 for r in mine)
    assert max(r.window_blocks_held for r in mine) >= 3 * 3
    # full lanes hold 75 positions: 19 blocks of 4 each in the tables'
    # pool, which the rings never touch
    assert max(r.blocks_reserved for r in mine) >= 19 + 18
    assert all(r.blocks_total == total for r in mine)
    assert "window_blocks_held" in mine[0].as_dict()


def test_a_prefix_lookup_is_a_miss(seeded):
    """The same prompt twice: the full layers' blocks could be shared, the
    window layers' rings have long given the prefix's pages away; so the
    second is a miss too, counted as one, and answers the same."""
    net, _, _ = seeded
    prompt = _prompts((33,))[0]
    with ServingEngine(net, **ENGINE) as eng:
        a = eng.submit(prompt, 5).result(timeout=600)
        b = eng.submit(prompt, 5).result(timeout=600)
        st = eng.stats()
        assert a == b
        assert st["prefix_cache"]["hits"] == 0
        assert st["prefix_cache"]["misses"] == 2
        assert st["prefix_cache"]["cached_tokens"] == 0
        assert eng.varz_config()["prefix_cache"] is False


def test_speculation_and_int8_kv_are_refused(seeded):
    net, _, _ = seeded
    kw = dict(max_batch=2, block_size=4, max_seq_len=64)
    with pytest.raises(ValueError, match="window layers or routed"):
        ServingEngine(net, speculate_k=2, **kw)
    with pytest.raises(ValueError, match="window layers or routed"):
        ServingEngine(net, draft_net=net, **kw)
    with pytest.raises(ValueError, match="window layers or routed"):
        ServingEngine(net, kv_dtype="int8", **kw)


# --- what is counted --------------------------------------------------------- #
def test_the_experts_counts_ride_with_the_tokens(seeded):
    """`expert_pairs`, `expert_tokens` and `expert_busiest` of the ring:
    counted on the device, read with the step's tokens (no step is handed
    over behind a read: `steps_ahead` is what it is without them).  Every
    token routed is a prompt or decode token times the 6 routed layers;
    a pair is a token's choice of an expert held here, at most 4 a token
    and layer."""
    from incubator_mxnet_tpu import telemetry

    net, _, _ = seeded
    prompts = _prompts((23, 40), seed=8)
    t0 = time.monotonic()
    with ServingEngine(net, **ENGINE) as eng:
        hs = [eng.submit(p, 9) for p in prompts]
        for h in hs:
            h.result(timeout=600)
        assert eng.drain(timeout=600)
        st = eng.stats()
        name = eng._name
    records, _ = telemetry.profiler.iterations(t0, None)
    mine = [r for r in records if r.engine == name]
    tokens = sum(r.expert_tokens for r in mine)
    pairs = sum(r.expert_pairs for r in mine)
    # every prompt token, and every decode step's token (the last output
    # of a request needs no step)
    assert tokens == 6 * (23 + 40 + 2 * 8)
    assert 0 < pairs <= 4 * tokens
    assert all(r.expert_busiest <= r.expert_pairs for r in mine)
    assert max(r.expert_busiest for r in mine) >= 1
    assert st["steps"] == len(mine)
    assert "expert_pairs" in mine[0].as_dict()


def test_varz_stats_and_gauges_name_the_share(seeded):
    from incubator_mxnet_tpu import telemetry

    net, _, _ = seeded
    telemetry.enable()
    with ServingEngine(net, **ENGINE) as eng:
        v = eng.varz_config()
        assert v["experts_held"] == 8 and v["experts_published"] == 32
        assert v["attention_window"] == 10
        assert v["window_blocks_per_lane"] == 4
        assert v["window_pool_bytes"] == eng.window_pool_bytes > 0
        assert v["prog_label"] == "float_win_moe"
        assert v["paged_pages_per_step"] == 0       # the dense path here
        st = eng.stats()
        assert st["window_pool_bytes"] == eng.window_pool_bytes
        assert st["kv_pool_bytes"] == eng.kv_pool_bytes
        assert eng.state_bytes == 0
        labels = {"engine": eng._name}
        assert telemetry.gauge("serving_window_pool_bytes",
                               labels=labels).value == eng.window_pool_bytes
        assert telemetry.gauge("serving_experts_held",
                               labels=labels).value == 8
        # the tables' pool alone: 2 full layers of 2 KV heads, keys 12 and
        # values 8 wide, float32
        assert eng.kv_bytes_per_token == 2 * 2 * (12 + 8) * 4


def test_an_engine_without_either_reports_neither():
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
        assert (v["experts_held"], v["experts_published"],
                v["attention_window"], v["window_pool_bytes"]) == (0, 0, 0, 0)
        assert eng.stats()["window_pool_bytes"] == 0
        name = eng._name
    records, _ = telemetry.profiler.iterations(t0, None)
    assert all(r.expert_pairs == r.expert_tokens == r.window_blocks_total
               == 0 for r in records if r.engine == name)
