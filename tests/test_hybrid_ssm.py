"""A decoder of Mamba and attention layers (`models/hybrid_ssm.py`) against
its plain reference (`perf/reference/jamba2-3b.py`, which imports nothing
of the program), on seeded weights at a tiny size: two periods of a 4-layer
pattern, 4 query heads on 1 KV head, d_state 4.

Whole-sequence forward, prefill-then-decode through `ServingEngine`, and
what the engine promises of the second kind of per-sequence state
(docs/serving.md, "Two kinds of state"): it does not depend on how a
prompt was chunked, co-batched lanes do not touch each other, a reused lane
starts from zero, a chunk's padded tail advances nothing.
"""
import importlib
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.models import generation as G
from incubator_mxnet_tpu.models.hybrid_ssm import HybridSSMDecoder
from incubator_mxnet_tpu.models.transformer import TransformerLM
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.serving import ServingEngine
from incubator_mxnet_tpu.serving import programs as SP

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_scan = importlib.import_module("incubator_mxnet_tpu.ops.selective_scan")

CFG = dict(vocab_size=97, hidden_size=32, intermediate_size=64,
           num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=1,
           attn_layer_period=4, attn_layer_offset=2, mamba_d_state=4,
           mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=6,
           mamba_conv_bias=True, mamba_proj_bias=False, rms_norm_eps=1e-6,
           max_position_embeddings=64, tie_word_embeddings=True)


@pytest.fixture(scope="module")
def ref():
    import sys

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "perf_reference_jamba2_3b",
        os.path.join(ROOT, "perf", "reference", "jamba2-3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _seeded(ref, dtype="float32", seed=5):
    """(net, the reference's float32 leaves): the benchmark's own weights
    from the seed, handed over through the configuration's `param_map`."""
    from perf import weights

    with open(os.path.join(ROOT, "perf", "configs", "jamba2-3b.json")) as f:
        param_map = json.load(f)["program"]["param_map"]
    net = HybridSSMDecoder(dtype=dtype, **CFG)
    net.initialize()
    w = weights.make(seed, ref.param_shapes(CFG))
    weights.assign(weights.leaves(net, param_map, w), w)
    return net, {k: v.astype(jnp.float32) for k, v in w.items()}


@pytest.fixture(scope="module")
def seeded(ref):
    return _seeded(ref)


def _ref_logits(ref, w32, seq):
    with jax.default_matmul_precision("highest"):
        return onp.asarray(ref.logits(w32, jnp.asarray(seq)[None], CFG)[0])


def _gap(ref, w32, prompt, served):
    """How far the served tokens' logits lie below the reference's best."""
    lg = _ref_logits(ref, w32, onp.concatenate([prompt, served]))
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(served)]
    return float(max(r.max() - r[t] for r, t in zip(rows, served)))


def _prompts(ns, seed=1):
    rng = onp.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).astype(onp.int32)
            for n in ns]


# --- the description ------------------------------------------------------ #
def test_layer_kinds_follow_the_published_rule(seeded, ref):
    net, _ = seeded
    spec = G.decoder_spec(net)
    assert list(spec.kinds) == ref.layer_kinds(CFG)
    assert spec.kinds.count("attn") == 2 and spec.recurrent
    assert (spec.heads, spec.kv_heads, spec.head_dim) == (4, 1, 8)
    assert spec.ssm == G.SsmSpec(64, 4, 4, 6)
    assert not spec.positions and spec.norm == "rms"
    # one stacked leaf a kind of weight: a row a layer of its kind, in
    # depth order, and the gathered pytree reads as a dict a layer
    assert net.gate_w.shape[0] == 8 and net.in_proj_w.shape[0] == 6
    assert net.q_w.shape[0] == 2
    layers = G._gather_params(net, 64)["layers"]
    assert len(layers) == 8 and len(jax.tree_util.tree_leaves(layers)) == 19
    assert "qkv" in layers[6] and "in_proj" in layers[3]
    onp.testing.assert_array_equal(
        layers[3]["in_proj"][0], net.in_proj_w.data()._data[2])
    onp.testing.assert_array_equal(
        layers[6]["qkv"][0][:32], net.q_w.data()._data[1])


def test_transformer_lm_is_served_through_the_same_description():
    net = TransformerLM(vocab=50, units=16, hidden_size=32, num_layers=2,
                        num_heads=4, max_len=64, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    spec = G.decoder_spec(net)
    assert spec.kinds == ("attn", "attn") and not spec.recurrent
    assert spec.heads == spec.kv_heads == 4 and spec.positions
    assert spec.norm == "layer" and spec.ssm is None
    assert set(G._gather_params(net, 64)["layers"][0]) == {
        "ln1", "qkv", "proj", "ln2", "ffn1", "ffn2"}
    with pytest.raises(TypeError):
        G.decoder_spec(object())


# --- whole-sequence forward ------------------------------------------------ #
def test_forward_matches_the_reference(seeded, ref):
    net, w32 = seeded
    toks = onp.stack(_prompts((12, 12)))
    got = onp.asarray(net(NDArray(jnp.asarray(toks)))._data)
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(ref.logits(w32, jnp.asarray(toks), CFG))
    onp.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_backward_fills_every_gradient(ref):
    net, _ = _seeded(ref, seed=6)
    toks = NDArray(jnp.asarray(onp.stack(_prompts((9,)))))
    with mx.autograd.record():
        loss = (net(toks) ** 2).mean()
    loss.backward()
    for name, p in net.collect_params().items():
        g = onp.asarray(p.grad()._data)
        assert onp.isfinite(g).all() and onp.abs(g).max() > 0, name


def test_grad_req_null_holds_no_gradient_buffers():
    net = HybridSSMDecoder(dtype="bfloat16", grad_req="null", **CFG)
    net.initialize()
    for name, p in net.collect_params().items():
        assert p.data()._grad is None, name
        assert p.data()._data.dtype == jnp.bfloat16, name


# --- through the engine ---------------------------------------------------- #
@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_engine_matches_the_reference(seeded, ref, impl):
    """Prefill in chunks, then decode, three requests co-batched: every
    served token is the reference's best at its position (float32 both
    sides: a gap within roundoff of the logits, 1e-4)."""
    net, w32 = seeded
    prompts = _prompts((11, 5, 19))
    with ServingEngine(net, max_batch=4, block_size=8, max_seq_len=64,
                       prefill_chunk=16, attn_impl=impl) as eng:
        handles = [eng.submit(p, 9) for p in prompts]
        served = [onp.asarray(h.result(timeout=300), onp.int32)
                  for h in handles]
    for p, s in zip(prompts, served):
        assert len(s) == 9
        assert _gap(ref, w32, p, s) < 1e-4


def test_engine_in_bfloat16_stays_within_its_rounding(ref):
    """bfloat16 weights AND activations (the benchmark's configuration):
    logits here are below 1 in size and bf16 keeps 8 bits, so a served
    token may lie below the float32 reference's best by a few 2^-8 of
    that; 0.05 is ten such steps and a twentieth of the spread between
    the logits of one position."""
    net, w32 = _seeded(ref, dtype="bfloat16")
    prompts = _prompts((13, 21), seed=3)
    with ServingEngine(net, max_batch=2, block_size=8, max_seq_len=64,
                       prefill_chunk=8) as eng:
        served = [onp.asarray(eng.submit(p, 8).result(timeout=300), onp.int32)
                  for p in prompts]
    for p, s in zip(prompts, served):
        assert _gap(ref, w32, p, s) < 0.05


def _state_of(eng, lane):
    states, convs = eng._programs.recurrent_state
    return ([onp.asarray(s[lane]) for s in states],
            [onp.asarray(c[:, lane]) for c in convs])


def test_state_after_a_prompt_does_not_depend_on_the_chunking(seeded):
    """Chunks of 1, 3, 8 and the whole prompt leave the same recurrent
    state, conv window and first token (float32 roundoff: the scan walks
    the tokens in the same order, the matmuls before it tile otherwise)."""
    net, _ = seeded
    prompt = _prompts((23,))[0]
    seen = []
    for chunk in (1, 3, 8, 32):
        with ServingEngine(net, max_batch=2, block_size=8, max_seq_len=64,
                           prefill_chunk=chunk) as eng:
            h = eng.submit(prompt, 1)       # the last chunk's token, no step
            tok = h.result(timeout=300)
            seen.append((tok, *_state_of(eng, 0)))
    tok0, states0, convs0 = seen[0]
    assert any(onp.abs(s).max() > 1e-3 for s in states0)
    for tok, states, convs in seen[1:]:
        assert tok == tok0
        for a, b in zip(states + convs, states0 + convs0):
            onp.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-5)


def test_cobatched_lanes_do_not_touch_each_other(seeded):
    """A request decoded alone and among 3 others: identical tokens,
    identical state."""
    net, _ = seeded
    mine, others = _prompts((14,))[0], _prompts((7, 22, 10), seed=9)
    kw = dict(max_batch=4, block_size=8, max_seq_len=64, prefill_chunk=8)
    with ServingEngine(net, **kw) as eng:
        alone = eng.submit(mine, 12).result(timeout=300)
        state_alone = _state_of(eng, 0)
    with ServingEngine(net, **kw) as eng:
        h = eng.submit(mine, 12)
        rest = [eng.submit(p, n) for p, n in zip(others, (5, 20, 12))]
        among = h.result(timeout=300)
        for r in rest:
            r.result(timeout=300)
        state_among = _state_of(eng, 0)
    assert among == alone
    for a, b in zip(state_alone[0] + state_alone[1],
                    state_among[0] + state_among[1]):
        onp.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("how", ["finish", "cancel", "evict", "eos"])
def test_a_reused_lane_starts_from_zero_state(seeded, how):
    """Whatever a lane's last request left in its row — it finished, was
    cancelled mid-decode, ran into its deadline, or met an EOS and had its
    row advanced once more by the step already handed over (the decode
    loop learns an EOS one step late) — the next request's first chunk
    starts from zero: its tokens are those of a fresh engine.  Nothing is
    cleared at release; the first chunk does it."""
    net, _ = seeded
    first, second = _prompts((18, 12), seed=4)
    kw = dict(max_batch=1, block_size=8, max_seq_len=64, prefill_chunk=8)
    with ServingEngine(net, **kw) as eng:
        fresh = eng.submit(second, 10).result(timeout=300)
    with ServingEngine(net, **kw) as eng:
        if how == "finish":
            eng.submit(first, 6).result(timeout=300)
        elif how == "eos":
            eng.set_fault_hook(lambda phase: time.sleep(0.02))
            h = eng.submit(first, 40)
            while len(h.tokens) < 3:
                time.sleep(0.01)
            # (this net repeats a token: the next step read ends on it,
            # with the step after it already handed over)
            eng._eos = h.tokens[-1]
            got = h.result(timeout=300)
            assert h.status == "done" and 3 <= len(got) < 40
            assert eng.drain(timeout=60)        # the step past it has landed
            assert len(h.tokens) == len(got) and eng.stats()["steps_ahead"]
            eng._eos = -1
            eng.set_fault_hook(None)
        else:
            eng.set_fault_hook(lambda phase: time.sleep(0.02))
            h = eng.submit(first, 40,
                           deadline=0.3 if how == "evict" else None)
            while len(h.tokens) < 3:
                time.sleep(0.01)
            if how == "cancel":
                h.cancel()
            with pytest.raises(Exception):
                h.result(timeout=300)
            assert h.status == ("cancelled" if how == "cancel" else "evicted")
            eng.set_fault_hook(None)
        dirty = _state_of(eng, 0)
        assert any(onp.abs(s).max() > 1e-3 for s in dirty[0])
        assert eng.submit(second, 10).result(timeout=300) == fresh
        assert eng.stats()["state_bytes"] == eng.state_bytes > 0


def test_a_chunks_padded_tail_leaves_state_and_window_unchanged(seeded):
    """The mixer over 8 positions of which 5 are valid leaves the lane's
    state and conv window as the mixer over those 5 alone does; the other
    lanes' rows are not touched; `fresh` starts from zero whatever the row
    held."""
    net, _ = seeded
    spec = G.decoder_spec(net)
    lp = G._gather_params(net, 64)["layers"][0]
    Di, Ds, K, _ = spec.ssm
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k[0], (1, 8, CFG["hidden_size"]))
    conv = jax.random.normal(k[1], (K - 1, 3, Di))
    state = jax.random.normal(k[2], (3, Ds, Di))
    lane = jnp.int32(1)

    def run(x, n_valid, fresh):
        ok = (jnp.arange(x.shape[1]) < n_valid)[None]
        return SP._ssm_mixer(spec, lp, x, conv, state, lane,
                             jnp.bool_(fresh), ok, "dense")

    for fresh in (False, True):
        out8, conv8, state8 = run(x, 5, fresh)
        out5, conv5, state5 = run(x[:, :5], 5, fresh)
        onp.testing.assert_allclose(out8[:, :5], out5, atol=1e-6)
        onp.testing.assert_allclose(conv8, conv5, atol=1e-6)
        onp.testing.assert_allclose(state8, state5, atol=1e-6)
        for other in (0, 2):
            onp.testing.assert_array_equal(conv8[:, other], conv[:, other])
            onp.testing.assert_array_equal(state8[other], state[other])
    zero = SP._ssm_mixer(spec, lp, x, jnp.zeros_like(conv),
                         jnp.zeros_like(state), lane, jnp.bool_(False),
                         jnp.ones((1, 8), bool), "dense")
    onp.testing.assert_allclose(run(x, 8, True)[2][1], zero[2][1], atol=1e-6)


def test_the_step_leaves_inactive_lanes_alone(seeded):
    """The step's form over 3 lanes of which one is live: the other rows
    of state and window keep their values to the bit."""
    net, _ = seeded
    spec = G.decoder_spec(net)
    lp = G._gather_params(net, 64)["layers"][1]
    Di, Ds, K, _ = spec.ssm
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (3, 1, CFG["hidden_size"]))
    conv = jax.random.normal(k[1], (K - 1, 3, Di))
    state = jax.random.normal(k[2], (3, Ds, Di))
    ok = jnp.array([[False], [True], [False]])
    for impl in ("dense", "pallas"):
        _, conv1, state1 = SP._ssm_mixer(spec, lp, x, conv, state, None,
                                         None, ok, impl)
        for lane in (0, 2):
            onp.testing.assert_array_equal(conv1[:, lane], conv[:, lane])
            onp.testing.assert_array_equal(state1[lane], state[lane])
        assert onp.abs(onp.asarray(state1[1] - state[1])).max() > 1e-3
        onp.testing.assert_array_equal(conv1[:-1, 1], conv[1:, 1])


# --- what is refused, what is counted --------------------------------------- #
def test_speculation_and_int8_kv_are_refused(seeded):
    net, _ = seeded
    draft = HybridSSMDecoder(**dict(CFG, num_hidden_layers=4))
    draft.initialize()
    kw = dict(max_batch=2, block_size=8, max_seq_len=64)
    with pytest.raises(ValueError, match="recurrent"):
        ServingEngine(net, speculate_k=2, **kw)
    with pytest.raises(ValueError, match="recurrent"):
        ServingEngine(net, draft_net=draft, **kw)
    with pytest.raises(ValueError, match="recurrent"):
        ServingEngine(net, kv_dtype="int8", **kw)
    lm = TransformerLM(vocab=CFG["vocab_size"], units=16, hidden_size=32,
                       num_layers=1, num_heads=2, max_len=64, dropout=0.0)
    lm.initialize()
    lm(NDArray(jnp.ones((1, 4), jnp.int32)))
    with pytest.raises(ValueError, match="recurrent"):
        ServingEngine(lm, speculate_k=2, draft_net=draft, **kw)


def test_a_prefix_lookup_is_a_miss(seeded):
    """The same prompt twice: K/V blocks of the first could be shared, the
    recurrent state after them is nowhere; so the second is a miss too,
    counted as one, prefills whole and answers the same."""
    net, _ = seeded
    prompt = _prompts((33,))[0]
    with ServingEngine(net, max_batch=2, block_size=8, max_seq_len=64,
                       prefill_chunk=16) as eng:
        a = eng.submit(prompt, 5).result(timeout=300)
        b = eng.submit(prompt, 5).result(timeout=300)
        st = eng.stats()
        assert a == b
        assert st["prefix_cache"]["hits"] == 0
        assert st["prefix_cache"]["misses"] == 2
        assert st["prefix_cache"]["cached_tokens"] == 0
        assert eng.varz_config()["prefix_cache"] is False


def test_state_is_counted_beside_the_pool(seeded):
    from incubator_mxnet_tpu import telemetry

    net, _ = seeded
    telemetry.enable()
    resets0 = telemetry.counter("serving_state_resets_total").value
    t0 = time.monotonic()
    with ServingEngine(net, max_batch=2, block_size=8, max_seq_len=64,
                       prefill_chunk=8) as eng:
        for p in _prompts((20, 9)):
            eng.submit(p, 6)
        assert eng.drain(timeout=300)
        Di, Ds, K, _ = G.decoder_spec(net).ssm
        per_seq = 6 * (Ds * Di * 4 + (K - 1) * Di * 4)   # 6 ssm layers, f32
        assert eng.state_bytes_per_seq == per_seq
        assert eng.state_bytes == 2 * per_seq
        assert eng.stats()["state_bytes"] == 2 * per_seq
        assert eng.varz_config()["state_bytes_per_seq"] == per_seq
        assert eng.requestz()["stats"]["state_bytes"] == 2 * per_seq
        assert telemetry.gauge("serving_state_bytes_per_seq",
                               labels={"engine": eng._name}).value == per_seq
        # two attention layers of one KV head of 8: K and V, float32
        assert eng.kv_bytes_per_token == 2 * 2 * 8 * 4
    assert telemetry.counter("serving_state_resets_total").value \
        == resets0 + 2
    records, _ = telemetry.profiler.iterations(t0, None)
    mine = [r for r in records if r.engine == eng._name]
    assert sum(r.state_resets for r in mine) == 2
    assert max(r.state_rows for r in mine) == 2
    assert "state_rows" in mine[0].as_dict()


def test_an_attention_only_engine_reports_no_state():
    lm = TransformerLM(vocab=50, units=16, hidden_size=32, num_layers=1,
                       num_heads=2, max_len=64, dropout=0.0)
    lm.initialize()
    lm(NDArray(jnp.ones((1, 4), jnp.int32)))
    t0 = time.monotonic()
    with ServingEngine(lm, max_batch=2, block_size=8, max_seq_len=64) as eng:
        eng.submit(onp.arange(5, dtype=onp.int32), 4).result(timeout=300)
        assert eng.state_bytes == 0
        assert eng._programs.recurrent_state == ()
        assert eng.varz_config()["prefix_cache"] is True
        from incubator_mxnet_tpu import telemetry

        records, _ = telemetry.profiler.iterations(t0, None)
        assert all(r.state_rows == 0 and r.state_resets == 0
                   for r in records if r.engine == eng._name)


# --- the kernel -------------------------------------------------------------- #
def _scan_inputs(N, T, S, Di=256, Ds=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        u=jax.random.normal(k[0], (N, T, Di)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (N, T, Di))),
        z=jax.random.normal(k[2], (N, T, Di)),
        Bm=jax.random.normal(k[3], (N, T, Ds)),
        Cm=jax.random.normal(k[4], (N, T, Ds)),
        A=-jnp.exp(0.3 * jax.random.normal(k[5], (Ds, Di))),
        D=jax.random.normal(k[6], (Di,)),
        state=jax.random.normal(k[7], (S, Ds, Di)))


@pytest.mark.parametrize("form", ["chunk_1xT", "chunk_reset", "step_Bx1"])
def test_selective_scan_kernel_xla_and_reference_agree(ref, form):
    """The Pallas kernel (interpret mode), the XLA path and the plain
    reference's loop over tokens, for one sequence of T tokens continuing a
    lane's row (or starting from zero) and for B lanes of one token."""
    if form == "step_Bx1":
        a, rows, reset = _scan_inputs(8, 1, 8), None, None
    else:
        a = _scan_inputs(1, 32, 4)
        rows = jnp.array([2], jnp.int32)
        reset = jnp.array([int(form == "chunk_reset")], jnp.int32)
    args = (a["u"], a["dt"], a["z"], a["Bm"], a["Cm"], a["A"], a["D"],
            a["state"])
    y_x, s_x = _scan.selective_scan(*args, rows=rows, reset=reset, impl="xla")
    y_p, s_p = _scan.selective_scan(*args, rows=rows, reset=reset,
                                    impl="pallas")
    onp.testing.assert_allclose(y_p, y_x, atol=2e-5, rtol=1e-5)
    onp.testing.assert_allclose(s_p, s_x, atol=2e-5, rtol=1e-5)
    if form == "chunk_1xT":
        for other in (0, 1, 3):     # the other lanes' rows, to the bit
            onp.testing.assert_array_equal(s_p[other], a["state"][other])
            onp.testing.assert_array_equal(s_x[other], a["state"][other])
    if form == "chunk_reset":
        # the reference's recurrence from a zero state, then D and the gate
        y = ref.recurrence(a["u"][0], a["dt"][0], a["A"].T, a["Bm"][0],
                           a["Cm"][0], "fp32")
        y = (y + a["D"] * a["u"][0]) * jax.nn.silu(a["z"][0])
        onp.testing.assert_allclose(y_x[0], y, atol=2e-5, rtol=1e-5)


def test_selective_scan_falls_back_for_odd_lengths():
    a = _scan_inputs(1, 5, 2)
    args = (a["u"], a["dt"], a["z"], a["Bm"], a["Cm"], a["A"], a["D"],
            a["state"])
    rows, reset = jnp.array([1], jnp.int32), jnp.array([0], jnp.int32)
    y_p, s_p = _scan.selective_scan(*args, rows=rows, reset=reset,
                                    impl="pallas")
    y_x, s_x = _scan.selective_scan(*args, rows=rows, reset=reset, impl="xla")
    onp.testing.assert_array_equal(y_p, y_x)
    onp.testing.assert_array_equal(s_p, s_x)
    with pytest.raises(ValueError):
        _scan.selective_scan(*args, impl="mosaic")


def test_a_closed_engine_lets_go_of_the_device(seeded):
    """`close()` drops the pools, the recurrent state, the gathered
    weights and the net: a `Request` handle that outlives its engine must
    not keep a model's memory alive through it (a benchmark holds
    thousands of handles while the reference takes the chip)."""
    net, _ = seeded
    eng = ServingEngine(net, max_batch=2, block_size=8, max_seq_len=64)
    handle = eng.submit(_prompts((6,))[0], 3)
    assert handle.result(timeout=300)
    progs = eng._programs
    assert progs.recurrent_state and all(progs.kv_pools[:2])
    assert progs._params is not None
    eng.close()
    assert handle._engine is eng
    assert progs.recurrent_state == () and progs.kv_pools == ((),) * 4
    assert progs._net is None and progs._params is None
    assert eng.state_bytes > 0          # what it held is still reported
    eng.close()                         # idempotent
