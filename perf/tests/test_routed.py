"""The routed configuration's part of the yardstick: `perf/work/routed.py`'s
counts against hand arithmetic at the published widths and against the
reference's own leaves, the control of `correct` at a test's size, the CPU
rehearsal of the cell, and the four readers where nothing is to read and
on a recorded excerpt of a real traced run."""
import json
import math
import os
from types import SimpleNamespace

import numpy as np
import pytest

from perf import compare, run, trace_reduce
from perf.tests import rehearse
from perf.work import hybrid, routed

CELL = "mimo-v2-flash.serve-mixed"
MIMO = run.load_json("configs", "mimo-v2-flash.json")
PEAK = run.load_json("peaks.json")["devices"]["TPU v5 lite"]
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_counts_at_the_published_widths_by_hand():
    assert routed.layers(MIMO) == [
        (4, False, False), (8, True, True), (8, True, True), (8, True, True),
        (8, True, True), (4, False, True), (8, True, True)]
    assert routed.layer_counts(MIMO) == {"full": 2, "window": 5,
                                         "routed": 6, "dense": 1}
    # attention: q 64 x 192 x 4096, k Hkv x 192 x 4096, v Hkv x 128 x 4096,
    # o 4096 x 64 x 128
    full = (64 * 192 + 4 * 192 + 4 * 128) * 4096 + 4096 * 64 * 128
    window = (64 * 192 + 8 * 192 + 8 * 128) * 4096 + 4096 * 64 * 128
    assert (full, window) == (89_128_960, 94_371_840)
    dense, router = 3 * 16384 * 4096, 256 * 4096
    assert routed.token_params(MIMO) == (2 * full + 5 * window + dense
                                         + 6 * router)
    assert routed.pair_params(MIMO) == 3 * 4096 * 2048 == 25_165_824
    # one decode token at a context of 1,000 with 3 pairs held here: the
    # head, the full layers' whole context, the window layers' 128
    work = dict(prompt_tokens=0, decode_tokens=1, output_tokens=1,
                prefill_context=0, decode_context=1000)
    win = dict(prefill_context=0, decode_context=128)
    assert routed.serve_flops(MIMO, work, win, 3) == (
        2 * routed.token_params(MIMO) + 2 * 3 * 25_165_824
        + 2 * 19072 * 4096 + 2 * 64 * 320 * (2 * 1000 + 5 * 128))
    # K and V: a full layer 4 x (192 + 128) x 2 B = 2,560 B a position, a
    # window layer 8 x 320 x 2 B = 5,120 B
    assert routed.paged_bytes(10, 0, MIMO) == 2 * 2560 * 10
    assert routed.paged_bytes(0, 10, MIMO) == 5 * 5120 * 10
    assert routed.paged_flops(7, 3, MIMO) == 2 * 64 * 320 * (2 * 7 + 5 * 3)
    slow = dict(hbm_bytes_per_s=1e18, flops_bf16=1e3)
    assert routed.paged_floor_seconds(10, 10, 7, 3, MIMO, slow) == \
        pytest.approx(routed.paged_flops(7, 3, MIMO) / 1e3)
    # the experts: a step of 96 lanes gives 6 layers' 16 experts 48 pairs a
    # layer; every expert with a pair streams its 50.3 MB once
    pairs = 6 * 48
    assert routed.experts_bytes(MIMO, pairs, programs=1) == 2 * (
        6 * 16 * 25_165_824 + pairs * 2 * 4096)
    assert routed.experts_bytes(MIMO, 5, programs=1) == 2 * (
        5 * 25_165_824 + 5 * 2 * 4096)      # no more experts than pairs
    # 4.83 GB at 819 GB/s: 5.9 ms a program, the floor of both programs
    assert 5.8e-3 < routed.experts_floor_seconds(MIMO, pairs, 1, PEAK) \
        < 6.0e-3
    # nothing of a block size, a tile or a ring enters
    assert set(routed.experts_bytes.__code__.co_varnames[:3]) == {
        "cfg", "pairs", "programs"}


def test_counts_agree_with_the_references_leaves():
    """Every matrix of `param_shapes` is counted once: by token (attention,
    the dense layer, the routers), by pair (an expert's three), or as the
    head; left over are the embedding (a gather), the norms' gains, the
    sink logits and the selection biases."""
    ref = run.load_file("reference", "mimo-v2-flash")
    shapes = ref.param_shapes(MIMO)
    total = sum(math.prod(v) for v in shapes.values())
    assert total == 3_429_955_392                  # 6.86 GB in bfloat16
    assert max(math.prod(v) for v in shapes.values()) == 16 * 2048 * 4096
    by_token = sum(math.prod(v) for k, v in shapes.items()
                   if k.endswith("_w") and "." in k)
    assert by_token == routed.token_params(MIMO)
    experts = sum(math.prod(v) for k, v in shapes.items()
                  if k.endswith("_e"))
    assert experts == 6 * 16 * routed.pair_params(MIMO) == 2_415_919_104
    small = sum(math.prod(v) for k, v in shapes.items()
                if k.endswith(("_g", "sink", "router_bias")))
    assert total == by_token + experts + 2 * 19072 * 4096 + small
    assert [layer[:2] + layer[3:] for layer in ref.layers_of(MIMO)] \
        == routed.layers(MIMO)
    assert ref.sizes(MIMO)["rope"] == 64 and ref.sizes(MIMO)["first"] == 0


def test_the_configuration_keeps_every_published_width():
    import json as _json

    for line in open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists("/opt/skills/guides/model-configs/"
                              "architectures.jsonl") else []:
        row = _json.loads(line)
        if row["name"] != "MiMo-V2-Flash":
            continue
        changed = {k for k, v in row["config"].items() if MIMO.get(k) != v}
        assert changed == set(MIMO["reduced"])
        assert MIMO["source"].startswith(row["source_url"])
    assert MIMO["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                               "moe_layer_freq", "n_routed_experts",
                               "vocab_size"]
    assert (MIMO["hidden_size"], MIMO["num_attention_heads"],
            MIMO["head_dim"], MIMO["v_head_dim"]) == (4096, 64, 192, 128)
    assert (MIMO["num_key_value_heads"], MIMO["swa_num_key_value_heads"],
            MIMO["sliding_window"]) == (4, 8, 128)
    assert (MIMO["intermediate_size"], MIMO["moe_intermediate_size"],
            MIMO["num_experts_per_tok"]) == (16384, 2048, 8)
    assert (MIMO["n_routed_experts"], MIMO["n_routed_experts_published"],
            MIMO["experts_held"]) == (16, 256, "0-15")
    assert MIMO["vocab_size"] * 8 == MIMO["vocab_size_published"] == 152576
    assert len(MIMO["hybrid_layer_pattern"]) == 7 \
        and MIMO["num_hidden_layers_published"] == 48
    # one whole period at the published 5 : 1 behind the dense layer
    assert MIMO["hybrid_layer_pattern"][1:] == [1, 1, 1, 1, 0, 1]
    # every leaf of the reference has its parameter
    ref = run.load_file("reference", "mimo-v2-flash")
    assert set(MIMO["program"]["param_map"]) == set(ref.param_shapes(MIMO))


def test_windowed_work_counts_what_a_window_sees():
    # one prompt of 300 in chunks of 256 and 44, then tokens at contexts
    # 301 and 302: under a window of 128
    requests = [(300, 0.1, [0.5, 0.6, 0.7])]
    got = routed.windowed_work(requests, 0.0, 1.0, 256, 128)
    assert got["decode_context"] == 2 * 128
    assert got["prefill_context"] == sum(min(q + 1, 128) for q in range(300))
    # a window wider than everything is the plain count
    from perf.work import served
    plain = served.count_work(requests, 0.0, 1.0, 256)
    wide = routed.windowed_work(requests, 0.0, 1.0, 256, 10_000)
    assert wide["decode_context"] == plain["decode_context"]
    assert wide["prefill_context"] == plain["prefill_context"]
    # only what falls in the span
    assert routed.windowed_work(requests, 0.55, 0.65, 256, 128) == dict(
        decode_context=128, prefill_context=0)


def _rec(t1, pairs, tokens, busiest):
    return SimpleNamespace(t1=t1, expert_pairs=pairs, expert_tokens=tokens,
                           expert_busiest=busiest)


def test_the_rings_counts_over_a_span():
    records = [_rec(0.5, 10, 60, 4), _rec(1.5, 300, 3648, 9),
               _rec(2.5, 50, 576, 6), SimpleNamespace(t1=1.7)]
    got = routed.ring_counts(records, 1.0, 3.0)
    assert got == {"pairs": 350, "tokens": 4224, "busiest": [9, 6],
                   "pairs_each": [300, 50]}
    assert routed.ring_counts(records, 5.0, 6.0) is None
    assert routed.ring_counts([SimpleNamespace(t1=1.0)], 0.0, 2.0) is None


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from incubator_mxnet_tpu.serving import engine

    real = engine.Request._deliver

    def altered(self, tok, now):
        # one token in seven comes out as its neighbour
        if len(self.tokens) % 7 == 3:
            tok = (tok + 1) % 640
        return real(self, tok, now)

    monkeypatch.setattr(engine.Request, "_deliver", altered)
    result = rehearse.run_tiny(CELL, seed=13, seconds=1.5)
    assert result["correct"] is False
    gap = {c["name"]: (c["value"], c["limit"]) for c in result["compared"]}
    value, limit = gap["served_logit_widest_gap"]
    assert value > limit


# what a test run can hold of the cell: the published hidden width (the
# control's gap grows with it: 0.28-0.57 at a quarter and a half of it,
# 1.42-1.55 at the cell's own size on the chip, PERF.md section 2), an
# eighth of the heads, of the feed-forwards' widths and of the experts, an
# eighteenth of the vocabulary's slice
SMALL = dict(hidden_size=4096, intermediate_size=2048, vocab_size=1024,
             num_attention_heads=8, num_key_value_heads=2,
             swa_num_key_value_heads=4, head_dim=96, v_head_dim=64,
             sliding_window=32, moe_intermediate_size=256,
             n_routed_experts=4, n_routed_experts_published=32,
             experts_held="0-3", num_experts_per_tok=4,
             max_position_embeddings=128)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_the_control_fails_and_the_stated_precision_passes(seed):
    """The fp8 reference in the program's place, held to the cell's limit:
    the token IT puts first lies further below the float32 reference's
    best than the limit; the token the bf16 reference (the precision the
    configuration states) puts first does not."""
    from perf import weights

    ref = run.load_file("reference", "mimo-v2-flash")
    cfg = dict(MIMO, **SMALL)
    limits = run.load_json("workloads", CELL + ".json")["limits"]
    w = weights.make(seed, ref.param_shapes(cfg))
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(0, cfg["vocab_size"], n, dtype=np.int32),
             rng.integers(0, cfg["vocab_size"], m, dtype=np.int32))
            for n, m in ((40, 30), (17, 60), (5, 90))]

    def widest(prec):
        gaps = [g for row in ref.served_gaps(w, rows, cfg, 128, control=prec)
                for g in row]
        assert len(gaps) == 30 + 60 + 90
        return compare.served(gaps, limits, {}, lambda *a: None)[0]

    fp8, bf16 = widest("fp8"), widest("bf16")
    assert fp8["value"] > fp8["limit"], fp8
    assert bf16["value"] <= bf16["limit"], bf16


def test_the_rehearsal_of_the_cell_is_correct():
    result = rehearse.run_tiny(CELL, seed=2**31 + 6, seconds=1.5, trace=1)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    # on the CPU no kernel runs, so the two rooflines say nothing; the
    # whole step's share, the router's spread and the nine readers that
    # know no model do
    assert {"step_mfu.serve.moe", "expert_load_imbalance"} \
        <= set(result["metrics"])
    assert "moe_experts_roofline" not in result["metrics"]
    assert "paged_attn_roofline.mixed" not in result["metrics"]
    assert {"batch_occupancy_pct", "sched_host_ms", "kv_pool_written_pct",
            "prefill_tokens_per_s"} <= set(result["metrics"])
    assert result["metrics"]["expert_load_imbalance"]["value"] >= 1.0


NEW = ("step_mfu.serve.moe", "moe_experts_roofline",
       "paged_attn_roofline.mixed", "expert_load_imbalance")


def test_the_readers_leave_their_metric_out_where_nothing_is_to_read():
    """A program without the kernels, the scopes or the ring's fields (the
    parent commit has none of them) gives None, not an error."""
    empty = {"ops": {"fusion.1": {"seconds": 1.0, "count": 1, "label": ""}},
             "modules": {}}
    old = (  # a ring whose records know nothing of experts
        [SimpleNamespace(t0=0.0, t1=0.5, causes=(0.0,))], True, ("x",))
    record = dict(trace=empty, trace_t0=0.0, trace_t1=1.0, t_open=0.0,
                  t_close=1.0, window_s=1.0, chunk=512, requests=[],
                  config=MIMO, peak=PEAK, ring=old,
                  work=dict(output_tokens=5, prompt_tokens=0, decode_tokens=5,
                            prefill_context=0, decode_context=50))
    for name in NEW:
        assert run.load_file("metrics", name).read(record) is None, name
        if "roofline" in name:
            assert run.load_file("metrics", name).read({}) is None
    assert run.load_file("metrics", "step_mfu.serve.moe").read({}) is None


def test_the_readers_on_counts_by_hand():
    """The ring's three counts through the two readers that need no
    trace."""
    ring = ([SimpleNamespace(t0=0.0, t1=0.2, causes=(0.0,), expert_pairs=288,
                             expert_tokens=576, expert_busiest=9),
             SimpleNamespace(t0=0.2, t1=0.4, causes=(0.0,), expert_pairs=1824,
                             expert_tokens=3648, expert_busiest=38)],
            True, ("x",))
    work = dict(output_tokens=96, prompt_tokens=512, decode_tokens=96,
                prefill_context=0, decode_context=96 * 1000)
    record = dict(t_open=0.0, t_close=1.0, window_s=1.0, chunk=512,
                  requests=[], config=MIMO, peak=PEAK, ring=ring, work=work)
    got = run.load_file("metrics", "expert_load_imbalance").read(record)
    # 96 slots (16 experts x 6 layers): 9 over 3 pairs a slot, 38 over 19
    assert got == pytest.approx((9 * 96 / 288 + 38 * 96 / 1824) / 2)
    need = routed.serve_flops(MIMO, work, dict(decode_context=0,
                                               prefill_context=0), 2112)
    got = run.load_file("metrics", "step_mfu.serve.moe").read(record)
    assert got == pytest.approx(100 * need / 197e12)


def test_the_readers_on_a_recorded_excerpt_of_the_real_run():
    """45 ms of the cell's traced run on the chip (PERF_TRACE_EXCERPT, my
    chip run, PR 36, seed 3900000103): one decode step whole and parts of
    the chunks around it.  Both kernels are found by their names, their
    time is the plain sum of their events, and each reader divides the
    floor `perf/work/routed.py` gives for the slice's work by it."""
    with open(os.path.join(DATA, "recorded_routed.json")) as f:
        planes = json.load(f)["planes"]
    reduced = trace_reduce.reduce(planes, chips=1)
    ops = planes["devices"][sorted(planes["devices"])[0]]["ops"]

    def plain_sum(kernel):
        hit = [d for name, _l, _s, d in ops if name.split(".")[0] == kernel]
        return sum(hit), len(hit)

    moe_s, moe_n = plain_sum("moe_experts")
    paged_s, paged_n = plain_sum("paged_attention")
    assert (moe_n, paged_n) == (12, 10)     # 6 a program; 7 a step, 2 a chunk
    assert hybrid.kernel_time(reduced, "paged_attention_window") is None
    got = hybrid.kernel_time(reduced, "moe_experts")
    assert got["count"] == 12 and got["seconds"] <= moe_s + 1e-12
    assert hybrid.programs_run(reduced, "jit_serving_step(") == 1
    assert hybrid.programs_run(reduced, "jit_serving_prefill_chunk(") == 1
    families = [name for name, _s in reduced["breakdown"]["device_ops"]]
    assert any(f.startswith("moe_experts ") for f in families)

    # a slice that held one prompt of 600 (chunks of 512 and 88) and two
    # decode tokens at contexts 601 and 602; its two iterations' counts
    requests = [(600, 0.1, [0.5, 0.6, 0.7])]
    ring = ([SimpleNamespace(t0=0.0, t1=0.4, causes=(0.0,), expert_pairs=1536,
                             expert_tokens=3072, expert_busiest=40),
             SimpleNamespace(t0=0.4, t1=0.8, causes=(0.0,), expert_pairs=300,
                             expert_tokens=600, expert_busiest=9)],
            True, ("x",))
    record = dict(trace=reduced, trace_t0=0.0, trace_t1=1.0, t_open=0.0,
                  chunk=512, requests=requests, config=MIMO, peak=PEAK,
                  ring=ring)
    floor = routed.experts_floor_seconds(MIMO, 1836, 2, PEAK)
    got = run.load_file("metrics", "moe_experts_roofline").read(record)
    assert got == pytest.approx(
        100 * floor / hybrid.kernel_time(reduced, "moe_experts")["seconds"])
    # full layers: the chunks read 512 + 600, the steps 601 + 602, every
    # prompt token attends its prefix and every decode token its context;
    # window layers: the steps' lanes alone, 128 positions each
    floor = routed.paged_floor_seconds(
        512 + 600 + 1203, 256, 600 * 601 // 2 + 1203, 256, MIMO, PEAK)
    got = run.load_file("metrics", "paged_attn_roofline.mixed").read(record)
    assert got == pytest.approx(
        100 * floor
        / hybrid.kernel_time(reduced, "paged_attention")["seconds"])
